"""The comparison's arithmetic on made-up outputs, and the window's
end-to-end arithmetic."""
import math

import numpy as np
import pytest

import chipbench_testing  # noqa: F401  (puts the checkout on sys.path)

from chipbench import compare, harness, reference
from chipbench.reference import Outputs


def _outputs(scale=1.0, bias_update=1e-9, loss=(2.0, 1.0, 0.5)):
    """One compared round; its record's deltas are the update, each
    client's step-1 loss the first of ``loss``."""
    rng = np.random.default_rng(0)
    p0 = {"a": rng.normal(size=100), "b": rng.normal(size=10),
          "c": np.zeros(5)}
    upd = {"a": 0.1 * rng.normal(size=100), "b": 0.1 * rng.normal(size=10),
           "c": np.full(5, bias_update)}
    p1 = {k: p0[k] + scale * upd[k] for k in p0}
    res = {k: np.stack([upd[k], -upd[k]]) for k in p0}
    rounds = [{"deltas": {k: np.stack([upd[k], upd[k]]) for k in p0},
               "params": p1, "residuals": res, "step1": np.full(2, loss[0])}]
    return Outputs(losses=list(loss), params0=p0, residuals=res,
                   rounds=rounds)


def _numbers(prog, ref):
    """The numbers with ``ref``'s own rounds as the teacher-forced ones."""
    return compare.numbers(prog, ref, ref.rounds)


def test_identical_sides_read_zero():
    nums = _numbers(_outputs(), _outputs())
    assert set(nums) == set(compare.NUMBERS)
    assert all(v == 0.0 for v in nums.values())


def test_unchanged_state_reads_one():
    ref = _outputs()
    frozen = _outputs(scale=0.0)
    nums = _numbers(frozen, ref)
    assert nums["update1"] == pytest.approx(1.0)
    assert nums["change_n"] == pytest.approx(1.0)
    assert nums["agg_update"] == pytest.approx(1.0)


def test_negligible_leaf_is_left_out():
    """A leaf whose reference update is under 1e-3 of the median leaf's
    does not count, however far the program's is from it."""
    ref = _outputs(bias_update=1e-9)
    prog = _outputs(bias_update=3e-9)
    nums = _numbers(prog, ref)
    for k in ("update1", "agg_update", "agg_residual_median",
              "sgd_delta_median"):
        assert nums[k] == 0.0, k


def test_forced_gaps_and_nan():
    """The teacher-forced numbers read each side's round record: doubled
    deltas or residuals read 1 on the larger leaf and less on the smaller,
    which is scaled by the median leaf; a NaN in the params fails."""
    ref = _outputs()
    prog = _outputs()
    for key in ("deltas", "residuals"):
        prog.rounds[0][key] = {k: 2 * v
                               for k, v in ref.rounds[0][key].items()}
    nums = _numbers(prog, ref)
    assert 0.5 < nums["agg_residual_median"] < 1.0
    assert 0.5 < nums["sgd_delta_median"] < 1.0
    assert nums["agg_update"] == 0.0
    prog.rounds[0]["params"] = dict(prog.rounds[0]["params"],
                                    a=np.full(100, np.nan))
    nums = _numbers(prog, ref)
    assert math.isnan(nums["agg_update"])
    assert math.isnan(nums["agg_update_median"])
    assert not compare.verdict(nums, {"agg_update_median": 1.0})


def test_probe_gaps_and_nan():
    """``sgd_step1`` reads each client's step-1 loss, worst client; it
    reads nothing of the later steps, and a NaN fails."""
    ref = _outputs(loss=(2.0, 1.0, 0.5))
    prog = _outputs(loss=(2.0, 1.1, 0.5))
    prog.rounds[0]["step1"][1] = 2.2
    nums = _numbers(prog, ref)
    assert nums["sgd_step1"] == pytest.approx(0.1)
    assert nums["sgd_delta_median"] == 0.0
    assert not compare.verdict(nums, {"sgd_step1": 0.05})
    assert compare.verdict(nums, {"sgd_step1": 0.2})
    prog.rounds[0]["step1"][0] = np.nan
    nums = _numbers(prog, ref)
    assert math.isnan(nums["sgd_step1"])
    assert not compare.verdict(nums, {"sgd_step1": 1.0})


def test_loss_gaps_and_nan():
    ref = _outputs(loss=(2.0, 1.0, 0.5))
    prog = _outputs(loss=(2.0, 1.1, float("nan")))
    nums = _numbers(prog, ref)
    assert nums["loss1"] == 0.0 and math.isnan(nums["loss"])
    assert not compare.verdict(nums, {"loss": 1.0})
    assert compare.verdict(nums, {"loss1": 1e-9})


@pytest.mark.parametrize("k", [1, 2, 3, 7, 50, 200])
def test_reference_top_k_breaks_ties_to_the_lowest_index(k):
    """The reference's top-k sends what ``lax.top_k`` picks, ties too."""
    import jax

    mag = np.random.default_rng(k).integers(0, 20, 200).astype(np.float32)
    want = np.asarray(jax.lax.top_k(mag, k)[1])
    assert sorted(reference.top_k(mag, k).tolist()) == sorted(want.tolist())
    assert sorted(reference.top_k(np.array([1, 3, 2, 3, 3, .5]), 2)
                  .tolist()) == [1, 3]


def test_a_cell_must_compare_something():
    assert not compare.verdict(dict.fromkeys(compare.NUMBERS, 0.0),
                               {"readings": {}})


def test_nearest_rank_percentile():
    values = list(range(1, 101))
    assert harness.nearest_rank(values, 0.9) == 90
    assert harness.nearest_rank([3.0], 0.9) == 3.0
    assert harness.nearest_rank([5, 1, 4, 2, 3], 0.9) == 5


def test_block_dropouts_give_every_seed_the_same_mix():
    traffic = {"dropout_blocks": [0, 1, 1, 2]}
    cohort = [1, 3, 4, 7, 9]
    for seed in (1, 2147483701, 2**31 + 5):
        draw = harness.block_dropouts(traffic, seed)
        counts = [len(draw(r, cohort, min_survivors=3)) for r in range(12)]
        for b in range(3):
            assert sorted(counts[4 * b:4 * b + 4]) == [0, 1, 1, 2]
        assert all(set(draw(r, cohort)) <= set(cohort) for r in range(12))
        assert draw(5, cohort) == draw(5, cohort)
    assert harness.block_dropouts({"dropout_rate": 0.2}, 1) is None
    with pytest.raises(ValueError, match="cannot drop"):
        draw = harness.block_dropouts({"dropout_blocks": [3]}, 1)
        draw(0, cohort, min_survivors=3)
