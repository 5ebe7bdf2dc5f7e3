"""``correct`` comes out false when the timed path is broken underneath
(a run of a tiny CPU cell with one fault planted in the program), and
for the control: the plain reference computed in bfloat16 put in the
program's place. ``tiny_drop`` (an MLP) compares the free-running numbers;
``tiny_cnn_drop`` (conv + BatchNorm) only the teacher-forced ones and the
probe's (each client's step-1 loss)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from chipbench_testing import (DATA, any_device, jax_settings,  # noqa: F401
                               load_script, tiny_argv, tiny_cnn_model)

from chipbench import compare, harness, spec

run_script = load_script("run")


def _unchanged(monkeypatch):
    """A round that returns the params it was given."""
    from repro.sim import engine

    real = engine.run_round

    def frozen(state, *a, **kw):
        params = state.params
        out = real(state, *a, **kw)
        out.params = params
        return out

    monkeypatch.setattr(engine, "run_round", frozen)


def _half_batch(monkeypatch):
    """Local SGD's loss over the first half of each batch."""
    from repro.sim import engine

    real = engine.cross_entropy_loss

    def half(model):
        full = real(model)

        def loss_fn(params, batch):
            x, y = batch
            n = x.shape[0] // 2
            return full(params, (x[:n], y[:n]))
        return loss_fn

    monkeypatch.setattr(engine, "cross_entropy_loss", half)


def _lost_upload(monkeypatch):
    """The decode leaves the first client's stream out."""
    from repro.core import streams

    real = streams._flatten_round_stream

    def lossy(batch, alive, weights, extra):
        batch = batch._replace(values=batch.values.at[0].set(0.0))
        return real(batch, alive, weights, extra)

    monkeypatch.setattr(streams, "_flatten_round_stream", lossy)


def _no_recovery(monkeypatch):
    """Dropped clients' masks are not cancelled (recovery streams zero)."""
    from repro.core import streams

    real = streams.dropout_cancel_streams_seeded

    def silent(*a, **kw):
        out = real(*a, **kw)
        return out._replace(values=jnp.zeros_like(out.values))

    monkeypatch.setattr(streams, "dropout_cancel_streams_seeded", silent)


def _sgd_bf16(monkeypatch):
    """Local SGD's forward and backward passes in bfloat16 (the loss casts
    params and inputs); the update and the aggregation stay float32."""
    from repro.sim import engine

    real = engine.cross_entropy_loss

    def bf16(model):
        full = real(model)

        def loss_fn(params, batch):
            x, y = batch
            low = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16),
                                         params)
            return full(low, (x.astype(jnp.bfloat16), y))
        return loss_fn

    monkeypatch.setattr(engine, "cross_entropy_loss", bf16)


FAULTS = {"unchanged": _unchanged, "half_batch": _half_batch,
          "lost_upload": _lost_upload, "no_recovery": _no_recovery}
CONV_FAULTS = dict(FAULTS, sgd_bf16=_sgd_bf16)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_round_is_not_correct(fault, monkeypatch, capsys,
                                     jax_settings):
    jax.clear_caches()          # the planted fault has to be traced afresh
    FAULTS[fault](monkeypatch)
    doc = run_script.main(tiny_argv(seed=31), bench_file=DATA /
                          "BENCHMARK.json", root=DATA, chip_check=any_device)
    assert doc["correct"] is False
    assert any(c["value"] == "nan" or c["value"] > c["limit"]
               for c in doc["checks"].values())


def test_control_is_not_correct(jax_settings):
    cell = spec.resolve("tiny_drop", DATA / "BENCHMARK.json", DATA)
    harness.configure_jax(cell, harness.spec.CHECKOUT)
    run = harness.run_program(cell, 2147483701, 0.0, 0.0)
    sound = harness.check(cell, 2147483701, run)
    control = harness.check(cell, 2147483701, run, dtype="bfloat16")
    assert compare.verdict(sound, cell.limits)
    assert not compare.verdict(control, cell.limits)


@pytest.mark.parametrize("fault", sorted(CONV_FAULTS))
def test_broken_conv_round_is_not_correct(fault, monkeypatch, tiny_cnn_model,
                                          jax_settings):
    jax.clear_caches()
    CONV_FAULTS[fault](monkeypatch)
    doc = run_script.main(tiny_argv("tiny_cnn_drop", seed=31),
                          bench_file=DATA / "BENCHMARK.json", root=DATA,
                          chip_check=any_device)
    assert doc["correct"] is False
    assert any(c["value"] == "nan" or c["value"] > c["limit"]
               for c in doc["checks"].values())


def test_conv_control_is_not_correct(tiny_cnn_model, jax_settings):
    """The sound conv run is correct and the control is not; the harness
    puts local SGD back once the compared rounds are recorded, and records
    no other round."""
    from repro.core import fedavg

    update = fedavg.batched_client_update
    cell = spec.resolve("tiny_cnn_drop", DATA / "BENCHMARK.json", DATA)
    harness.configure_jax(cell, harness.spec.CHECKOUT)
    run = harness.run_program(cell, 2147483701, 0.0, 0.0)
    assert fedavg.batched_client_update is update
    assert len(run.prog.rounds) == len(run.compare_rounds) >= 3
    sound = harness.check(cell, 2147483701, run)
    control = harness.check(cell, 2147483701, run, dtype="bfloat16")
    assert compare.verdict(sound, cell.limits)
    assert not compare.verdict(control, cell.limits)


def test_probe_leaves_the_round_unchanged(monkeypatch, tiny_cnn_model,
                                          jax_settings):
    """The probe reads each client's step-1 loss through the round's own
    compiled local SGD: a run traces and lowers no more programs with it
    than without it, every compared round's losses, deltas, params and
    residuals are bit-identical with it on and off, and the wrap of local
    SGD is gone before the first window round."""
    from repro.core import fedavg

    update = fedavg.batched_client_update
    seen = []
    call = harness.Driver.__call__

    def hook(drv, r, info):
        call(drv, r, info)
        seen.append((drv.phase, fedavg.batched_client_update is update))

    monkeypatch.setattr(harness.Driver, "__call__", hook)
    cell = spec.resolve("tiny_cnn_drop", DATA / "BENCHMARK.json", DATA)
    harness.configure_jax(cell, harness.spec.CHECKOUT)
    probe = harness.probe_sgd
    runs = {}
    for on in (False, True):
        monkeypatch.setattr(harness, "probe_sgd",
                            probe if on else lambda *a: {})
        jax.clear_caches()
        runs[on] = harness.run_program(cell, 2147483659, 0.0, 0.0)
        runs[on].programs = update._cache_size()
    off, on = runs[False], runs[True]
    assert on.programs == off.programs
    for k in ("traces", "lowerings"):
        assert on.compiles_setup[k] == off.compiles_setup[k], k
    assert off.prog.losses == on.prog.losses
    for a, b in zip(off.prog.rounds, on.prog.rounds):
        assert set(b) - set(a) == {"step1"}
        for key in a:
            for name in a[key]:
                np.testing.assert_array_equal(a[key][name], b[key][name])
        assert np.all(np.isfinite(b["step1"])) and b["step1"].shape == (4,)
    window = [same for phase, same in seen if phase == "window"]
    assert window and all(window)
    assert fedavg.batched_client_update is update
