"""The benchmark's own copies of the program's arithmetic, checked against
the program when copied: the paper bit accounting (Eq. 6-8), Eq. 1's
per-leaf k, the pair-mask support, and the model FLOP counts against XLA's
cost analysis."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_testing import REPO

from chipbench import accounting, reference, spec


def _tiny_round(dropped):
    from repro.core import fedavg
    from repro.core.types import FedConfig, SecureAggConfig, THGSConfig
    from repro.models.paper_models import PAPER_MODELS, cross_entropy_loss

    model = PAPER_MODELS["mnist_mlp"]
    params = model.init(jax.random.key(0))
    fed = FedConfig(n_clients=5, clients_per_round=5, local_steps=2,
                    local_batch=8, local_lr=0.05, rounds=10)
    key = jax.random.split(jax.random.key(1), 2)
    x = jax.random.normal(key[0], (5, 2, 8, 28, 28, 1))
    y = jax.random.randint(key[1], (5, 2, 8), 0, 10)
    state = fedavg.init_state(params, fed)
    state = fedavg.run_round(
        state, {c: (x[c], y[c]) for c in range(5)},
        cross_entropy_loss(model), fed,
        THGSConfig(s0=0.05, alpha=0.9, s_min=0.01, time_varying=False),
        SecureAggConfig(mask_ratio=0.01, seed=7), dropped=dropped)
    return state.comm_log[-1]


@pytest.mark.parametrize("dropped", [(), (3,)])
def test_accounting_copy_equals_round_record(dropped):
    from repro.sim.ledger import CommLedger

    rec = _tiny_round(dropped)
    facts = {"ks": rec.ks, "k_masks": rec.k_masks,
             "n_clients": rec.n_clients, "n_survivors": rec.n_survivors,
             "model_size": rec.model_size}
    assert accounting.upload_bits(rec.ks, rec.k_masks, rec.n_clients,
                                  rec.n_survivors) == rec.upload_bits
    assert accounting.dense_bits(rec.model_size,
                                 rec.n_clients) == rec.dense_upload_bits
    ledger = CommLedger()
    ledger.record(rec)
    assert accounting.upload_vs_dense([facts]) == pytest.approx(
        ledger.totals("paper")["upload_vs_dense"], rel=1e-15)


@pytest.mark.parametrize("model", ["mnist_mlp", "cifar_mlp", "cifar_vgg16"])
def test_eq1_copy_equals_schedule(model):
    from repro.core import schedules
    from repro.core.types import THGSConfig
    from repro.models.paper_models import PAPER_MODELS

    shapes = jax.eval_shape(PAPER_MODELS[model].init, jax.random.key(0))
    sizes = [x.size for x in jax.tree_util.tree_leaves(shapes)]
    thgs = {"s0": 0.05, "alpha": 0.9, "s_min": 0.01, "k_levels": 16}
    assert reference.eq1_ks(thgs, sizes) == schedules.leaf_ks(
        THGSConfig(s0=0.05, alpha=0.9, s_min=0.01, time_varying=False),
        sizes)


def test_mask_copy_equals_program():
    from repro.core import masks
    from repro.core.types import SecureAggConfig

    sa = SecureAggConfig(mask_ratio=0.01, seed=2147483659)
    secagg = {"enabled": True, "mask_ratio": 0.01}
    for size, n in ((4_718_592, 5), (1_000, 4), (10, 5)):
        assert reference.k_mask(secagg, size, n) == sa.k_mask_for(size, n)
    for a, b, r, leaf in ((0, 3, 0, 1), (7, 2, 5, 0), (4, 9, 11, 53)):
        seed = reference.pair_seed(sa.seed, a, b, r)
        assert seed == masks.pair_seed(sa, a, b, r)
        idx, vals = reference.pair_mask(seed, leaf, 257, 100_003, sa.p, sa.q)
        want = masks.pair_mask(sa, a, b, r, leaf, 100_003, 257)
        np.testing.assert_array_equal(idx, np.asarray(want.indices))
        sign = 1.0 if a < b else -1.0
        np.testing.assert_array_equal(sign * vals, np.asarray(want.values))


# XLA counts every elementwise operation too (bias adds, BatchNorm, ReLU,
# pooling compares); the model-FLOP count leaves those out, so XLA's figure
# is higher by their share: 0.04% for the MLP, 0.7% for VGG16.
FLOP_GAP = {"cifar_mlp": 1e-3, "cifar_vgg16": 1e-2}


@pytest.mark.parametrize("name", sorted(FLOP_GAP))
def test_flops_agree_with_xla_cost_analysis(name):
    ref = spec.load_module(REPO / "chipbench" / "configs" / f"{name}.py")
    params = jax.eval_shape(ref.init, jax.random.key(0))
    x = jax.ShapeDtypeStruct((1, 32, 32, 3), jnp.float32)
    cost = jax.jit(ref.forward).lower(params, x).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    ours = ref.forward_flops(1)
    assert ours <= cost["flops"]
    assert (cost["flops"] - ours) / cost["flops"] < FLOP_GAP[name]
    assert ref.forward_flops(50) == 50 * ours
