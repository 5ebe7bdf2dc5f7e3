"""Hierarchical (tree) aggregation == flat aggregation, bit-exact (§13).

The tree decode partitions the padded dense buffer into contiguous index
ranges — one sub-aggregator per range — and combines partials by pure
concatenation, so for ANY partition every output position folds the exact
same contributions in the exact same order as the flat fused scatter-add.
This suite pins that property where it could silently break:

  * arbitrary uneven partitions (group sizes 1..n, randomly drawn cuts);
  * secure aggregation with arbitrary survivor subsets >= the Shamir
    threshold (Bonawitz dropout recovery streams route by range too);
  * every wire codec (the codec round trip happens at encode; decode is
    codec-agnostic);
  * the full round: run_round(topology='tree') vs 'flat' — params,
    residuals and the CommLedger facts identical.

The partition/dropout parity properties run as hypothesis property tests
when hypothesis is installed, and fall back to a seeded deterministic sweep
over the same case space otherwise (tier-1 containers ship without it).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:  # dev-only dep; the seeded sweep below keeps coverage without it
    from hypothesis import given, settings, strategies as st
except ImportError:
    st = None

from repro.core import streams as se
from repro.core.fedavg import init_state, run_round
from repro.core.types import FedConfig, SecureAggConfig, THGSConfig
from repro.secagg import RoundProtocol

THGS = THGSConfig(s0=0.2, alpha=0.9, s_min=0.05, time_varying=False)


def _random_splits(rng, padded: int) -> tuple:
    """Arbitrary monotone boundaries (0, ..., padded): uneven group sizes,
    including width-1 ranges."""
    n_cuts = int(rng.integers(0, min(5, padded - 1) + 1))
    cuts = rng.choice(np.arange(1, padded), size=n_cuts, replace=False)
    return (0, *sorted(int(c) for c in cuts), padded)


# --------------------------------------------------- parity case generators
def _check_partition_case(C, nb, m, k, seed, splits):
    """Core property: weighted tree decode == flat decode, bit for bit —
    any partition, any C/nb/k, negative values, duplicate indices (the
    -0.0 dump-slot property rides on this)."""
    key = jax.random.key(seed)
    idx = jax.random.randint(key, (C, nb, k), 0, nb * m, dtype=jnp.int32)
    vals = jax.random.normal(jax.random.fold_in(key, 1), (C, nb, k))
    weights = jax.random.uniform(jax.random.fold_in(key, 2), (C,),
                                 minval=0.1, maxval=3.0)
    stb = se.StreamBatch(indices=idx, values=vals)
    flat = se.decode_sum_blocks(stb, nb, m, weights=weights)
    tree = se.decode_sum_tree(stb, nb, m, splits=splits, weights=weights)
    np.testing.assert_array_equal(np.asarray(flat), np.asarray(tree))


def _check_secagg_dropout_case(C, size, k, seed, mask_ratio, survivors,
                               splits):
    """Core property: masked round, survivor subset >= Shamir t — the
    Bonawitz recovery streams join the round stream before range routing,
    so the tree decode cancels masks exactly like flat."""
    sa = SecureAggConfig(mask_ratio=mask_ratio, threshold=0.6, seed=seed)
    participants = list(range(C))
    proto = RoundProtocol.setup(sa, participants, round_t=0)
    pair_seeds, pair_signs = proto.pair_seed_matrix()
    k_mask = sa.k_mask_for(size, C)
    key = jax.random.key(seed)
    grads = jax.random.normal(key, (C, size))
    residuals = 0.1 * jax.random.normal(jax.random.fold_in(key, 1), (C, size))
    weights = jax.random.uniform(jax.random.fold_in(key, 2), (C,),
                                 minval=0.5, maxval=2.0)
    stb, _ = se.encode_leaf_batch(
        grads, residuals, k=k, nb=1, m=size, size=size,
        pair_seeds=pair_seeds, pair_signs=pair_signs, k_mask=k_mask,
        mask_p=sa.p, mask_q=sa.q, leaf_id=0, weights=weights)

    assert len(survivors) >= proto.t
    dropped = sorted(set(participants) - set(survivors))
    alive = jnp.asarray([c in survivors for c in participants], bool)
    rec_seeds = (proto.recover_seeds(sorted(survivors), dropped) if dropped
                 else pair_seeds)
    flat = se.decode_leaf_batch(
        stb, nb=1, m=size, size=size, alive=alive, pair_seeds=rec_seeds,
        pair_signs=pair_signs, k_mask=k_mask, mask_p=sa.p, mask_q=sa.q,
        leaf_id=0)
    tree = se.decode_leaf_tree(
        stb, nb=1, m=size, size=size, splits=splits, alive=alive,
        pair_seeds=rec_seeds, pair_signs=pair_signs, k_mask=k_mask,
        mask_p=sa.p, mask_q=sa.q, leaf_id=0)
    np.testing.assert_array_equal(np.asarray(flat), np.asarray(tree))


# ------------------------------------------------------------ decode parity
def test_tree_splits_shapes():
    assert se.tree_splits(10, 3) == (0, 4, 7, 10)
    assert se.tree_splits(10, 1) == (0, 10)
    assert se.tree_splits(4, 9) == (0, 1, 2, 3, 4)   # clamped to padded
    assert se.tree_splits(7, 0) == (0, 7)            # clamped to >= 1
    with pytest.raises(ValueError):
        se.decode_sum_tree(
            se.StreamBatch(indices=jnp.zeros((1, 1, 1), jnp.int32),
                           values=jnp.zeros((1, 1, 1), jnp.float32)),
            1, 8, splits=(0, 9))                     # boundary != padded


@pytest.mark.parametrize("case_seed", range(12))
def test_tree_decode_bitexact_partition_sweep(case_seed):
    """Seeded sweep over the partition-parity case space (always runs; the
    hypothesis twin below explores it adversarially when available)."""
    rng = np.random.default_rng([0xBEEF, case_seed])
    C = int(rng.integers(1, 6))
    nb = int(rng.integers(1, 4))
    m = int(rng.integers(2, 25))
    k = int(rng.integers(1, 2 * m + 1))
    splits = _random_splits(rng, nb * m)
    _check_partition_case(C, nb, m, k, int(rng.integers(0, 2**31)), splits)


@pytest.mark.parametrize("case_seed", range(8))
def test_tree_decode_bitexact_secagg_dropout_sweep(case_seed):
    """Seeded sweep over the secagg-dropout parity case space."""
    rng = np.random.default_rng([0xD00D, case_seed])
    C = int(rng.integers(2, 7))
    size = int(rng.integers(8, 97))
    k = int(rng.integers(1, size + 1))
    mask_ratio = float(rng.choice([0.05, 0.2]))
    sa = SecureAggConfig(mask_ratio=mask_ratio, threshold=0.6)
    t = sa.t_for(C)
    n_surv = int(rng.integers(t, C + 1))
    survivors = sorted(int(c) for c in
                       rng.choice(C, size=n_surv, replace=False))
    splits = _random_splits(rng, size)
    _check_secagg_dropout_case(C, size, k, int(rng.integers(0, 1000)),
                               mask_ratio, survivors, splits)


if st is not None:
    def _draw_splits(data, padded: int) -> tuple:
        n_cuts = data.draw(st.integers(0, min(5, padded - 1)), label="n_cuts")
        cuts = data.draw(
            st.lists(st.integers(1, padded - 1), min_size=n_cuts,
                     max_size=n_cuts, unique=True), label="cuts")
        return (0, *sorted(cuts), padded)

    @given(data=st.data())
    @settings(max_examples=20, deadline=None)
    def test_tree_decode_bitexact_arbitrary_partitions(data):
        C = data.draw(st.integers(1, 5), label="C")
        nb = data.draw(st.integers(1, 3), label="nb")
        m = data.draw(st.integers(2, 24), label="m")
        k = data.draw(st.integers(1, 2 * m), label="k")
        seed = data.draw(st.integers(0, 2**31 - 1), label="seed")
        splits = _draw_splits(data, nb * m)
        _check_partition_case(C, nb, m, k, seed, splits)

    @given(data=st.data())
    @settings(max_examples=15, deadline=None)
    def test_tree_decode_bitexact_secagg_dropout(data):
        C = data.draw(st.integers(2, 6), label="C")
        size = data.draw(st.integers(8, 96), label="size")
        k = data.draw(st.integers(1, size), label="k")
        seed = data.draw(st.integers(0, 1000), label="seed")
        ratio = data.draw(st.sampled_from([0.05, 0.2]), label="ratio")
        t = SecureAggConfig(mask_ratio=ratio, threshold=0.6).t_for(C)
        n_surv = data.draw(st.integers(t, C), label="n_surv")
        survivors = sorted(
            data.draw(st.permutations(list(range(C))),
                      label="perm")[:n_surv])
        splits = _draw_splits(data, size)
        _check_secagg_dropout_case(C, size, k, seed, ratio, survivors,
                                   splits)


@pytest.mark.parametrize("codec", ["f32", "int8", "int4", "1bit"])
def test_tree_decode_bitexact_all_codecs(codec):
    """The codec round trip happens at encode (quantize -> pack -> unpack ->
    dequantize); the decode sees f32 streams, so tree == flat holds per
    codec too."""
    C, size, k = 4, 192, 8
    key = jax.random.key(3)
    grads = jax.random.normal(key, (C, size))
    residuals = 0.1 * jax.random.normal(jax.random.fold_in(key, 1), (C, size))
    stb, _ = se.encode_leaf_batch(
        grads, residuals, k=k, nb=1, m=size, size=size, codec=codec)
    flat = se.decode_leaf_batch(stb, nb=1, m=size, size=size)
    for splits in [(0, size), (0, 1, size), (0, 7, 61, 62, size),
                   se.tree_splits(size, 13)]:
        tree = se.decode_leaf_tree(stb, nb=1, m=size, size=size,
                                   splits=splits)
        np.testing.assert_array_equal(np.asarray(flat), np.asarray(tree))


@pytest.mark.parametrize("seed", [0, 1])
def test_tree_decode_bitexact_pallas(seed):
    """The Pallas decode (interpret mode here) sorts the stream and groups it
    in chunk windows global to the sorted stream, so tree == flat holds on
    it bit for bit too: heavy duplicates across a tile boundary, negative
    values, recovery slots, a dropped client's gate, and uneven splits that
    cut tiles part-way. The XLA-path cases above keep their dump slot."""
    C, tile = 5, 64 * 128
    size, k, k_rec = 3 * tile + 700, 900, 300
    key = jax.random.key(seed)
    hot = jax.random.randint(key, (C, 1, k // 2), tile - 200, tile + 200)
    wide = jax.random.randint(jax.random.fold_in(key, 1),
                              (C, 1, k - k // 2), 0, size)
    stb = se.StreamBatch(
        indices=jnp.concatenate([hot, wide], axis=-1),
        values=jax.random.normal(jax.random.fold_in(key, 2), (C, 1, k)))
    extra = se.StreamBatch(
        indices=jax.random.randint(jax.random.fold_in(key, 3),
                                   (C * C, 1, k_rec), tile - 300, size),
        values=-jax.random.normal(jax.random.fold_in(key, 4),
                                  (C * C, 1, k_rec)))
    alive = jnp.asarray([True, False, True, True, True])
    weights = jax.random.uniform(jax.random.fold_in(key, 5), (C,),
                                 minval=0.5, maxval=2.0)
    kw = dict(alive=alive, weights=weights, extra=extra)
    flat = se.decode_sum_blocks(stb, 1, size, use_pallas=True, **kw)
    np.testing.assert_allclose(
        np.asarray(flat),
        np.asarray(se.decode_sum_blocks(stb, 1, size, use_pallas=False,
                                        **kw)), rtol=1e-5, atol=1e-5)
    for splits in [(0, 5000, tile, tile + 1, 2 * tile + 77, size),
                   se.tree_splits(size, 3)]:
        tree = se.decode_sum_tree(stb, 1, size, splits=splits,
                                  use_pallas=True, **kw)
        np.testing.assert_array_equal(np.asarray(flat), np.asarray(tree))


def test_scatter_steps_counts_each_range():
    """The span stat ``scatter_steps``: the tree decode runs the kernel once
    per non-empty range, each call over the whole round stream."""
    from repro.kernels.stream_decode import grid_steps

    C, k, k_mask, size = 4, 300, 20, 3 * 64 * 128 + 5
    n = C * (k + C * k_mask) + C * C * k_mask
    assert se.scatter_steps(C, k, k_mask, 1, size, recovery=True) == \
        grid_steps(n, size)
    assert se.scatter_steps(C, k, k_mask, 1, size, recovery=True,
                            splits=(0, 100, 100, 9000, size)) == sum(
        grid_steps(n, w) for w in (100, 8900, size - 9000))


# ------------------------------------------------------------- round parity
def _one_round(topology, tree_groups, dropped):
    from repro.models.paper_models import PAPER_MODELS, cross_entropy_loss

    C, steps, batch = 5, 2, 8
    model = PAPER_MODELS["mnist_mlp"]
    loss_fn = cross_entropy_loss(model)
    params = model.init(jax.random.key(0))
    key = jax.random.key(1)
    x = jax.random.normal(key, (C, steps, batch, 784))
    y = jax.random.randint(key, (C, steps, batch), 0, 10)
    batches = {c: (x[c], y[c]) for c in range(C)}
    fed = FedConfig(n_clients=C, clients_per_round=C, local_steps=steps,
                    local_batch=batch, local_lr=0.05, rounds=10)
    sa = SecureAggConfig(mask_ratio=0.02, threshold=0.6, seed=5)
    weights = {c: float(c + 1) for c in range(C)}
    state = init_state(params, fed)
    return run_round(state, batches, loss_fn, fed, THGS, sa,
                     client_weights=weights, dropped=dropped,
                     topology=topology, tree_groups=tree_groups)


@pytest.mark.parametrize("dropped", [(), (1, 3)])
@pytest.mark.parametrize("tree_groups", [0, 1, 3])
def test_run_round_tree_equals_flat(dropped, tree_groups):
    """Full secagg round: params, residuals and the CommRecord facts are
    bit-identical across topologies (with and without dropout recovery)."""
    s_flat = _one_round("flat", 0, dropped)
    s_tree = _one_round("tree", tree_groups, dropped)
    for a, b in zip(jax.tree_util.tree_leaves(s_flat.params),
                    jax.tree_util.tree_leaves(s_tree.params)):
        assert bool(jnp.all(a == b)), f"params diverge (dropped={dropped})"
    for c in s_flat.residuals:
        for a, b in zip(jax.tree_util.tree_leaves(s_flat.residuals[c]),
                        jax.tree_util.tree_leaves(s_tree.residuals[c])):
            assert bool(jnp.all(a == b)), f"residuals diverge c={c}"
    assert s_flat.comm_log[-1] == s_tree.comm_log[-1]


def test_ledger_totals_identical_across_topologies():
    """CommLedger stays exact under the tree: same round facts -> identical
    totals under BOTH accountings (the topology never touches the wire
    accounting — clients upload the same streams either way)."""
    from repro.sim.ledger import CommLedger

    led_flat, led_tree = CommLedger(), CommLedger()
    for dropped in ((), (1, 3)):
        led_flat.record(_one_round("flat", 0, dropped).comm_log[-1])
        led_tree.record(_one_round("tree", 3, dropped).comm_log[-1])
    for acct in ("paper", "tpu"):
        assert led_flat.totals(acct) == led_tree.totals(acct)
    assert led_flat.summary() == led_tree.summary()


def test_tree_requires_thgs_and_valid_topology():
    from repro.models.paper_models import PAPER_MODELS, cross_entropy_loss

    model = PAPER_MODELS["mnist_mlp"]
    loss_fn = cross_entropy_loss(model)
    params = model.init(jax.random.key(0))
    key = jax.random.key(1)
    batches = {0: (jax.random.normal(key, (1, 4, 784)),
                   jax.random.randint(key, (1, 4), 0, 10))}
    fed = FedConfig(n_clients=1, clients_per_round=1, local_steps=1,
                    local_batch=4, local_lr=0.05, rounds=4)
    state = init_state(params, fed)
    sa = SecureAggConfig(enabled=False)
    with pytest.raises(ValueError, match="requires THGS"):
        run_round(state, batches, loss_fn, fed, None, sa, topology="tree")
    with pytest.raises(ValueError, match="unknown topology"):
        run_round(state, batches, loss_fn, fed, THGS, sa, topology="ring")
