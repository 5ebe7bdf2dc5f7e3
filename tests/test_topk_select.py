"""The encode's exact top-k by threshold select (kernels/topk_select.py,
``streams.select_topk_rows``): the same index set as ``lax.top_k`` and as
the benchmark's plain reference, ties at the k-th magnitude to the lowest
index, and — given in ascending order — the same streams, residuals and
decoded sums bit for bit."""
import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import streams
from repro.core.types import SecureAggConfig
from repro.kernels import ops, ref, topk_select

REPO = Path(__file__).resolve().parents[1]


def _reference_top_k():
    spec = importlib.util.spec_from_file_location(
        "chipbench_reference_for_topk", REPO / "chipbench" / "reference.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod        # its dataclasses look themselves up
    spec.loader.exec_module(mod)
    return mod.top_k


reference_top_k = _reference_top_k()


def _case(name: str, rows: int, m: int, k: int) -> np.ndarray:
    rng = np.random.default_rng([rows, m, k])
    if name == "normal":
        return rng.standard_normal((rows, m)).astype(np.float32)
    if name == "ties_at_threshold":
        # few distinct magnitudes: the k-th one has copies on both sides of
        # the cut, spread over the whole row
        return (rng.integers(-6, 7, (rows, m))
                * 0.25).astype(np.float32)
    if name == "all_equal":
        return np.full((rows, m), -1.5, np.float32)
    if name == "zeros_beyond_k":
        # fewer nonzeros than k: zeros, some of them -0.0, fill the rest
        a = np.zeros((rows, m), np.float32)
        a[:, ::7] = rng.standard_normal((rows, len(range(0, m, 7))))
        a[:, 1::5] = -0.0
        return a
    if name == "neg_zero_threshold":
        a = rng.standard_normal((rows, m)).astype(np.float32)
        a[:, m // 3:] = np.where(rng.random((rows, m - m // 3)) < 0.5,
                                 -0.0, 0.0)
        return a
    if name == "rows_differ":
        # every row its own scale, so its own threshold
        scale = (10.0 ** np.arange(rows))[:, None]
        return (rng.standard_normal((rows, m)) * scale).astype(np.float32)
    raise ValueError(name)


CASES = [
    ("normal", 3, 5000, 300),
    ("normal", 2, 1000, 1),
    ("normal", 2, 1000, 999),
    ("normal", 2, 1000, 1000),
    ("ties_at_threshold", 3, 3000, 1500),
    ("ties_at_threshold", 2, 2053, 1),
    ("all_equal", 2, 700, 10),
    ("all_equal", 1, 1024, 1024),
    ("zeros_beyond_k", 2, 3001, 900),
    ("neg_zero_threshold", 2, 2000, 1900),
    ("rows_differ", 4, 1500, 77),
]


def _want(a: np.ndarray, k: int) -> np.ndarray:
    top = np.sort(np.asarray(jax.lax.top_k(jnp.abs(jnp.asarray(a)), k)[1]),
                  axis=1)
    plain = np.stack([np.sort(reference_top_k(np.abs(r), k)) for r in a])
    np.testing.assert_array_equal(top, plain)
    return top


@pytest.mark.parametrize("name,rows,m,k", CASES)
def test_select_equals_top_k_and_reference(name, rows, m, k):
    """Through the wrapper (bisection + the kernel's twin here) and through
    the kernel itself in interpret mode, with blocks of 8 rows so that a row
    of a few thousand spans several of them and ties straddle blocks."""
    a = _case(name, rows, m, k)
    want = _want(a, k)
    got = np.asarray(ops.topk_select(jnp.asarray(a), k=k))
    np.testing.assert_array_equal(got, want)
    a3 = topk_select.row_view(jnp.asarray(a))
    thr, need = topk_select.row_thresholds(a3, k)
    kern = topk_select.topk_compact(a3, thr, need, k, m, block_rows=8,
                                    interpret=True)
    np.testing.assert_array_equal(np.asarray(kern), want)


@pytest.mark.parametrize("name,rows,m,k", [c for c in CASES if c[1] > 1])
def test_thresholds_are_the_kth_magnitude(name, rows, m, k):
    a = _case(name, rows, m, k)
    thr, need = topk_select.row_thresholds(
        topk_select.row_view(jnp.asarray(a)), k)
    keys = np.abs(a).view(np.int32)
    kth = np.sort(keys, axis=1)[:, m - k]
    np.testing.assert_array_equal(np.asarray(thr), kth)
    np.testing.assert_array_equal(
        np.asarray(need), k - (keys > kth[:, None]).sum(1))
    assert (np.asarray(need) >= 1).all()


def test_select_under_vmap_blocks_and_clients():
    """nb > 1 blocks of each of C vmapped clients, every row with its own
    threshold: the mapped axis joins the rows of one select."""
    C, nb, m, k = 3, 2, streams.SELECT_MIN_M + 300, 400
    a = _case("rows_differ", C * nb, m, k).reshape(C, nb, m)
    assert streams.uses_select(m, k)
    got = jax.jit(jax.vmap(
        lambda x: streams.select_topk_rows(x, k, "exact", 0.01)))(
            jnp.asarray(a))
    want = _want(a.reshape(C * nb, m), k).reshape(C, nb, k)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_crossover_picks_by_shape():
    """Below the crossover the rows keep ``lax.top_k``; from it on the
    exact selectors take the select; 'sampled' and the DP release never do,
    and the span stat counts the rows that do."""
    lo, hi = streams.SELECT_MIN_M - 1, streams.SELECT_MIN_M

    def prims(m, selector="exact"):
        jaxpr = jax.make_jaxpr(lambda x: streams.select_topk_rows(
            x, 5, selector, 0.5))(jnp.zeros((2, m), jnp.float32))
        return str(jaxpr)

    assert "top_k" in prims(lo)
    assert "top_k" not in prims(hi) and "top_k" not in prims(hi, "local")
    assert not streams.uses_select(hi, 5, "sampled")
    assert streams.select_rows(5, 5, 1, hi, "exact") == 5
    assert streams.select_rows(4, 5, 3, hi, "local") == 12
    assert streams.select_rows(5, 5, 1, lo, "exact") == 0
    assert streams.select_rows(5, 5, 1, hi, "exact", dp=True) == 0
    assert streams.select_rows(5, 5, 1, hi, "sampled") == 0


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def test_encode_sorts_no_long_row():
    """The CIFAR-MLP's ``l0.w`` encode (5 × 4,718,592, k 264,529, secagg
    masks), traced from shapes alone: no ``top_k``, and every sort is over
    the top-k and mask slots (the first-occurrence gate), not the row."""
    C, m, k = 5, 4_718_592, 264_529
    km = SecureAggConfig(mask_ratio=0.01).k_mask_for(m, C)
    seeds, signs = streams.pair_seed_matrix(
        SecureAggConfig(mask_ratio=0.01, seed=3), list(range(C)), round_t=1)
    row = jax.ShapeDtypeStruct((C, m), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda g, r: streams.encode_leaf_batch(
        g, r, k=k, nb=1, m=m, size=m, pair_seeds=seeds, pair_signs=signs,
        k_mask=km, leaf_id=0))(row, row)
    names = [e.primitive.name for e in _eqns(jaxpr.jaxpr)]
    assert "top_k" not in names
    sorts = [e for e in _eqns(jaxpr.jaxpr) if e.primitive.name == "sort"]
    assert sorts
    assert max(v.aval.shape[-1] for e in sorts for v in e.invars) \
        == k + C * km == 311_714


@pytest.fixture
def force_path(monkeypatch):
    """Set the crossover for the next traces (the jitted encode reads it
    while tracing, so the caches are cleared around each setting)."""
    def force(min_m: int):
        monkeypatch.setattr(streams, "SELECT_MIN_M", min_m)
        jax.clear_caches()
    yield force
    jax.clear_caches()


def test_encode_decode_bit_identical_either_path(force_path):
    """A secure-aggregation round with a drop, ties planted at the k-th
    magnitude: the select path and ``lax.top_k`` give the same residuals and
    decoded sum bit for bit, and every client the same multiset of (index,
    value bits) — only the top-k slots' order differs."""
    C, n, k = 4, 3000, 150
    sa = SecureAggConfig(mask_ratio=0.05, seed=3)
    rng = np.random.default_rng(8)
    g = rng.standard_normal((C, n)).astype(np.float32)
    r = (rng.standard_normal((C, n)) * 0.1).astype(np.float32)
    for c in range(C):
        kth = np.sort(np.abs(g[c] + r[c]))[n - k]
        pos = rng.choice(n, 40, replace=False)
        g[c, pos], r[c, pos] = kth * rng.choice([-1, 1], 40), 0.0
    g, r = jnp.asarray(g), jnp.asarray(r)
    km = sa.k_mask_for(n, C)
    seeds, signs = streams.pair_seed_matrix(sa, list(range(C)), round_t=2)
    alive = jnp.array([True, False, True, True])
    kw = dict(k_mask=km, mask_p=sa.p, mask_q=sa.q, leaf_id=1)

    def round_():
        st, nr = streams.encode_leaf_batch(
            g, r, k=k, nb=1, m=n, size=n, pair_seeds=seeds,
            pair_signs=signs, **kw)
        dense = streams.decode_leaf_batch(
            st, nb=1, m=n, size=n, alive=alive, pair_seeds=seeds,
            pair_signs=signs, **kw)
        return [np.asarray(x) for x in (st.indices, st.values, nr, dense)]

    force_path(1)
    assert streams.uses_select(n, k)
    idx_s, val_s, res_s, dense_s = round_()
    acc = np.abs(np.asarray(g + r))
    kth = np.sort(acc, axis=1)[:, n - k]
    assert ((acc > kth[:, None]).sum(1) < k).all()          # ties are cut
    assert ((acc >= kth[:, None]).sum(1) > k).all()
    force_path(n + 1)
    assert not streams.uses_select(n, k)
    idx_t, val_t, res_t, dense_t = round_()

    np.testing.assert_array_equal(res_s.view(np.int32), res_t.view(np.int32))
    np.testing.assert_array_equal(dense_s.view(np.int32),
                                  dense_t.view(np.int32))
    for c in range(C):
        assert (np.diff(idx_s[c, 0, :k]) > 0).all()
        pairs = [sorted(zip(i[c, 0].tolist(), v[c, 0].view(np.int32).tolist()))
                 for i, v in ((idx_s, val_s), (idx_t, val_t))]
        assert pairs[0] == pairs[1]
        np.testing.assert_array_equal(idx_s[c, 0, k:], idx_t[c, 0, k:])


def test_compact_ref_matches_nonzero():
    """The kernel's twin is the ascending columns of the selected set."""
    a = _case("ties_at_threshold", 3, 777, 200)
    thr, need = topk_select.row_thresholds(
        topk_select.row_view(jnp.asarray(a)), 200)
    got = np.asarray(ref.topk_compact_ref(jnp.asarray(a), thr, need, 200))
    keys = np.abs(a).view(np.int32)
    for row, t, nd, out in zip(keys, np.asarray(thr), np.asarray(need), got):
        eq = np.flatnonzero(row == t)[:nd]
        np.testing.assert_array_equal(
            out, np.sort(np.concatenate([np.flatnonzero(row > t), eq])))
