"""Pallas kernels vs pure-jnp oracles (interpret mode), sweeping shapes/dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.key(42)


@pytest.mark.parametrize("b,t,s,h,hkv,hd", [
    (1, 128, 128, 4, 4, 64),
    (2, 256, 256, 4, 2, 64),
    (1, 256, 256, 8, 1, 128),   # MQA
    (2, 128, 128, 2, 2, 128),
])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 64),
                                           (False, None)])
def test_flash_attention_matches_ref(b, t, s, h, hkv, hd, causal, window):
    q = jax.random.normal(jax.random.fold_in(KEY, 1), (b, t, h, hd))
    k = jax.random.normal(jax.random.fold_in(KEY, 2), (b, s, hkv, hd))
    v = jax.random.normal(jax.random.fold_in(KEY, 3), (b, s, hkv, hd))
    out = ops.flash_attention(q, k, v, causal=causal, window=window)
    exp = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    q = jax.random.normal(KEY, (1, 128, 4, 64)).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(KEY, 5), (1, 128, 2, 64)).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 6), (1, 128, 2, 64)).astype(dtype)
    out = ops.flash_attention(q, k, v)
    exp = ref.flash_attention_ref(q, k, v)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), rtol=tol, atol=tol)
    assert out.dtype == dtype


@pytest.mark.parametrize("shape", [(100,), (64, 129), (7, 3, 11), (4096,)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_thgs_sparsify_matches_ref(shape, dtype):
    g = jax.random.normal(jax.random.fold_in(KEY, 9), shape).astype(dtype)
    r = (jax.random.normal(jax.random.fold_in(KEY, 10), shape) * 0.2).astype(dtype)
    thr = 0.8
    sp, nr = ops.thgs_sparsify(g, r, thr)
    spr, nrr = ref.thgs_sparsify_ref(g, r, thr)
    np.testing.assert_allclose(np.asarray(sp, np.float32),
                               np.asarray(spr, np.float32), rtol=1e-2, atol=1e-2)
    np.testing.assert_allclose(np.asarray(nr, np.float32),
                               np.asarray(nrr, np.float32), rtol=1e-2, atol=1e-2)
    # exact split: every position is in exactly one of (sparse, residual)
    both = np.asarray(jnp.abs(sp.astype(jnp.float32)) *
                      jnp.abs(nr.astype(jnp.float32)))
    assert (both < 1e-6).all()


@pytest.mark.parametrize("shape", [(513, 7), (1000,), (128, 128)])
def test_mask_prng_matches_ref_and_cancels(shape):
    g = jax.random.normal(jax.random.fold_in(KEY, 11), shape)
    o_k, m_k = ops.mask_prng_apply(g, seed=1234, sigma=-0.4, sign=1.0)
    o_r, m_r = ref.mask_prng_ref(g, 1234, p=-1.0, q=2.0, sigma=-0.4, sign=1.0)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), atol=1e-6)
    np.testing.assert_allclose(np.asarray(m_k), np.asarray(m_r), atol=1e-6)
    _, m_neg = ops.mask_prng_apply(g, seed=1234, sigma=-0.4, sign=-1.0)
    assert float(jnp.max(jnp.abs(m_k + m_neg))) == 0.0


@pytest.mark.parametrize("sign", [1.0, -1.0])
@pytest.mark.parametrize("n,block_rows", [
    (1, 256),          # single element, maximal padding
    (97, 2),           # odd size, n far from a lane multiple
    (128 * 2, 2),      # exactly one 128*block_rows tile
    (128 * 2 + 1, 2),  # one element past the tile boundary
    (128 * 2 - 1, 2),  # one element short of it
    (50_000, 256),     # many tiles, ragged tail
])
def test_mask_prng_kernel_ref_parity_padding_boundaries(n, block_rows, sign):
    """mask_prng.py (interpret) vs ref.py over odd sizes and padding
    boundaries (n not a multiple of 128*block_rows), both signs — the
    padded lanes of the last tile must not leak into the unpadded view."""
    from repro.kernels.mask_prng import mask_prng_apply

    g = jax.random.normal(jax.random.fold_in(KEY, n), (n,))
    o_k, m_k = mask_prng_apply(g, 77, sigma=-0.2, sign=sign,
                               block_rows=block_rows, interpret=True)
    o_r, m_r = ref.mask_prng_ref(g, 77, p=-1.0, q=2.0, sigma=-0.2, sign=sign)
    np.testing.assert_array_equal(np.asarray(m_k), np.asarray(m_r))
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), atol=1e-6)
    assert m_k.shape == g.shape


@pytest.mark.parametrize("n_pairs,nb,k_mask,m", [
    (3, 1, 37, 257),     # odd everything
    (6, 4, 17, 1000),    # blocked layout
    (2, 1, 1, 5),        # minimal
    (5, 2, 129, 4097),   # k_mask one past the lane boundary
    (4, 1, 128, 128),    # exactly one lane row
])
def test_pair_mask_streams_kernel_ref_parity(n_pairs, nb, k_mask, m):
    """The sparse pair-mask kernel (interpret) is bit-identical to
    ref.pair_mask_stream_ref — indices AND values, mixed signs."""
    from repro.kernels.mask_prng import pair_mask_streams

    seeds = (jnp.arange(1, n_pairs + 1, dtype=jnp.uint32)
             * jnp.uint32(2654435761))
    signs = jnp.asarray([(-1.0) ** i for i in range(n_pairs)], jnp.float32)
    ik, vk = pair_mask_streams(seeds, signs, nb=nb, k_mask=k_mask, m=m,
                               interpret=True)
    ir, vr = ref.pair_mask_stream_ref(seeds, signs, nb, k_mask, m,
                                      p=-1.0, q=2.0)
    assert ik.shape == (n_pairs, nb, k_mask)
    np.testing.assert_array_equal(np.asarray(ik), np.asarray(ir))
    np.testing.assert_array_equal(np.asarray(vk), np.asarray(vr))
    assert (np.asarray(ik) >= 0).all() and (np.asarray(ik) < m).all()


def test_pair_mask_streams_opposite_signs_cancel_bitwise():
    from repro.kernels.mask_prng import pair_mask_streams

    seeds = jnp.asarray([0xABCD1234, 0xABCD1234], jnp.uint32)
    signs = jnp.asarray([1.0, -1.0], jnp.float32)
    idx, vals = pair_mask_streams(seeds, signs, nb=1, k_mask=50, m=333,
                                  interpret=True)
    np.testing.assert_array_equal(np.asarray(idx[0]), np.asarray(idx[1]))
    assert float(jnp.max(jnp.abs(vals[0] + vals[1]))) == 0.0


TILE = 64 * 128          # dense entries per tile of the decode kernel


def _scatter_case(pattern, n, size, key):
    """(indices, values) of one decode case; ``random`` draws duplicates,
    the -1 padding sentinel and out-of-range indices."""
    k1, k2, k3 = jax.random.split(key, 3)
    val = jax.random.normal(k2, (n,))
    if pattern == "random":
        return jax.random.randint(k1, (n,), -2, size + 3), val
    if pattern == "one_tile":          # all slots in tile 2 of 5
        return jax.random.randint(k1, (n,), 2 * TILE, 3 * TILE), val
    if pattern == "long_run":          # one index repeated past a chunk
        run = jnp.full((1200,), 777, jnp.int32)
        rest = jax.random.randint(k1, (n - 1200,), 0, size)
        return jax.random.permutation(k3, jnp.concatenate([run, rest])), val
    if pattern == "straddle":          # sorted chunk 1 spans tiles 0, 1, 2
        parts = [jax.random.randint(jax.random.fold_in(k1, t), (c,),
                                    t * TILE, (t + 1) * TILE)
                 for t, c in enumerate((600, 100, 324))]
        return jax.random.permutation(k3, jnp.concatenate(parts)), val
    if pattern == "pad_and_oob":       # -1 padding, indices at/above size
        odd = jnp.asarray([-1] * 40 + [size] * 20 + [size + 7] * 10
                          + [TILE - 1, TILE, 5 * TILE], jnp.int32)
        rest = jax.random.randint(k1, (n - odd.shape[0],), 0, size)
        return jax.random.permutation(k3, jnp.concatenate([odd, rest])), val
    raise ValueError(pattern)


@pytest.mark.parametrize("n,size,pattern", [
    pytest.param(100, 1000, "random", id="100-1000"),
    pytest.param(700, 257, "random", id="700-257"),
    pytest.param(2048, 100_000, "random", id="2048-100000"),
    pytest.param(5, 64, "random", id="5-64"),
    pytest.param(3000, 5 * TILE, "one_tile", id="one_tile"),
    pytest.param(2000, 3 * TILE, "long_run", id="long_run"),
    pytest.param(1024, 4 * TILE, "straddle", id="chunk_straddles_3_tiles"),
    pytest.param(700, 3 * TILE - 100, "pad_and_oob", id="pad_and_oob"),
    pytest.param(37, 20_000, "random", id="n_below_chunk"),
    pytest.param(2000, TILE, "random", id="one_tile_buffer"),
])
def test_stream_scatter_add_matches_ref(n, size, pattern):
    idx, val = _scatter_case(pattern, n, size,
                             jax.random.fold_in(KEY, 20))
    out = ops.stream_scatter_add(idx, val, size=size)
    exp = ref.stream_scatter_add_ref(idx, val, size)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=1e-5,
                               atol=1e-5)
    if pattern == "one_tile":
        dense = np.asarray(out)
        assert not dense[:2 * TILE].any() and not dense[3 * TILE:].any()


@pytest.mark.parametrize("n,size", [(3000, 5 * TILE), (1024, 4 * TILE),
                                    (37, 20_000), (2000, TILE),
                                    (100_000, 40 * TILE)])
def test_stream_scatter_add_work_list(n, size):
    """The sorted stream's (tile, chunk) list: of length ``grid_steps``,
    monotone in the tile and in the chunk, every tile visited, its live
    items exactly the pairs that share a slot."""
    from repro.kernels import stream_decode as sd

    chunk = 512
    steps = sd.grid_steps(n, size)
    n_tiles = -(-size // TILE)
    n_chunks = -(-n // chunk)
    assert steps == n_chunks + n_tiles
    idx = jax.random.randint(jax.random.fold_in(KEY, 21), (n,), -1, size)
    idx = jnp.sort(jnp.pad(idx, (0, n_chunks * chunk - n),
                           constant_values=-1))
    tile_of, chunk_of, live = map(np.asarray, sd.work_list(
        idx, n_tiles, TILE, chunk, steps))
    assert tile_of.shape == chunk_of.shape == live.shape == (steps,)
    assert (np.diff(tile_of) >= 0).all() and (np.diff(chunk_of) >= 0).all()
    assert set(tile_of.tolist()) == set(range(n_tiles))
    assert live.sum() <= n_chunks + n_tiles
    tiles = np.asarray(idx).reshape(n_chunks, chunk) // TILE
    overlap = {(int(t), c) for c in range(n_chunks) for t in tiles[c]
               if 0 <= t < n_tiles}
    items = {(int(t), int(c)) for t, c, ok in zip(tile_of, chunk_of, live)
             if ok}
    assert items == overlap


def test_stream_scatter_add_duplicates_accumulate():
    idx = jnp.array([3, 3, 3, 0, 9], jnp.int32)
    val = jnp.array([1.0, 2.0, 4.0, 5.0, -1.0])
    out = ops.stream_scatter_add(idx, val, size=10)
    assert float(out[3]) == 7.0 and float(out[0]) == 5.0
    assert float(out[9]) == -1.0 and float(out.sum()) == 11.0


def test_mask_prng_support_fraction():
    g = jnp.zeros((100_000,))
    _, m = ops.mask_prng_apply(g, seed=7, sigma=-0.5, sign=1.0)
    frac = float(jnp.mean(m != 0))
    assert abs(frac - 0.25) < 0.02  # (sigma - p)/q = 0.25


# --------------------------------------------------- wire-format bit packing
@pytest.mark.parametrize("rows,k,width", [
    (1, 1, 1),       # degenerate single-slot
    (3, 37, 11),     # odd everything
    (5, 64, 32),     # full-word fields
    (2, 33, 17),     # one past a chunk boundary
    (7, 31, 18),     # one short of a chunk boundary
    (4, 256, 4),     # many whole chunks
    (2, 97, 1),      # 1-bit sign stream
    (8, 128, 8),     # exact tile
])
def test_bitpack_rows_kernel_matches_ref(rows, k, width):
    """Pallas pack/unpack (interpret mode) is bit-exact with the ref twin."""
    from repro.kernels import pack

    bits = jax.random.bits(jax.random.fold_in(KEY, rows * 1000 + k),
                           (rows, k), jnp.uint32)
    u = bits >> jnp.uint32(32 - width)
    words_ref = ref.bitpack_rows_ref(u, width)
    words_ker = pack.bitpack_rows(u, width, interpret=True)
    np.testing.assert_array_equal(np.asarray(words_ker),
                                  np.asarray(words_ref))
    back_ref = ref.bitunpack_rows_ref(words_ref, k, width)
    back_ker = pack.bitunpack_rows(words_ker, k, width, interpret=True)
    np.testing.assert_array_equal(np.asarray(back_ref), np.asarray(u))
    np.testing.assert_array_equal(np.asarray(back_ker), np.asarray(u))


@pytest.mark.parametrize("k,width", [(1, 1), (37, 11), (64, 32), (33, 17)])
def test_bitpack_rows_ops_dispatch(k, width):
    """The ops-layer jitted wrappers round-trip through either backend."""
    bits = jax.random.bits(jax.random.fold_in(KEY, k + width), (2, k),
                           jnp.uint32)
    u = bits >> jnp.uint32(32 - width)
    words = ops.bitpack_rows(u, width=width)
    assert words.shape == (2, ref.packed_words(k, width))
    back = ops.bitunpack_rows(words, k=k, width=width)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(u))


def test_packed_words():
    assert ref.packed_words(32, 1) == 1
    assert ref.packed_words(33, 1) == 2
    assert ref.packed_words(1, 32) == 1
    assert ref.packed_words(100, 17) == -(-100 * 17 // 32)


@pytest.mark.parametrize("rows,m,k,block_rows,pattern", [
    pytest.param(3, 5000, 300, 8, "normal", id="blocks_of_8_rows"),
    pytest.param(2, 4000, 2000, 16, "ties", id="ties_straddle_blocks"),
    pytest.param(2, 1000, 1000, 8, "normal", id="k_eq_m"),
    pytest.param(2, 70_380, 2151, 512, "normal", id="mlp_l2w_two_blocks"),
    pytest.param(1, 66_000, 900, 512, "ties", id="block_overhangs_row"),
])
def test_topk_select_kernel_matches_ref(rows, m, k, block_rows, pattern):
    """The compaction kernel in interpret mode against its jnp twin, at the
    thresholds the bisection finds."""
    from repro.kernels import topk_select

    key = jax.random.fold_in(KEY, 30)
    if pattern == "ties":
        acc = jax.random.randint(key, (rows, m), -5, 6).astype(jnp.float32)
    else:
        acc = jax.random.normal(key, (rows, m))
    acc3 = topk_select.row_view(acc)
    thr, need = topk_select.row_thresholds(acc3, k)
    out = topk_select.topk_compact(acc3, thr, need, k, m,
                                   block_rows=block_rows, interpret=True)
    exp = ref.topk_compact_ref(acc, thr, need, k)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))
