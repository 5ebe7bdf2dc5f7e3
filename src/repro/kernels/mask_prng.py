"""Pallas TPU kernel: counter-based sparse-mask generation + apply (Eq. 3-5).

Secure aggregation's data-plane hot loop: for each parameter position i, derive
a uniform u(i) in [p, p+q) from a murmur-style 32-bit avalanche of (seed ^ i)
(counter-based — masks are *recomputed*, never stored, so the mask matrix costs
zero HBM), keep it only where u(i) < sigma (Eq. 4's threshold: expected support
fraction (sigma-p)/q = k/x), and add it to the gradient tile in one pass.

Both endpoints of a pair run the same kernel with the same seed and opposite
``sign``, so the aggregated masks cancel exactly. Matches ref.mask_prng_ref.
That dense kernel has no caller and Mosaic refuses its uint32 -> f32 cast;
the sparse ``pair_mask_streams`` below is the one secure aggregation runs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import IDX_SALT, VAL_SALT, _mix32

LANE = 128


def _kernel(g_ref, o_ref, m_ref, *, seed: int, p: float, q: float,
            sigma: float, sign: float, block_rows: int):
    i = pl.program_id(0)
    base = i * block_rows * LANE
    idx = base + jax.lax.broadcasted_iota(jnp.int32, g_ref.shape, 0) * LANE \
        + jax.lax.broadcasted_iota(jnp.int32, g_ref.shape, 1)
    x = _mix32(idx.astype(jnp.uint32) ^ jnp.uint32(seed))
    u = p + q * (x.astype(jnp.float32) / jnp.float32(2**32))
    mask = jnp.where(u < sigma, u, 0.0) * sign
    m_ref[...] = mask
    o_ref[...] = (g_ref[...].astype(jnp.float32) + mask).astype(o_ref.dtype)


def mask_prng_apply(g: jax.Array, seed: int, *, p: float = -1.0, q: float = 2.0,
                    sigma: float, sign: float = 1.0, block_rows: int = 256,
                    interpret: bool = False):
    """Returns (g + mask, mask) with the sparse pairwise mask regenerated on the
    fly. g: any shape."""
    orig_shape = g.shape
    n = g.size
    rows = -(-n // LANE)
    pad = rows * LANE - n
    gf = jnp.pad(g.reshape(-1), (0, pad)).reshape(rows, LANE)
    block_rows = min(block_rows, rows)
    grid = (-(-rows // block_rows),)

    kernel = functools.partial(_kernel, seed=seed, p=p, q=q, sigma=sigma,
                               sign=sign, block_rows=block_rows)
    out, mask = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANE), g.dtype),
            jax.ShapeDtypeStruct((rows, LANE), jnp.float32),
        ],
        interpret=interpret,
    )(gf)
    unpad = lambda x: x.reshape(-1)[:n].reshape(orig_shape)
    return unpad(out), unpad(mask)


def _urem(x: jax.Array, m: int) -> jax.Array:
    """``x % m`` for uint32 ``x`` and a static ``0 < m < 2**30``, with
    signed int32 operations only: ``x = 2*(x >> 1) + (x & 1)`` with
    ``x >> 1 < 2**31``, so
    ``t = 2*((x >> 1) % m) + (x & 1) < 2*m`` and one conditional subtract
    finishes. Returns int32 in ``[0, m)``."""
    half = jax.lax.bitcast_convert_type(x >> 1, jnp.int32)
    low = jax.lax.bitcast_convert_type(x & jnp.uint32(1), jnp.int32)
    t = 2 * jax.lax.rem(half, jnp.int32(m)) + low
    return jnp.where(t >= m, t - m, t)


def _pair_stream_kernel(s_ref, sg_ref, i_ref, v_ref, *, L: int, m: int,
                        p: float, q: float, rows: int):
    """One grid step = one pair: counter-based (idx, val) slots for that pair.

    The per-pair seed and sign arrive as ``(1, 1)`` tiles of ``(N, 1, 1)``
    operands (block ``(None, 1, 1)``): a block's last two dims must be
    multiples of (8, 128) or the full dims, which a ``(1, 1)`` block of an
    ``(N, 1)`` array is not. Both stay ``(1, 1)`` vectors and broadcast
    against the counter tile. Counters past ``L`` are padding lanes; they
    are zeroed and sliced off by the wrapper. The uint32 -> f32 and remainder
    steps go through int32 (exact: draws are < 2**24, and see ``_urem``).
    """
    seed = s_ref[...]                                     # uint32[1, 1]
    sign = sg_ref[...]                                    # f32[1, 1]
    ci = (jax.lax.broadcasted_iota(jnp.int32, (rows, LANE), 0) * LANE
          + jax.lax.broadcasted_iota(jnp.int32, (rows, LANE), 1))
    c = jax.lax.bitcast_convert_type(ci, jnp.uint32)
    base_i = _mix32(seed ^ jnp.uint32(IDX_SALT))
    base_v = _mix32(seed ^ jnp.uint32(VAL_SALT))
    idx = _urem(_mix32(base_i + c), m)
    # top 24 bits: the f32-exact uniform grid (see ref.pair_mask_stream_ref)
    draw = jax.lax.bitcast_convert_type(_mix32(base_v + c) >> 8, jnp.int32)
    u = draw.astype(jnp.float32) / jnp.float32(2**24)
    val = sign * (p + q * u)
    valid = ci < L
    i_ref[...] = jnp.where(valid, idx, 0)
    v_ref[...] = jnp.where(valid, val, 0.0)


def pair_mask_streams(seeds: jax.Array, signs: jax.Array, *, nb: int,
                      k_mask: int, m: int, p: float = -1.0, q: float = 2.0,
                      interpret: bool = False):
    """All pair masks of a round in ONE fused pass (paper Eq. 3-4 data plane).

    ``seeds`` uint32[N] (one per active pair, leaf already folded in) and
    ``signs`` f32[N] produce ``(idx int32[N, nb, k_mask], vals f32)`` —
    the sparse-support counterpart of :func:`mask_prng_apply`'s dense sigma
    thresholding, matching ``ref.pair_mask_stream_ref`` bit for bit. Grid is
    one step per pair; each step fills that pair's ``nb * k_mask`` slots from
    a murmur-avalanched counter stream, so masks are regenerated on the fly
    (zero HBM for the mask matrix) exactly as the dense kernel does.
    """
    if not 0 < m < 2**30:
        raise ValueError(f"block length m={m} outside (0, 2**30)")
    n_pairs = seeds.shape[0]
    L = nb * k_mask
    rows = max(1, -(-L // LANE))
    kernel = functools.partial(_pair_stream_kernel, L=L, m=m, p=p, q=q,
                               rows=rows)
    idx, vals = pl.pallas_call(
        kernel,
        grid=(n_pairs,),
        in_specs=[
            pl.BlockSpec((None, 1, 1), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, 1, 1), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, rows, LANE), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, rows, LANE), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pairs, rows, LANE), jnp.int32),
            jax.ShapeDtypeStruct((n_pairs, rows, LANE), jnp.float32),
        ],
        interpret=interpret,
    )(seeds.astype(jnp.uint32).reshape(n_pairs, 1, 1),
      signs.astype(jnp.float32).reshape(n_pairs, 1, 1))
    idx = idx.reshape(n_pairs, rows * LANE)[:, :L].reshape(n_pairs, nb, k_mask)
    vals = vals.reshape(n_pairs, rows * LANE)[:, :L].reshape(
        n_pairs, nb, k_mask)
    return idx, vals
