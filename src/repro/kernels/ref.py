"""Pure-jnp oracles for every Pallas kernel (the allclose targets in tests)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def flash_attention_ref(q, k, v, *, causal: bool = True,
                        window: Optional[int] = None) -> jax.Array:
    """[B,T,H,hd] x [B,S,Hkv,hd] GQA attention, f32 math."""
    b, t, h, hd = q.shape
    s = k.shape[1]
    group = h // k.shape[2]
    kf = jnp.repeat(k, group, axis=2).astype(jnp.float32)
    vf = jnp.repeat(v, group, axis=2).astype(jnp.float32)
    scores = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32), kf) / (hd ** 0.5)
    q_pos = jnp.arange(t)[:, None]
    k_pos = jnp.arange(s)[None, :]
    mask = jnp.ones((t, s), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhts,bshd->bthd", probs, vf)
    return out.astype(q.dtype)


def thgs_sparsify_ref(g, residual, threshold):
    """Fused THGS threshold step: acc = g + residual; split at |acc| > delta."""
    acc = (g.astype(jnp.float32) + residual.astype(jnp.float32))
    keep = jnp.abs(acc) > threshold
    sparse = jnp.where(keep, acc, 0.0)
    new_resid = jnp.where(keep, 0.0, acc)
    return sparse.astype(g.dtype), new_resid.astype(residual.dtype)


def _mix32(x: jax.Array) -> jax.Array:
    """murmur3-style avalanche of uint32 lanes (the kernel uses the same)."""
    x = x.astype(jnp.uint32)
    x ^= x >> 16
    x *= jnp.uint32(0x7FEB352D)
    x ^= x >> 15
    x *= jnp.uint32(0x846CA68B)
    x ^= x >> 16
    return x


def stream_scatter_add_ref(indices, values, size: int):
    """Scatter-add a flat stream into dense f32[size]; out-of-range dropped."""
    idx = indices.reshape(-1).astype(jnp.int32)
    val = values.reshape(-1).astype(jnp.float32)
    valid = (idx >= 0) & (idx < size)
    return jnp.zeros((size,), jnp.float32).at[
        jnp.where(valid, idx, 0)].add(jnp.where(valid, val, 0.0))


# Domain-separation salts for the counter-based pair-mask streams: one murmur
# stream for support indices, one for values, one for per-leaf seed folding.
# Both endpoints of a pair derive the same uint32 seed (repro/secagg), so the
# same counters yield the same (idx, |val|) draws and the signed values cancel.
IDX_SALT = 0x9E3779B9
VAL_SALT = 0x85EBCA6B
LEAF_SALT = 0xA511E9B3


def fold_leaf_seed(seeds: jax.Array, leaf_id) -> jax.Array:
    """Fold a (traced or static) leaf id into uint32 pair seeds.

    In-trace equivalent of deriving an independent counter stream per leaf;
    matches the kernel and the host reference (masks.pair_mask) bit for bit.
    """
    leaf = jnp.asarray(leaf_id).astype(jnp.uint32)
    return _mix32(jnp.asarray(seeds, jnp.uint32)
                  ^ _mix32(leaf + jnp.uint32(LEAF_SALT)))


def pair_mask_stream_ref(seeds, signs, nb: int, k_mask: int, m: int,
                         *, p: float, q: float):
    """Counter-based sparse pair-mask streams — the engine's mask data plane.

    For each pair seed (uint32[...]) generate ``nb`` blocks of ``k_mask``
    (index, value) slots: ``idx = mix32(mix32(seed^IDX_SALT) + c) % m`` and
    ``val = sign * (p + q * (mix32(mix32(seed^VAL_SALT) + c) >> 8) / 2**24)``
    with flat counter ``c = block * k_mask + slot`` — only the top 24 bits of
    the value draw are used (see the inline comment below; the 2^-24 grid is
    what makes colliding masks cancel bit-exactly in f32). Support indices
    MAY repeat
    (mod-m collisions); both endpoints generate identical duplicates, so every
    slot still cancels in the aggregate, and the unified-stream
    first-occurrence gate transmits the underlying gradient only once
    (tested end-to-end in tests/test_secagg_protocol.py).

    Returns ``(idx int32[..., nb, k_mask], vals f32[..., nb, k_mask])``.
    """
    seeds = jnp.asarray(seeds, jnp.uint32)
    signs = jnp.asarray(signs, jnp.float32)
    c = jnp.arange(nb * k_mask, dtype=jnp.uint32).reshape(nb, k_mask)
    c = c.reshape((1,) * seeds.ndim + (nb, k_mask))
    base_i = _mix32(seeds ^ jnp.uint32(IDX_SALT))[..., None, None]
    base_v = _mix32(seeds ^ jnp.uint32(VAL_SALT))[..., None, None]
    idx = (_mix32(base_i + c) % jnp.uint32(m)).astype(jnp.int32)
    # top 24 bits only: uniforms land on the f32-exact 2^-24 grid, so masks
    # colliding at one dense position still cancel bit-exactly in the
    # scatter-add (32-bit entropy would leave 1-ulp residue on collisions)
    u = (_mix32(base_v + c) >> 8).astype(jnp.float32) / jnp.float32(2**24)
    vals = signs[..., None, None] * (p + q * u)
    return idx, vals


# Domain-separation salts for the distributed-DP streams (core/dp.py,
# DESIGN.md §15): two independent murmur counter streams per client feed a
# Box-Muller transform, and one public stream draws the round's common
# release support. Distinct from IDX/VAL/LEAF_SALT so DP draws never
# collide with the pair-mask draws even under equal seeds.
DP_U1_SALT = 0x94D049BB
DP_U2_SALT = 0xBF58476D
DP_SUP_SALT = 0xC2B2AE35


def dp_support_stream_ref(seeds, nb: int, k: int, m: int):
    """Counter-based PUBLIC common-support indices for the DP data release.

    Under DP noise every client of a round releases gradient values at the
    SAME ``k`` positions per block — drawn here from a seed that is a pure
    function of (dp seed, round, leaf), never of the data. A data-dependent
    support (top-k) would leak through the transmitted indices and would
    spread each client's noise over slots the others don't share; a common
    public support makes the index release free and stacks every survivor's
    noise on every released coordinate (core/dp.py, DESIGN.md §15).

    Same draw discipline as the pair-mask support
    (:func:`pair_mask_stream_ref`): ``idx = mix32(mix32(seed^DP_SUP_SALT)
    + c) % m`` with flat counter ``c = block * k + slot``. Mod-``m``
    collisions MAY repeat an index inside a block; the unified stream's
    first-occurrence gate transmits the underlying gradient once, and the
    duplicate slot just carries one extra noise draw (privacy-conservative).
    Returns int32[..., nb, k].
    """
    seeds = jnp.asarray(seeds, jnp.uint32)
    c = jnp.arange(nb * k, dtype=jnp.uint32).reshape(nb, k)
    c = c.reshape((1,) * seeds.ndim + (nb, k))
    base = _mix32(seeds ^ jnp.uint32(DP_SUP_SALT))[..., None, None]
    return (_mix32(base + c) % jnp.uint32(m)).astype(jnp.int32)


def dp_noise_stream_ref(seeds, nb: int, k: int, *, sigma: float):
    """Counter-based grid-rounded Gaussian noise on the f32-exact 2^-24 grid.

    For each uint32 seed draw ``nb`` blocks of ``k`` noise values with flat
    counter ``c = block * k + slot`` — the same counter discipline as
    :func:`pair_mask_stream_ref`, so a resumed run replays the identical
    stream from (seed, leaf, slot) alone. Two murmur streams give 24-bit
    uniforms ``u1 in (0, 1]`` and ``u2 in [0, 1)``; Box-Muller maps them to a
    standard normal ``z``, and the emitted value is

        ``round(z * sigma * 2**24) * 2**-24``

    — an integer multiple of the mask grid. Pair masks are multiples of the
    same grid (the ``>> 8`` draw above), so masks + noise compose exactly in
    f32 scatter-adds while per-slot partial sums stay below 1 in magnitude
    (2^24 grid units — the identical headroom contract the mask plane has;
    DESIGN.md §15). This is a *rounded continuous* Gaussian — accounted as
    continuous by core/dp.py (the <= 2^-25 rounding perturbation is
    negligible against any practical sigma) — NOT the Canonne-Kamath-Steinke
    discrete Gaussian mechanism. Returns f32[..., nb, k].
    """
    seeds = jnp.asarray(seeds, jnp.uint32)
    c = jnp.arange(nb * k, dtype=jnp.uint32).reshape(nb, k)
    c = c.reshape((1,) * seeds.ndim + (nb, k))
    b1 = _mix32(seeds ^ jnp.uint32(DP_U1_SALT))[..., None, None]
    b2 = _mix32(seeds ^ jnp.uint32(DP_U2_SALT))[..., None, None]
    # u1 in (0, 1]: +1 keeps log(u1) finite; u2 in [0, 1) — top 24 bits only,
    # matching the mask draw's grid discipline
    u1 = ((_mix32(b1 + c) >> 8).astype(jnp.float32) + 1.0) / jnp.float32(2**24)
    u2 = (_mix32(b2 + c) >> 8).astype(jnp.float32) / jnp.float32(2**24)
    z = jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos(
        jnp.float32(2.0 * 3.141592653589793) * u2)
    q = jnp.round(z * jnp.float32(sigma) * jnp.float32(2**24))
    return q * jnp.float32(2.0 ** -24)


# --------------------------------------------------- wire-format bit packing
# Fixed-width bit packing of uint fields into uint32 words — the data plane of
# the StreamCodec wire stage (core/codecs.py, DESIGN.md §12). Rows are packed
# in chunks of 32 slots: a 32-slot chunk at field width ``w`` occupies exactly
# ``32*w`` bits = ``w`` words, so chunks never straddle a word boundary and the
# Pallas kernel (kernels/pack.py) can grid over (row tiles, chunks) with a
# statically-windowed output block. These refs use the identical per-chunk
# math, so kernel/ref parity is bit-exact by construction (pinned in
# tests/test_kernels.py).

PACK_CHUNK = 32  # slots per chunk; chunk bit-width = 32*width = width words


def _pack_chunk(u: jax.Array, width: int) -> jax.Array:
    """uint32[..., 32] fields (each < 2**width) -> uint32[..., width] words."""
    pos = jnp.arange(PACK_CHUNK, dtype=jnp.uint32) * jnp.uint32(width)
    j1 = (pos // 32).astype(jnp.int32)                       # low-bits word
    off = pos % 32
    lo = u << off                                            # wraps mod 2^32:
    # the dropped high bits are exactly the straddling part, re-emitted as hi
    sh = jnp.where(off == 0, jnp.uint32(0), jnp.uint32(32) - off)
    hi = jnp.where(off == 0, jnp.uint32(0), u >> sh)
    jj = jnp.arange(width, dtype=jnp.int32)                  # [width] words
    contrib = (jnp.where(jj == j1[:, None], lo[..., None], jnp.uint32(0))
               | jnp.where(jj == j1[:, None] + 1, hi[..., None],
                           jnp.uint32(0)))
    # fields within a word are disjoint, so an integer sum == bitwise OR
    return jnp.sum(contrib, axis=-2)


def _unpack_chunk(words: jax.Array, width: int) -> jax.Array:
    """uint32[..., width] words -> uint32[..., 32] fields (< 2**width)."""
    pos = jnp.arange(PACK_CHUNK, dtype=jnp.uint32) * jnp.uint32(width)
    j1 = (pos // 32).astype(jnp.int32)
    off = pos % 32
    jj = jnp.arange(width, dtype=jnp.int32)
    w1 = jnp.sum(jnp.where(jj == j1[:, None], words[..., None, :],
                           jnp.uint32(0)), axis=-1)
    w2 = jnp.sum(jnp.where(jj == j1[:, None] + 1, words[..., None, :],
                           jnp.uint32(0)), axis=-1)
    sh = jnp.where(off == 0, jnp.uint32(0), jnp.uint32(32) - off)
    u = (w1 >> off) | jnp.where(off == 0, jnp.uint32(0), w2 << sh)
    mask = jnp.uint32(0xFFFFFFFF if width == 32 else (1 << width) - 1)
    return u & mask


def packed_words(count: int, width: int) -> int:
    """uint32 words needed for ``count`` fields of ``width`` bits (host int)."""
    return -(-count * width // 32)


def bitpack_rows_ref(u: jax.Array, width: int) -> jax.Array:
    """Pack uint32[R, k] fields (each < 2**width) into uint32[R, W] words,
    W = ceil(k*width/32); big-endian-in-row, little-endian-in-word layout."""
    R, k = u.shape
    nc = -(-k // PACK_CHUNK)
    up = jnp.pad(u.astype(jnp.uint32), ((0, 0), (0, nc * PACK_CHUNK - k)))
    words = _pack_chunk(up.reshape(R, nc, PACK_CHUNK), width)
    return words.reshape(R, nc * width)[:, :packed_words(k, width)]


def bitunpack_rows_ref(words: jax.Array, k: int, width: int) -> jax.Array:
    """Inverse of :func:`bitpack_rows_ref`: uint32[R, W] -> uint32[R, k]."""
    R = words.shape[0]
    nc = -(-k // PACK_CHUNK)
    wp = jnp.pad(words.astype(jnp.uint32),
                 ((0, 0), (0, nc * width - words.shape[1])))
    u = _unpack_chunk(wp.reshape(R, nc, width), width)
    return u.reshape(R, nc * PACK_CHUNK)[:, :k]


def mask_prng_ref(g, seed: int, *, p: float, q: float, sigma: float,
                  sign: float = 1.0):
    """Counter-based sparse-mask generation + add (paper Eq. 3-5 data plane).

    u(i) = mix32(seed ^ i) mapped to [p, p+q); the mask is kept only where
    u(i) < sigma (expected support fraction (sigma-p)/q) and added to g.
    Returns (masked, mask) — both parties regenerate `mask` identically from
    the shared seed, so +/- copies cancel at the aggregator.
    """
    n = g.size
    idx = jnp.arange(n, dtype=jnp.uint32)
    h = _mix32(idx ^ jnp.uint32(seed))
    u = p + q * (h.astype(jnp.float32) / jnp.float32(2**32))
    mask = jnp.where(u < sigma, u, 0.0).reshape(g.shape) * sign
    return (g.astype(jnp.float32) + mask).astype(g.dtype), mask


def topk_compact_ref(acc, thr, need, k: int) -> jax.Array:
    """Per row of ``acc`` [R, m]: the ascending columns of the entries whose
    ``|acc|`` bits (``topk_select.keys_of``) exceed ``thr``, then of the
    first ``need`` equal to it — ``kernels/topk_select.topk_compact``'s
    output. int32[R, k]."""
    from repro.kernels.topk_select import keys_of

    key = keys_of(acc)
    t = thr.astype(jnp.int32)[:, None]
    eq = key == t
    sel = (key > t) | (eq & (jnp.cumsum(eq, axis=1, dtype=jnp.int32)
                             <= need.astype(jnp.int32)[:, None]))
    slot = jnp.where(sel, jnp.cumsum(sel, axis=1, dtype=jnp.int32) - 1, k)
    rows = jnp.arange(acc.shape[0])[:, None]
    cols = jnp.broadcast_to(jnp.arange(acc.shape[1], dtype=jnp.int32),
                            acc.shape)
    out = jnp.zeros((acc.shape[0], k + 1), jnp.int32).at[rows, slot].set(cols)
    return out[:, :k]
