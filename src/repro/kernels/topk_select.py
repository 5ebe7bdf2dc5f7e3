"""Pallas TPU kernel: exact top-k of |acc| by threshold and compaction.

The encode's top-k (DESIGN.md §3) over rows of millions of entries. On a
TPU ``lax.top_k`` sorts every row whole; this path needs no sort.

1. :func:`row_thresholds` (plain XLA): the k-th largest ``|acc|`` of each
   row, found by bisection on its float32 bits. For a non-negative float the
   int32 order of the bits is the float order, so count passes build the
   largest key ``T`` with ``count(key >= T) >= k`` from the top bit down,
   two of the 31 bits a pass (three candidate counts, one read of the row).
   ``need = k - count(key > T)`` of the entries equal to ``T`` are
   taken, lowest index first, which is ``lax.top_k``'s rule for ties.
2. :func:`topk_compact` (this kernel): one sequential pass over each row in
   blocks of ``block_rows x 128`` entries writes the column of every entry
   above ``T`` and of the first ``need`` equal to it, in ascending order.
   Per block, two MXU products give the inclusive count of the selected
   entries before each one (a triangular ones matrix over the lanes, a
   strictly lower one over the rows; every operand is a 0/1 or a count of
   at most 128, exact in bfloat16, and the sums are exact in float32). Each
   selected entry then has to move left, from its slot ``1024 + j`` of a
   window that starts one (8, 128) group ahead of the block, to its output
   slot ``off % 1024 + rank``. The moves are done one bit of the distance at
   a time, lowest bit first: the distances do not fall along the row, so no
   two entries ever meet. The window is then added into the row's output
   (resident in VMEM, zeroed at the row's first block) at group
   ``off // 1024``; the running offset and the count of ties seen so far
   carry across the row's blocks in SMEM.

Entries past the row's end (the last block may overhang it) are never
selected. The output is exact: nothing is approximated or sampled.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
GROUP = 8 * LANE            # one (8, 128) int32 tile of the output
BLOCK_ROWS = 512            # 65,536 entries a grid step
KEY_BITS = 31               # a non-negative float32's bits, sign cleared


def keys_of(acc: jax.Array) -> jax.Array:
    """int32 bits of ``|acc|``: their order is the order of the magnitudes
    (``-0.0`` and ``+0.0`` both read 0)."""
    bits = jax.lax.bitcast_convert_type(acc.astype(jnp.float32), jnp.int32)
    return bits & jnp.int32(0x7FFFFFFF)


def row_view(acc: jax.Array) -> jax.Array:
    """[R, m] -> f32[R, ceil(m / 128), 128], the row's end padded with
    zeros: on a TPU the (8, 128) tiles then hold no padding rows, where
    [R, m] with R = 5 pads every tile to 8 rows."""
    R, m = acc.shape
    rows = -(-m // LANE)
    acc = acc.astype(jnp.float32)
    if rows * LANE != m:
        acc = jnp.pad(acc, ((0, 0), (0, rows * LANE - m)))
    return acc.reshape(R, rows, LANE)


def row_thresholds(acc3: jax.Array, k: int,
                   bits: int = 2) -> tuple[jax.Array, jax.Array]:
    """Per row of ``acc3`` [R, rows, 128] (:func:`row_view`): the key of
    the k-th largest ``|acc|`` and how many entries equal to it the top k
    take, both int32[R]. Each pass over the row counts the keys at or above
    the ``2**bits - 1`` candidates that decide the next ``bits`` bits; the
    zeros that pad the row never count, every candidate being above 0."""
    keys = keys_of(acc3)
    t = jnp.zeros((acc3.shape[0],), jnp.int32)
    top = KEY_BITS
    while top > 0:
        width = min(bits, top)
        low = top - width
        cands = [t | jnp.int32(j << low) for j in range(1, 1 << width)]
        for cand in cands:          # counts fall as the candidates rise
            count = jnp.sum(keys >= cand[:, None, None], axis=(1, 2),
                            dtype=jnp.int32)
            t = jnp.where(count >= k, cand, t)
        top = low
    above = jnp.sum(keys > t[:, None, None], axis=(1, 2), dtype=jnp.int32)
    return t, k - above


def out_groups(k: int, block_rows: int = BLOCK_ROWS) -> int:
    """(8, 128) groups of the kernel's output row: the k slots, and room for
    the last block's window to overhang them."""
    return k // GROUP + block_rows // 8 + 1


def _shift_left(v: jax.Array, s: int) -> jax.Array:
    """``v`` read in row-major order, moved ``s`` places towards the front;
    the places it leaves at the end read -1."""
    rows = v.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
    if s % LANE == 0:
        q = s // LANE
        return jnp.where(row < rows - q, pltpu.roll(v, rows - q, 0), -1)
    lane = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    same = pltpu.roll(v, LANE - s, 1)           # [a, l] = v[a, l + s]
    below = pltpu.roll(same, rows - 1, 0)       # [a, l] = same[a + 1, l]
    out = jnp.where(lane < LANE - s, same, below)
    return jnp.where((row == rows - 1) & (lane >= LANE - s), -1, out)


def _prefix(mask: jax.Array, tri_ref, low_ref) -> jax.Array:
    """Inclusive count of True in row-major order over a [rows, 128] mask."""
    prec = jax.lax.Precision.DEFAULT            # 0/1 and counts <= 128: exact
    lanes = jnp.dot(mask.astype(jnp.bfloat16), tri_ref[...], precision=prec,
                    preferred_element_type=jnp.float32)   # [rows, 256]
    above = jnp.dot(low_ref[...], lanes[:, LANE:].astype(jnp.bfloat16),
                    precision=prec, preferred_element_type=jnp.float32)
    return (above + lanes[:, :LANE]).astype(jnp.int32)


def _kernel(thr_ref, need_ref, acc_ref, tri_ref, low_ref, o_ref, win_ref,
            carry_ref, *, m: int, block_rows: int, n_bits: int):
    r = pl.program_id(0)
    b = pl.program_id(1)

    @pl.when(b == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        carry_ref[0] = 0                        # slots written so far
        carry_ref[1] = 0                        # entries equal to T so far

    shape = (block_rows, LANE)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    j = row * LANE + lane                       # place in the block
    live = b * (block_rows * LANE) + j < m
    key = keys_of(acc_ref[...])
    t = thr_ref[r]
    gt = live & (key > t)
    eq = live & (key == t)
    sel = gt | (eq & (_prefix(eq, tri_ref, low_ref)
                      <= need_ref[r] - carry_ref[1]))
    rank = _prefix(sel, tri_ref, low_ref)       # 1-based slot in the block
    off = carry_ref[0]
    # distance from window slot GROUP + j to output slot off % GROUP + rank-1
    dist = GROUP - off % GROUP + j + 1 - rank
    win_ref[:8, :] = jnp.full((8, LANE), -1, jnp.int32)
    win_ref[8:, :] = jnp.where(sel, dist, -1)
    w = win_ref[...]
    for i in range(n_bits):
        moved = _shift_left(w, 1 << i)
        take = (moved >= 0) & ((moved >> i) & 1 == 1)
        stay = (w >= 0) & ((w >> i) & 1 == 0)
        w = jnp.where(take, moved, jnp.where(stay, w, -1))
    wrow = jax.lax.broadcasted_iota(jnp.int32, w.shape, 0)
    wlane = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
    col = b * (block_rows * LANE) + wrow * LANE + wlane + w - GROUP
    out = jnp.where(w >= 0, col, 0).reshape(block_rows // 8 + 1, 8, LANE)
    g0 = off // GROUP
    o_ref[pl.ds(g0, block_rows // 8 + 1)] += out
    carry_ref[0] = off + jnp.sum(sel.astype(jnp.int32))
    carry_ref[1] = carry_ref[1] + jnp.sum(eq.astype(jnp.int32))


def vmem_bytes(k: int, block_rows: int = BLOCK_ROWS) -> int:
    """VMEM the kernel's buffers take (each pipelined buffer twice), plus
    room for the window's temporaries."""
    out = out_groups(k, block_rows) * GROUP * 4
    blk = block_rows * LANE * 4
    low = block_rows * block_rows * 2
    tri = LANE * 2 * LANE * 2
    win = (block_rows + 8) * LANE * 4
    return 2 * (out + blk + low + tri) + 8 * win


def fits_vmem(k: int, limit: int = 64 << 20) -> bool:
    """Whether the kernel's VMEM for top ``k`` stays within ``limit`` (a v5e
    core has 128 MiB): its output row is resident there whole."""
    return vmem_bytes(k) <= limit


def topk_compact(acc3: jax.Array, thr: jax.Array, need: jax.Array, k: int,
                 m: int, *, block_rows: int = BLOCK_ROWS,
                 interpret: bool = False) -> jax.Array:
    """int32[R, k]: per row of ``acc3`` (:func:`row_view` of an [R, m]
    array), the ascending columns of the entries whose key is above ``thr``
    and of the first ``need`` equal to it."""
    R, rows, _ = acc3.shape
    block_rows = min(block_rows, -(-rows // 8) * 8)
    n_blocks = -(-rows // block_rows)
    groups = out_groups(k, block_rows)
    n_bits = (GROUP + block_rows * LANE - 1).bit_length()
    idx = jnp.arange(LANE)
    tri = jnp.concatenate(
        [(idx[:, None] <= idx[None, :]), jnp.ones((LANE, LANE), bool)],
        axis=1).astype(jnp.bfloat16)                 # [128, 256]
    br = jnp.arange(block_rows)
    low = (br[None, :] < br[:, None]).astype(jnp.bfloat16)  # [BR, BR]

    out = pl.pallas_call(
        functools.partial(_kernel, m=m, block_rows=block_rows,
                          n_bits=n_bits),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(R, n_blocks),
            in_specs=[
                pl.BlockSpec((None, block_rows, LANE),
                             lambda r, b, thr, need: (r, b, 0)),
                pl.BlockSpec((LANE, 2 * LANE),
                             lambda r, b, thr, need: (0, 0)),
                pl.BlockSpec((block_rows, block_rows),
                             lambda r, b, thr, need: (0, 0)),
            ],
            out_specs=pl.BlockSpec((None, groups, 8, LANE),
                                   lambda r, b, thr, need: (r, 0, 0, 0)),
            scratch_shapes=[pltpu.VMEM((block_rows + 8, LANE), jnp.int32),
                            pltpu.SMEM((2,), jnp.int32)],
        ),
        out_shape=jax.ShapeDtypeStruct((R, groups, 8, LANE), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(vmem_bytes(k, block_rows), 16 << 20)),
        interpret=interpret,
        name="topk_select",
    )(thr.astype(jnp.int32), need.astype(jnp.int32), acc3, tri, low)
    return out.reshape(R, groups * GROUP)[:, :k]
