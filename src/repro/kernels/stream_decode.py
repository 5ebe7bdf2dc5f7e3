"""Pallas TPU kernel: fused sparse-stream scatter-add (the server decode).

The secure-aggregation server's hot loop (DESIGN.md §3): all clients' unified
streams — one flat (indices, values) vector after weighting/liveness gating —
scatter-added into the dense update buffer in ONE pass over HBM. Every dense
tile is written exactly once while the stream chunks that hold its slots
cycle through VMEM.

Scatter on TPU is formulated MXU-style: for a dense tile [TR, LANE] and a
stream chunk of KC entries, build the row one-hot [TR, KC] and lane one-hot
[KC, LANE] and contract — ``tile += rowhot @ (vals * lanehot)``. Duplicate
indices accumulate through the contraction, matching scatter-add semantics.

The wrapper first sorts the stream by index (stable, so equal indices keep
their slot order). Each tile's slots are then one contiguous range of the
sorted stream, and the kernel visits only the (tile, chunk) pairs that
overlap: a work list built on the device from the tile ranges, of static
length ``grid_steps`` = chunks + tiles, run as a 1-D grid whose index maps
read the list from scalar prefetch. The list is monotone in the tile, so
each tile's items are consecutive and its output block stays resident in
VMEM until it is written back once. Every tile has at least one item, the
first of which zeroes it, so an empty tile reads back as zeros. The chunks
are windows of the whole sorted stream (chunk ``j`` = sorted slots
``[KC*j, KC*j + KC)``), never windows relative to a tile: a decode of a
sub-range of the buffer (the tree decode) then groups each position's slots
into the same contractions as the flat decode, bit for bit. Entries with
index outside [0, size) — e.g. the -1 padding the wrapper adds, which sorts
first — are dropped by each step's in-range test.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128


def _tiles_and_chunks(n: int, size: int, tile_rows: int,
                      chunk: int) -> tuple[int, int]:
    rows = -(-size // LANE)
    return -(-rows // tile_rows), -(-max(n, 1) // chunk)


def grid_steps(n: int, size: int, tile_rows: int = 64,
               chunk: int = 512) -> int:
    """Grid length of :func:`stream_scatter_add` for an ``n``-slot stream
    into a dense ``size``: one step per stream chunk plus one per dense tile,
    the most (tile, chunk) pairs a sorted stream can make overlap."""
    n_tiles, n_chunks = _tiles_and_chunks(n, size, tile_rows, chunk)
    return n_chunks + n_tiles


def work_list(sorted_idx: jax.Array, n_tiles: int, tile: int, chunk: int,
              steps: int) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The (tile, chunk) pairs whose slots overlap, from an index-sorted
    stream padded to whole chunks: ``(tile_of, chunk_of, live)``, int32 of
    length ``steps``. Monotone in the tile; every tile has at least one item
    (``live`` 0 for a tile with no slot); the items after the last repeat its
    tile and chunk with ``live`` 0."""
    n_chunks = sorted_idx.shape[0] // chunk
    bases = jnp.arange(n_tiles + 1, dtype=jnp.int32) * tile
    edges = jnp.searchsorted(sorted_idx, bases, side="left").astype(jnp.int32)
    lo, hi = edges[:-1], edges[1:]
    nonempty = hi > lo
    first = jnp.minimum(lo // chunk, n_chunks - 1)
    last = jnp.where(nonempty, (hi - 1) // chunk, first)
    cnt = last - first + 1
    end = jnp.cumsum(cnt)                           # each tile's items end
    w = jnp.arange(steps, dtype=jnp.int32)
    tile_of = jnp.minimum(jnp.searchsorted(end, w, side="right"),
                          n_tiles - 1).astype(jnp.int32)
    start = (end - cnt)[tile_of]
    chunk_of = jnp.minimum(first[tile_of] + w - start, last[tile_of])
    live = ((w < end[-1]) & nonempty[tile_of]).astype(jnp.int32)
    return tile_of, chunk_of.astype(jnp.int32), live


def _kernel(tile_ref, chunk_ref, live_ref, idx_ref, val_ref, o_ref, *,
            tile_rows: int):
    del chunk_ref  # read by the index maps only
    w = pl.program_id(0)
    t = tile_ref[w]

    @pl.when((w == 0) | (tile_ref[jnp.maximum(w - 1, 0)] != t))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live_ref[w] == 1)
    def _accumulate():
        idx = idx_ref[...]                       # int32[1, KC]
        val = val_ref[...]                       # f32 [1, KC]
        kc = idx.shape[1]
        rel = idx - t * (tile_rows * LANE)
        inrange = (rel >= 0) & (rel < tile_rows * LANE)
        rel_c = jnp.where(inrange, rel, 0)
        row = rel_c // LANE                      # [1, KC]
        lane = rel_c % LANE                      # [1, KC]

        row_iota = jax.lax.broadcasted_iota(jnp.int32, (tile_rows, kc), 0)
        rowhot = ((row_iota == row) & inrange).astype(jnp.float32)  # [TR, KC]
        lane_iota = jax.lax.broadcasted_iota(jnp.int32, (kc, LANE), 1)
        lanehot = (lane_iota == lane.reshape(kc, 1)).astype(jnp.float32)
        weighted = val.reshape(kc, 1) * lanehot                      # [KC, LANE]
        # HIGHEST: a bf16-pass MXU product would round the stream values, and
        # pair masks cancel only if grid values survive the decode exactly
        o_ref[...] += jax.lax.dot(rowhot, weighted,
                                  precision=jax.lax.Precision.HIGHEST,
                                  preferred_element_type=jnp.float32)


def stream_scatter_add(
    indices: jax.Array,        # int32[n] flat indices; out-of-range dropped
    values: jax.Array,         # [n] accumulated as f32
    size: int,
    *,
    tile_rows: int = 64,
    chunk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """One-HBM-pass scatter-add of a flat stream into a dense f32[size]."""
    n = indices.shape[0]
    n_tiles, n_chunks = _tiles_and_chunks(n, size, tile_rows, chunk)
    steps = grid_steps(n, size, tile_rows, chunk)
    pad_n = n_chunks * chunk - n
    idx = jnp.pad(indices.reshape(-1).astype(jnp.int32), (0, pad_n),
                  constant_values=-1)
    val = jnp.pad(values.reshape(-1).astype(jnp.float32), (0, pad_n))
    idx, val = jax.lax.sort((idx, val), num_keys=1, is_stable=True)
    tile_of, chunk_of, live = work_list(idx, n_tiles, tile_rows * LANE,
                                        chunk, steps)
    # (n_chunks, 1, chunk), the leading axis squeezed from the block: the
    # block's last two dims then equal the array's, as Mosaic requires
    idx3 = idx.reshape(n_chunks, 1, chunk)
    val3 = val.reshape(n_chunks, 1, chunk)

    def chunk_map(w, tile_of, chunk_of, live):
        return chunk_of[w], 0, 0

    dense = pl.pallas_call(
        functools.partial(_kernel, tile_rows=tile_rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(steps,),
            in_specs=[pl.BlockSpec((None, 1, chunk), chunk_map),
                      pl.BlockSpec((None, 1, chunk), chunk_map)],
            out_specs=pl.BlockSpec(
                (tile_rows, LANE),
                lambda w, tile_of, chunk_of, live: (tile_of[w], 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((n_tiles * tile_rows, LANE),
                                       jnp.float32),
        interpret=interpret,
        name="stream_scatter_add",
    )(tile_of, chunk_of, live, idx3, val3)
    return dense.reshape(-1)[:size]
