"""Pallas TPU kernel: fused sparse-stream scatter-add (the server decode).

The secure-aggregation server's hot loop (DESIGN.md §3): all clients' unified
streams — one flat (indices, values) vector after weighting/liveness gating —
scatter-added into the dense update buffer in ONE pass over HBM. The seed
implementation re-read and re-wrote the dense buffer once per client; this
kernel writes every dense tile exactly once while the (small) stream chunks
cycle through VMEM.

Scatter on TPU is formulated MXU-style: for a dense tile [TR, LANE] and a
stream chunk of KC entries, build the row one-hot [TR, KC] and lane one-hot
[KC, LANE] and contract — ``tile += rowhot @ (vals * lanehot)``. Duplicate
indices accumulate through the contraction, matching scatter-add semantics.

Grid = (dense tiles, stream chunks); the output tile's index map ignores the
chunk axis, so the tile stays resident in VMEM and accumulates across the
inner grid dimension (the standard Pallas reduction pattern). Entries with
index outside [0, size) — e.g. the -1 padding the wrapper adds — are dropped.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128


def _kernel(idx_ref, val_ref, o_ref, *, tile_rows: int):
    i = pl.program_id(0)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    idx = idx_ref[...]                       # int32[1, KC]
    val = val_ref[...]                       # f32 [1, KC]
    kc = idx.shape[1]
    base = i * tile_rows * LANE
    rel = idx - base
    inrange = (rel >= 0) & (rel < tile_rows * LANE)
    rel_c = jnp.where(inrange, rel, 0)
    row = rel_c // LANE                      # [1, KC]
    lane = rel_c % LANE                      # [1, KC]

    row_iota = jax.lax.broadcasted_iota(jnp.int32, (tile_rows, kc), 0)
    rowhot = ((row_iota == row) & inrange).astype(jnp.float32)   # [TR, KC]
    lane_iota = jax.lax.broadcasted_iota(jnp.int32, (kc, LANE), 1)
    lanehot = (lane_iota == lane.reshape(kc, 1)).astype(jnp.float32)
    weighted = val.reshape(kc, 1) * lanehot                       # [KC, LANE]
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
    rows = -(-size // LANE)
    n_tiles = -(-rows // tile_rows)
    pad_n = -(-max(n, 1) // chunk) * chunk - n
    idx = jnp.pad(indices.reshape(-1).astype(jnp.int32), (0, pad_n),
                  constant_values=-1)
    val = jnp.pad(values.reshape(-1).astype(jnp.float32), (0, pad_n))
    n_chunks = idx.shape[0] // chunk
    # (n_chunks, 1, chunk), the leading axis squeezed from the block: the
    # block's last two dims then equal the array's, as Mosaic requires
    idx3 = idx.reshape(n_chunks, 1, chunk)
    val3 = val.reshape(n_chunks, 1, chunk)

    dense = pl.pallas_call(
        functools.partial(_kernel, tile_rows=tile_rows),
        grid=(n_tiles, n_chunks),
        in_specs=[
            pl.BlockSpec((None, 1, chunk), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((None, 1, chunk), lambda i, j: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((tile_rows, LANE), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_tiles * tile_rows, LANE),
                                       jnp.float32),
        interpret=interpret,
    )(idx3, val3)
    return dense.reshape(-1)[:size]
