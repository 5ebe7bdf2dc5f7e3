"""Pallas TPU kernels: fixed-width bit packing for the stream wire format.

The StreamCodec stage (core/codecs.py, DESIGN.md §12) ships quantized stream
values and delta-encoded sparse indices as dense fields of ``width`` bits
packed into uint32 words. Rows are processed in 32-slot chunks: a chunk at
field width ``w`` occupies exactly ``32*w`` bits = ``w`` whole words, so
chunks never straddle word boundaries (the layout of ref.py's
``bitpack_rows_ref``, bit for bit — pinned in tests/test_kernels.py over odd
sizes and padding).

Chunks are independent, so the wrapper lays them out slot-major: the
``[R, nc*32]`` fields become a ``[32, N]`` array (``N = R*nc`` chunks, one
per lane) and the words a ``[width, N]`` one. Slot ``j`` of every chunk is
then one sublane row, and its word index ``(j*w) // 32`` and bit offset
``(j*w) % 32`` are static, so the kernel is 32 unrolled shift/or steps on
``(1, TILE)`` rows — no in-kernel lane-splitting reshape. Blocks are
``(32, TILE)`` and ``(width, TILE)``: full first dims and a lane dim that
is a multiple of 128, which Mosaic's tiling rule needs for every width.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import PACK_CHUNK, packed_words

LANE = 128
TILE = 4 * LANE     # chunks per grid step


def _fields(width: int):
    """Static (slot, low word, bit offset, straddles) for one chunk."""
    for j in range(PACK_CHUNK):
        pos = j * width
        off = pos % 32
        yield j, pos // 32, off, off > 0 and off + width > 32


def _pack_kernel(u_ref, o_ref, *, width: int):
    words = [jnp.zeros((1, u_ref.shape[1]), jnp.uint32)
             for _ in range(width)]
    for j, w, off, straddles in _fields(width):
        u = u_ref[j:j + 1, :]
        words[w] = words[w] | (u << off)
        if straddles:
            words[w + 1] = words[w + 1] | (u >> (32 - off))
    for w in range(width):
        o_ref[w:w + 1, :] = words[w]


def _unpack_kernel(w_ref, o_ref, *, width: int):
    mask = jnp.uint32(0xFFFFFFFF if width == 32 else (1 << width) - 1)
    for j, w, off, straddles in _fields(width):
        u = w_ref[w:w + 1, :] >> off
        if straddles:
            u = u | (w_ref[w + 1:w + 2, :] << (32 - off))
        o_ref[j:j + 1, :] = u & mask


def _lanes(n_chunks: int) -> int:
    return -(-max(n_chunks, 1) // TILE) * TILE


def bitpack_rows(u: jax.Array, width: int, *,
                 interpret: bool = False) -> jax.Array:
    """Pack uint32[R, k] fields (each < ``2**width``) into uint32[R, W] words,
    ``W = ceil(k*width/32)``. Padding slots are zero bits; padded chunks and
    words are sliced off before returning."""
    R, k = u.shape
    nc = -(-k // PACK_CHUNK)
    n = R * nc
    lanes = _lanes(n)
    up = jnp.pad(u.astype(jnp.uint32), ((0, 0), (0, nc * PACK_CHUNK - k)))
    slots = jnp.pad(up.reshape(n, PACK_CHUNK).T, ((0, 0), (0, lanes - n)))
    words = pl.pallas_call(
        functools.partial(_pack_kernel, width=width),
        grid=(lanes // TILE,),
        in_specs=[pl.BlockSpec((PACK_CHUNK, TILE), lambda i: (0, i))],
        out_specs=pl.BlockSpec((width, TILE), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((width, lanes), jnp.uint32),
        interpret=interpret,
    )(slots)
    return words[:, :n].T.reshape(R, nc * width)[:, :packed_words(k, width)]


def bitunpack_rows(words: jax.Array, k: int, width: int, *,
                   interpret: bool = False) -> jax.Array:
    """Inverse of :func:`bitpack_rows`: uint32[R, W] words -> uint32[R, k]
    fields, each < ``2**width``."""
    R = words.shape[0]
    nc = -(-k // PACK_CHUNK)
    n = R * nc
    lanes = _lanes(n)
    wp = jnp.pad(words.astype(jnp.uint32),
                 ((0, 0), (0, nc * width - words.shape[1])))
    wt = jnp.pad(wp.reshape(n, width).T, ((0, 0), (0, lanes - n)))
    u = pl.pallas_call(
        functools.partial(_unpack_kernel, width=width),
        grid=(lanes // TILE,),
        in_specs=[pl.BlockSpec((width, TILE), lambda i: (0, i))],
        out_specs=pl.BlockSpec((PACK_CHUNK, TILE), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((PACK_CHUNK, lanes), jnp.uint32),
        interpret=interpret,
    )(wt)
    return u[:, :n].T.reshape(R, nc * PACK_CHUNK)[:, :k]
