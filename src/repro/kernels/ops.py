"""Jit'd public wrappers for the Pallas kernels.

Off-TPU the kernels execute in interpret mode — the kernel body runs as
traced jnp ops, which is how correctness is validated against ref.py. On TPU
they compile to Mosaic. The main-path kernels (``stream_scatter_add``,
``pair_mask_streams``, ``bitpack_rows``/``bitunpack_rows``) are compiled for
a described v5e in tests/test_tpu_compile.py and checked on the chip by
chip_smoke.py. ``flash_attention``, ``thgs_sparsify`` and
``mask_prng_apply`` have no caller on the main path and have not been
compiled for a chip (``mask_prng_apply``'s uint32 -> f32 cast is one Mosaic
refuses).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.mask_prng import mask_prng_apply as _mask
from repro.kernels.mask_prng import pair_mask_streams as _pair_streams
from repro.kernels.pack import bitpack_rows as _bitpack
from repro.kernels.pack import bitunpack_rows as _bitunpack
from repro.kernels.stream_decode import stream_scatter_add as _scatter
from repro.kernels.thgs_sparsify import thgs_sparsify as _thgs


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_kv"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    block_q: int = 128, block_kv: int = 128):
    return _flash(q, k, v, causal=causal, window=window, block_q=block_q,
                  block_kv=block_kv, interpret=_interpret())


@jax.jit
def thgs_sparsify(g, residual, threshold):
    return _thgs(g, residual, threshold, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("seed", "p", "q", "sigma", "sign"))
def mask_prng_apply(g, *, seed: int, p: float = -1.0, q: float = 2.0,
                    sigma: float, sign: float = 1.0):
    return _mask(g, seed, p=p, q=q, sigma=sigma, sign=sign,
                 interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("size", "tile_rows", "chunk"))
def stream_scatter_add(indices, values, *, size: int, tile_rows: int = 64,
                       chunk: int = 512):
    """Fused server decode: flat stream -> dense f32[size] in one HBM pass."""
    return _scatter(indices, values, size, tile_rows=tile_rows, chunk=chunk,
                    interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("nb", "k_mask", "m", "p", "q"))
def pair_mask_streams(seeds, signs, *, nb: int, k_mask: int, m: int,
                      p: float = -1.0, q: float = 2.0):
    """All of a round's pair-mask streams in one fused pass (Eq. 3-4).

    uint32 seeds + f32 signs, one per active pair -> counter-based
    ``(idx, vals)`` support streams. Pallas kernel on TPU; the bit-identical
    jnp oracle elsewhere (the ref IS the fallback — it vmaps/traces freely
    inside the batched encode, interpret-mode kernel parity is pinned in
    tests/test_kernels.py).
    """
    if _interpret():
        return ref.pair_mask_stream_ref(seeds, signs, nb, k_mask, m, p=p, q=q)
    return _pair_streams(seeds, signs, nb=nb, k_mask=k_mask, m=m, p=p, q=q)


@functools.partial(jax.jit, static_argnames=("width",))
def bitpack_rows(u, *, width: int):
    """Pack uint32[R, k] fields of ``width`` bits into uint32 words — the
    StreamCodec wire data plane (core/codecs.py, DESIGN.md §12). Pallas
    kernel on TPU; the chunk-identical jnp oracle elsewhere (the ref IS the
    fallback — interpret-mode kernel parity is pinned in
    tests/test_kernels.py)."""
    if _interpret():
        return ref.bitpack_rows_ref(u, width)
    return _bitpack(u, width)


@functools.partial(jax.jit, static_argnames=("k", "width"))
def bitunpack_rows(words, *, k: int, width: int):
    """Inverse of :func:`bitpack_rows`: words -> uint32[R, k] fields."""
    if _interpret():
        return ref.bitunpack_rows_ref(words, k, width)
    return _bitunpack(words, k, width)
