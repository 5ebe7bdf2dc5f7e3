"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

Each test lowers one kernel with ``interpret=False`` at VGG16 widths — its
largest leaf (2,359,296 params) under a 5-client secagg round — against a
described ``v5e:2x2`` topology, and checks that Mosaic accepted it
(``tpu_custom_call`` in the compiled module). Nothing runs: a pass says the
chip's compiler takes the tiling, not that results are right
(tests/test_kernels.py checks results in interpret mode, ``chip_smoke.py``
on the chip).

The topology is described only inside the fixtures: only one process at a
time may load the TPU compiler, and a worker that imports this file without
running it must not take it.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import codecs
from repro.core.types import SecureAggConfig
from repro.kernels import mask_prng, pack, stream_decode

LEAF = 2_359_296                      # VGG16's 3x3x512x512 conv kernel
COHORT = 5
K = math.ceil(0.05 * LEAF)            # THGS s0 = 0.05
K_MASK = SecureAggConfig(mask_ratio=0.01).k_mask_for(LEAF, COHORT)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent cache
    # but not read back, so keep the cache off around these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_for_chip(fn, one_chip, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("leaf,n", [
    # every client's k + C*k_mask slots, plus the C*C dropout-recovery streams
    pytest.param(LEAF, COHORT * (K + COHORT * K_MASK) + COHORT * COHORT
                 * K_MASK, id="vgg16"),
    # the benchmark's cifar_mlp l0.w (3072 x 1536) in a round with two drops:
    # 5 x (264,529 + 5 x 9,437) + 25 x 9,437 slots
    pytest.param(4_718_592, 1_794_495, id="cifar_mlp_l0w"),
])
def test_stream_scatter_add_compiles(one_chip, leaf, n):
    text = _compile_for_chip(
        lambda i, v: stream_decode.stream_scatter_add(i, v, leaf),
        one_chip, ((n,), jnp.int32), ((n,), jnp.float32))
    # the benchmark's roofline reader finds the kernel by this name
    assert re.search(r"%stream_scatter_add(\.\d+)? = ", text)


@pytest.mark.parametrize("n_pairs", [
    COHORT * (COHORT + 1) // 2,   # the encode's upper triangle
    COHORT * COHORT,              # dropout recovery's full matrix
])
def test_pair_mask_streams_compiles(one_chip, n_pairs):
    _compile_for_chip(
        lambda s, g: mask_prng.pair_mask_streams(s, g, nb=1, k_mask=K_MASK,
                                                 m=LEAF),
        one_chip, ((n_pairs,), jnp.uint32), ((n_pairs,), jnp.float32))


@pytest.mark.parametrize("width", [codecs.value_bits("int8"),
                                   codecs.index_width(LEAF)])
def test_bitpack_rows_compiles(one_chip, width):
    _compile_for_chip(lambda u: pack.bitpack_rows(u, width), one_chip,
                      ((COHORT, K), jnp.uint32))


@pytest.mark.parametrize("width", [codecs.value_bits("int8"),
                                   codecs.index_width(LEAF)])
def test_bitunpack_rows_compiles(one_chip, width):
    words = -(-K * width // 32)
    _compile_for_chip(lambda w: pack.bitunpack_rows(w, K, width), one_chip,
                      ((COHORT, words), jnp.uint32))


def test_decode_product_runs_at_highest_precision():
    """The one-hot scatter product keeps f32 values exact: a bf16-pass MXU
    product would round them and break pair-mask cancellation."""
    jaxpr = jax.make_jaxpr(
        lambda i, v: stream_decode.stream_scatter_add(i, v, 1024,
                                                      interpret=True))(
        jnp.zeros((512,), jnp.int32), jnp.zeros((512,), jnp.float32))
    assert "Precision.HIGHEST" in str(jaxpr)
