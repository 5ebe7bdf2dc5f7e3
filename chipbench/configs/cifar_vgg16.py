"""Plain reference of ``cifar_vgg16``: VGG16 with BatchNorm on 32x32x3
(paper Table 1, 14,728,266 params).

Thirteen 3x3 SAME convolutions (bias, then BatchNorm over the batch's
statistics, then ReLU) with 2x2 max pools after the 2nd, 4th, 7th, 10th and
13th, then a 512-10 linear head. Plain ``jax.numpy``/``lax`` at
``Precision.HIGHEST``. Weights follow the repository's published recipe
(``jax.random.split(key, 16)``: key ``i`` for conv ``i``, key 14 for the
head; He-normal weights, zero biases, unit BatchNorm scales), so a seed gives
the same initial weights on both sides without the reference taking them
from the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

CFG = (64, 64, "M", 128, 128, "M", 256, 256, 256, "M",
       512, 512, 512, "M", 512, 512, 512, "M")
HI = jax.lax.Precision.HIGHEST


def init(key, dtype=jnp.float32) -> dict:
    keys = jax.random.split(key, 16)
    params, cin, i = {}, 3, 0
    for v in CFG:
        if v == "M":
            continue
        scale = (2.0 / (9 * cin)) ** 0.5
        params[f"c{i}"] = {
            "w": (scale * jax.random.normal(keys[i], (3, 3, cin, v))
                  ).astype(dtype),
            "b": jnp.zeros((v,), dtype)}
        params[f"bn{i}"] = {"scale": jnp.ones((v,), dtype),
                            "bias": jnp.zeros((v,), dtype)}
        cin, i = v, i + 1
    params["head"] = {
        "w": ((2.0 / 512) ** 0.5
              * jax.random.normal(keys[14], (512, 10))).astype(dtype),
        "b": jnp.zeros((10,), dtype)}
    return params


def _batchnorm(p, x, eps=1e-5):
    mu = jnp.mean(x, axis=(0, 1, 2), keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=(0, 1, 2), keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def forward(params: dict, x: jax.Array) -> jax.Array:
    h, i = x, 0
    for v in CFG:
        if v == "M":
            h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max,
                                      (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
            continue
        h = jax.lax.conv_general_dilated(
            h, params[f"c{i}"]["w"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=HI) + params[f"c{i}"]["b"]
        h = jax.nn.relu(_batchnorm(params[f"bn{i}"], h))
        i += 1
    h = h.reshape(h.shape[0], -1)
    return jnp.dot(h, params["head"]["w"], precision=HI) + params["head"]["b"]


def forward_flops(batch: int = 1) -> int:
    """Multiply-add FLOPs of one forward pass (2 per MAC) of the 13 convs
    and the head. A 3x3 SAME convolution over an H x H image needs only the
    taps that fall inside the image, (3H - 2)^2 per channel pair (the
    padding's zeros need no work); bias, BatchNorm, ReLU and pooling are
    left out."""
    flops, hw, cin = 0, 32, 3
    for v in CFG:
        if v == "M":
            hw //= 2
            continue
        flops += 2 * cin * v * (3 * hw - 2) ** 2
        cin = v
    return batch * (flops + 2 * 512 * 10)
