"""Plain reference of ``cifar_mlp``: MLP 3072-1536-690-102-10 (paper Table 1).

Straightforward ``jax.numpy`` at ``Precision.HIGHEST``, no kernels, no
batching tricks. Weights follow the published recipe the repository uses
(He-normal ``sqrt(2 / n_in)`` weights from ``jax.random.split(key, 4)``,
zero biases), so a seed gives the same initial weights on both sides without
the reference taking them from the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

DIMS = (3072, 1536, 690, 102, 10)
HI = jax.lax.Precision.HIGHEST


def init(key, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, len(DIMS) - 1)
    return {f"l{i}": {
        "w": ((2.0 / DIMS[i]) ** 0.5
              * jax.random.normal(ks[i], (DIMS[i], DIMS[i + 1]))).astype(dtype),
        "b": jnp.zeros((DIMS[i + 1],), dtype)}
        for i in range(len(DIMS) - 1)}


def forward(params: dict, x: jax.Array) -> jax.Array:
    h = x.reshape(x.shape[0], -1)
    for i in range(len(DIMS) - 1):
        h = jnp.dot(h, params[f"l{i}"]["w"], precision=HI) + params[f"l{i}"]["b"]
        if i < len(DIMS) - 2:
            h = jax.nn.relu(h)
    return h


def forward_flops(batch: int = 1) -> int:
    """Multiply-add FLOPs of one forward pass (2 per MAC); bias adds and
    ReLUs are left out, as in the usual model-FLOP count."""
    return batch * sum(2 * a * b for a, b in zip(DIMS[:-1], DIMS[1:]))
