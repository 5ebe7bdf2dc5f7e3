"""Plain reference of the MNIST-MLP 784-200-10 (paper Table 1), for the
CPU rehearsal of the benchmark's run path."""
import jax
import jax.numpy as jnp

DIMS = (784, 200, 10)
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
    return batch * sum(2 * a * b for a, b in zip(DIMS[:-1], DIMS[1:]))
