"""Plain reference of the tiny conv + BatchNorm model of the CPU rehearsal:
two 3x3 SAME convolutions of 8 channels (bias, then BatchNorm over the
batch's statistics, then ReLU, then a 2x2 max pool) and a 392-10 head on
28x28x1; VGG16's block at a CPU test's size."""
import jax
import jax.numpy as jnp

CH = (1, 8, 8)
HI = jax.lax.Precision.HIGHEST


def init(key, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 3)
    params = {}
    for i in range(2):
        params[f"c{i}"] = {
            "w": ((2.0 / (9 * CH[i])) ** 0.5 * jax.random.normal(
                ks[i], (3, 3, CH[i], CH[i + 1]))).astype(dtype),
            "b": jnp.zeros((CH[i + 1],), dtype)}
        params[f"bn{i}"] = {"scale": jnp.ones((CH[i + 1],), dtype),
                            "bias": jnp.zeros((CH[i + 1],), dtype)}
    params["head"] = {
        "w": ((2.0 / 392) ** 0.5
              * jax.random.normal(ks[2], (392, 10))).astype(dtype),
        "b": jnp.zeros((10,), dtype)}
    return params


def _batchnorm(p, x, eps=1e-5):
    mu = jnp.mean(x, axis=(0, 1, 2), keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=(0, 1, 2), keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def forward(params: dict, x: jax.Array) -> jax.Array:
    h = x
    for i in range(2):
        h = jax.lax.conv_general_dilated(
            h, params[f"c{i}"]["w"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=HI) + params[f"c{i}"]["b"]
        h = jax.nn.relu(_batchnorm(params[f"bn{i}"], h))
        h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                  (1, 2, 2, 1), "VALID")
    h = h.reshape(h.shape[0], -1)
    return jnp.dot(h, params["head"]["w"], precision=HI) + params["head"]["b"]


def forward_flops(batch: int = 1) -> int:
    """Multiply-add FLOPs of one forward pass: the taps of each 3x3 SAME
    convolution that fall inside the image, and the head."""
    flops, hw = 0, 28
    for cin, cout in zip(CH[:-1], CH[1:]):
        flops += 2 * cin * cout * (3 * hw - 2) ** 2
        hw //= 2
    return batch * (flops + 2 * 392 * 10)
