"""Multi-device sharding behaviour, run in a subprocess with 8 fake CPU devices
(the main test process must keep seeing exactly 1 device).

The client-parallel round (full-manual shard_map over a 1-D clients mesh,
DESIGN.md §11) is covered by tests/test_client_sharded_round.py; this file
drives the partial-manual FL mesh step of launch/train.py.
"""
import json
import os
import subprocess
import sys

import pytest

SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
# force the CPU platform: xla_force_host_platform_device_count only applies to
# it, and probing for a TPU backend first hangs for minutes in this container
os.environ["JAX_PLATFORMS"] = "cpu"
import sys, json
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import configs
from repro.models import transformer as tf
from repro.models.sharding import logical_axis_rules
from repro.launch import shardings as shd
from repro.launch.mesh import logical_rules
from repro.launch.train import make_fl_train_step, make_dense_train_step
from repro.core.types import THGSConfig, SecureAggConfig

assert len(jax.devices()) == 8
mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = configs.reduced(configs.get("yi_6b"))
key = jax.random.key(0)
params = tf.init_params(cfg, key)
rules = logical_rules(mesh, fed_axis="pod")
pshapes = jax.tree_util.tree_map(
    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
pshard = shd.named(shd.param_specs(pshapes, rules, mesh), mesh)
params = jax.device_put(params, pshard)
B, T = 8, 32
batch = {"tokens": jax.random.randint(key, (B, T), 0, cfg.vocab),
         "labels": jax.random.randint(key, (B, T), 0, cfg.vocab)}
batch = jax.device_put(batch, NamedSharding(mesh, P(("pod", "data"), None)))
thgs = THGSConfig(s0=0.1, alpha=0.9, s_min=0.01)
sa = SecureAggConfig(mask_ratio=0.05)
step = make_fl_train_step(cfg, mesh, "pod", thgs, sa, lr=0.05)
res = jax.tree_util.tree_map(
    lambda x: jnp.zeros((2,) + x.shape, jnp.bfloat16), params)
res = jax.device_put(res, NamedSharding(mesh, P("pod")))
with logical_axis_rules(mesh, rules):
    losses = []
    p, r = params, res
    for i in range(3):
        p, r, loss = jax.jit(step)(p, r, batch, jax.random.key(i))
        losses.append(float(loss))
    dstep = jax.jit(make_dense_train_step(cfg, lr=0.05))
    pd, dloss = dstep(params, batch)
finite = all(bool(jnp.all(jnp.isfinite(x.astype(jnp.float32))))
             for x in jax.tree_util.tree_leaves(p))
print(json.dumps({"losses": losses, "dense_loss": float(dloss),
                  "finite": finite}))
"""


@pytest.mark.slow
def test_fl_step_on_multipod_mesh():
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    out = subprocess.run([sys.executable, "-c", SNIPPET], capture_output=True,
                         text=True, cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))), env=env, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["finite"]
    assert res["losses"][-1] < res["losses"][0], res  # FL training makes progress
