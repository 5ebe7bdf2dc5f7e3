"""Chip smoke test: the federated round at VGG16 width on a TPU.

    python chip_smoke.py               # one chip: phases A, B and C
    python chip_smoke.py --four-chips  # client-sharded vs serial round only

Phase A trains the paper's Table 2 protocol (Non-IID-4, 10 clients, 5 per
round, 5 local steps x batch 50, THGS s0=0.05 -> 0.01, sparse-mask secure
aggregation at mask ratio 0.01) on ``cifar_vgg16`` (14,728,266 params) with
synthetic CIFAR-10 from the seed, for 3 rounds at 20% dropout so Bonawitz
recovery runs, through ``repro.sim.Simulation``. Phase B serves requests
from the trained params through ``serving.InferenceServer``. Phase C checks
the Pallas kernels on the chip at VGG16's largest leaf (2,359,296 params)
for a 5-client cohort: the pair masks alone cancel to exactly 0 through the
decode kernel, the decode equals XLA's scatter-add exactly on grid-valued
streams, and the mask and pack kernels equal their jnp twins bit for bit.

``--four-chips`` runs the same VGG16 config at cohort 8 of 16 clients with
the cohort sharded over every local chip (``shard_clients='on'``) against
the single-device round (``'off'``) in this process, and compares their
ledgers and params.

The script runs in one process, starts no other, and refuses to run
anywhere but a TPU. Any failed check raises, so the exit code is non-zero
and no result line is printed. The last line of stdout is one JSON object
naming the device.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

LEAF = 2_359_296        # VGG16's largest leaf (a 3x3x512x512 conv kernel)
COHORT = 5
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


def check(ok, what: str) -> None:
    if not bool(ok):
        raise RuntimeError(f"check failed: {what}")
    print(f"  ok: {what}", flush=True)


def require_tpu():
    """The device list, or RuntimeError when JAX's first device is no TPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise RuntimeError(
            f"chip_smoke needs a TPU; JAX found {devices[0].platform!r}")
    return devices


def vgg16_config(**over):
    """The Table 2 protocol on VGG16 / synthetic CIFAR-10, 3 rounds."""
    from repro.sim import presets

    base = dict(name="chip_smoke_vgg16", model="cifar_vgg16",
                dataset="cifar10", rounds=3, dropout_rate=0.2, eval_every=3,
                out_json=None)
    base.update(over)
    return presets.get("table2").replace(**base)


# ----------------------------------------------------------------- phase A
def phase_train(cfg):
    """Run ``cfg`` through the sim engine, timing every round on the
    device. Returns (simulation, result)."""
    import jax
    import numpy as np

    from repro.sim import Simulation

    compile_s = [0.0]

    def on_event(event, secs, **_):
        if event in _COMPILE_EVENTS:
            compile_s[0] += secs

    jax.monitoring.register_event_duration_secs_listener(on_event)
    sim = Simulation(cfg)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(
        jax.eval_shape(sim.model.init, jax.random.key(0))))
    print(f"phase A: {cfg.model} ({n_params:,} params) {cfg.dataset} "
          f"cohort {cfg.clients_per_round}/{cfg.n_clients} rounds "
          f"{cfg.rounds} dropout {cfg.dropout_rate} "
          f"shard_clients={cfg.shard_clients} "
          f"mesh={sim.mesh.devices.size if sim.mesh is not None else 1}dev",
          flush=True)
    rounds = []

    def timed(r, info):
        jax.block_until_ready(info["state"].params)
        now = time.perf_counter()
        rounds.append(dict(round=r + 1, wall_s=now - mark[0],
                           compile_s=compile_s[0] - mark[1],
                           loss=info["loss"], dropped=list(info["dropped"])))
        mark[0], mark[1] = now, compile_s[0]
        print(f"  round {r + 1}: wall {rounds[-1]['wall_s']:.3f} s "
              f"(compile {rounds[-1]['compile_s']:.3f} s)  "
              f"loss {info['loss']:.4f}  dropped {rounds[-1]['dropped']}",
              flush=True)

    mark = [time.perf_counter(), compile_s[0]]
    res = sim.run(resume=False, hooks=[timed])
    jax.monitoring.unregister_event_duration_listener(on_event)
    totals = res.ledger.totals("paper")
    print(f"  first-round compile {rounds[0]['compile_s']:.3f} s, "
          f"upload_vs_dense {totals['upload_vs_dense']:.6f}, "
          f"accuracy {res.accuracies}", flush=True)
    check(len(rounds) == cfg.rounds == len(res.ledger.entries),
          f"{cfg.rounds} rounds ran and were recorded")
    check(all(np.isfinite(r["loss"]) for r in rounds), "losses finite")
    check(all(np.isfinite(x).all()
              for x in jax.tree_util.tree_leaves(sim.state.params)),
          "params finite")
    check(0.0 < totals["upload_vs_dense"] < 1.0,
          "sparse upload below the dense FedAvg upload")
    if cfg.dropout_rate > 0:
        check(any(r["dropped"] for r in rounds),
              "a round dropped clients, so Bonawitz recovery ran")
    return sim, res


# ----------------------------------------------------------------- phase B
def phase_serve(model, params, payloads, max_batch: int = 8):
    """Serve two full batches; every response must equal a direct apply of
    the same batch (the model's BatchNorm uses batch statistics)."""
    import jax
    import numpy as np

    from repro import serving

    n = 2 * max_batch
    adapter = serving.ClassifierAdapter(model, max_batch)
    server = serving.InferenceServer(adapter, params=params)
    tickets = [server.submit(x) for x in payloads[:n]]
    server.drain()
    out = np.stack([t.wait(timeout=600.0) for t in tickets])
    doc = server.metrics.summary()
    print(f"phase B: served {doc['requests']['served']} requests in "
          f"{doc['batches']['count']} batches, "
          f"{doc['requests']['errors']} errors", flush=True)
    check(doc["requests"]["served"] == n and doc["requests"]["errors"] == 0,
          f"{n} requests served, 0 errors")
    check(out.shape == (n, model.n_classes) and np.isfinite(out).all(),
          "logits finite, one row per request")
    apply = jax.jit(model.apply)
    ref = np.concatenate([np.asarray(apply(params, payloads[i:i + max_batch]))
                          for i in range(0, n, max_batch)])
    check(np.array_equal(out, ref), "served logits == direct apply")


# ----------------------------------------------------------------- phase C
def phase_kernels(size: int = LEAF, cohort: int = COHORT, seed: int = 0):
    """The main-path Pallas kernels on the device, at one leaf's width."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import codecs, streams
    from repro.core.types import SecureAggConfig
    from repro.kernels import ops, ref

    sa = SecureAggConfig(mask_ratio=0.01)
    k_mask = sa.k_mask_for(size, cohort)
    k = -(-size * 5 // 100)            # THGS s0 = 0.05
    print(f"phase C: leaf {size:,}, cohort {cohort}, k {k:,}, "
          f"k_mask {k_mask:,}", flush=True)
    seeds, signs = streams.pair_seed_matrix(sa, list(range(cohort)), 0)
    seeds, signs = jnp.asarray(seeds), jnp.asarray(signs)

    # the pair-mask kernel against its jnp twin
    iu, ju = np.triu_indices(cohort)
    folded = ref.fold_leaf_seed(seeds, 7)[iu, ju]
    ones = jnp.ones(folded.shape, jnp.float32)
    i_k, v_k = ops.pair_mask_streams(folded, ones, nb=1, k_mask=k_mask,
                                     m=size)
    i_r, v_r = jax.jit(ref.pair_mask_stream_ref, static_argnums=(2, 3, 4),
                       static_argnames=("p", "q"))(
        folded, ones, 1, k_mask, size, p=-1.0, q=2.0)
    check(np.array_equal(i_k, i_r) and np.array_equal(v_k, v_r),
          f"pair_mask_streams == jnp twin for {len(iu)} pairs")

    # masks alone, every client alive: they cancel exactly in the decode
    m_idx, m_vals = streams.mask_streams_all_pairs(
        seeds, signs, 1, k_mask, size, p=sa.p, q=sa.q, leaf_id=7)
    masks = streams.StreamBatch(m_idx, m_vals)
    dense = streams.decode_sum_blocks(masks, 1, size, use_pallas=True)
    one = streams.decode_sum_blocks(
        streams.StreamBatch(m_idx[:1], m_vals[:1]), 1, size, use_pallas=True)
    check(int(jnp.count_nonzero(one)) > 0,
          "one client's masks alone decode to a nonzero buffer")
    check(int(jnp.count_nonzero(dense)) == 0,
          f"all {cohort} clients' masks cancel to exactly 0 in the Pallas "
          "decode")

    # grid-valued round-shaped stream: top-k-like slots + the real mask
    # support. Values are multiples of 2**-24 below 2**-13, so every partial
    # sum is exact in f32 whatever the order (and a bf16 pass is not)
    kk = jax.random.split(jax.random.key(seed))
    top = jax.random.randint(kk[0], (cohort, 1, k), 0, size, jnp.int32)
    idx = jnp.concatenate([top, m_idx], -1)
    vals = (jax.random.randint(kk[1], idx.shape, -2**11, 2**11)
            .astype(jnp.float32) * jnp.float32(2.0**-24))
    pallas = ops.stream_scatter_add(idx.reshape(-1), vals.reshape(-1),
                                    size=size)
    xla = jax.jit(lambda i, v: jnp.zeros((size,), jnp.float32)
                  .at[i].add(v))(idx.reshape(-1), vals.reshape(-1))
    check(np.array_equal(pallas, xla),
          f"Pallas decode == XLA scatter-add exactly ({idx.size:,} slots)")

    # the wire pack/unpack kernels against their jnp twins
    for width in (codecs.value_bits("int8"), codecs.index_width(size)):
        u = jax.random.bits(jax.random.fold_in(kk[0], width), (cohort, k),
                            jnp.uint32) >> (32 - width)
        words = ops.bitpack_rows(u, width=width)
        check(np.array_equal(words, jax.jit(
            ref.bitpack_rows_ref, static_argnums=1)(u, width)),
            f"bitpack_rows == jnp twin at width {width}")
        check(np.array_equal(ops.bitunpack_rows(words, k=k, width=width), u),
              f"bitunpack_rows round trip at width {width}")


# ------------------------------------------------------- --four-chips phase
def _leaf_parity(a_tree, b_tree, label: str) -> int:
    """Print per leaf whether two pytrees are bit-identical; returns the
    number of leaves that differ."""
    import jax
    import numpy as np

    flat_a = jax.tree_util.tree_flatten_with_path(a_tree)[0]
    flat_b = jax.tree_util.tree_leaves(b_tree)
    differ = 0
    for (path, a), b in zip(flat_a, flat_b):
        a, b = np.asarray(a), np.asarray(b)
        eq = np.array_equal(a, b)
        differ += not eq
        d = float(np.max(np.abs(a.astype(np.float64) - b)))
        print(f"  {label} {jax.tree_util.keystr(path):22s} {a.size:>9,} "
              f"{'bit-identical' if eq else f'DIFFERS max|d|={d:.3e}'}")
    print(f"{label}: {len(flat_b) - differ}/{len(flat_b)} leaves "
          "bit-identical", flush=True)
    return differ


def _sgd_parity(sim, mesh) -> int:
    """Round 1's local-SGD deltas, sharded over ``mesh`` vs vmapped on one
    device, from the same params and batches — the first stage of a round."""
    import jax
    import jax.numpy as jnp

    from repro.core import fedavg, streams

    cfg = sim.cfg
    params = sim._fresh_state().params
    batches = sim._batches_for(0, sim.sampler.cohort_for(0))
    stacked = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *[batches[c] for c in sorted(batches)])
    d_off, _ = fedavg.batched_client_update(
        params, stacked, sim.loss_fn, cfg.local_steps, cfg.local_lr)
    d_on, _ = fedavg.batched_client_update_sharded(
        mesh, params, streams.shard_client_tree(stacked, mesh), sim.loss_fn,
        cfg.local_steps, cfg.local_lr)
    return _leaf_parity(d_on, d_off, "round-1 SGD delta")


def phase_sharded_parity(n_devices: int):
    """Cohort 8 of 16 sharded over every chip vs the single-device round.

    Two rounds, not three: each arm compiles about a hundred per-leaf
    programs, and the two arms share none of them."""
    import jax

    check(n_devices > 1, f"{n_devices} local chips (need more than one)")
    cfg = vgg16_config(name="chip_smoke_vgg16_c8", n_clients=16,
                       clients_per_round=8, rounds=2, eval_every=2)
    runs = {}
    for mode in ("on", "off"):
        sim, res = phase_train(cfg.replace(shard_clients=mode))
        runs[mode] = (jax.device_get(sim.state.params), res, sim.mesh)
    (p_on, r_on, mesh), (p_off, r_off, _) = runs["on"], runs["off"]
    print(f"losses on  {r_on.losses}\nlosses off {r_off.losses}", flush=True)
    print(f"accuracy on {r_on.accuracies} off {r_off.accuracies}", flush=True)
    if _leaf_parity(p_on, p_off, "params"):
        # name the stage: do the round's first-stage outputs already differ?
        _sgd_parity(sim, mesh)
    check(r_on.ledger.summary() == r_off.ledger.summary(),
          "CommLedger identical, sharded vs serial")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the client-sharded vs serial comparison "
                         "across every local chip")
    args = ap.parse_args(argv)
    devices = require_tpu()
    from repro.compile_cache import enable_compile_cache

    print(f"device: {devices[0].device_kind} x{len(devices)}; compile cache "
          f"{enable_compile_cache()}", flush=True)
    if args.four_chips:
        phase_sharded_parity(len(devices))
    else:
        sim, _ = phase_train(vgg16_config())
        phase_serve(sim.model, sim.state.params, sim.xt)
        phase_kernels()
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
