"""Drive one cell through the program's normal path.

One ``repro.sim.Simulation`` is built per run from the cell's files and the
seed. ``Simulation.run`` drives every round: cohort sampling and dropout,
batches, the secure-aggregation control plane, and ``fedavg.run_round``
(local SGD, THGS encode with the pair-mask PRNG, the Pallas scatter-add
decode with Bonawitz recovery, the server update). A hook that
``Simulation.run`` calls after every round does the rest:

1. the first rounds are recorded for the comparison with the plain
   reference (their inputs: cohort, dropped clients, batches; their outputs:
   losses, the cohort's local-SGD deltas, params, residuals):
   ``compare_rounds`` of them, and in a mix with
   ``compare_through_first_drop`` on to the first round with a dropped
   client, so that recovery is compared. The deltas are taken from
   ``fedavg.batched_client_update``, wrapped while these rounds run and put
   back before any other round does. The wrap also reads each client's
   step-1 loss through the same compiled program (``probe_sgd``);
2. rounds go on until every dropped count the mix can draw has run, so
   that every program the window uses is compiled, and (in a mix with
   ``dropout_blocks``) to the end of a block; all of that is set-up;
3. after a block on the params, back-to-back rounds are timed for
   ``seconds``; the window closes at the first round end past it, after a
   block on the params. Eval, checkpoints and the ledger file are off.

The harness owns the compile cache and counts JAX's compile events apart
for set-up and window. The reference runs after the window, once the peak
memory has been read and the program's state is freed.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import glob
import inspect
import math
import os
import time
from pathlib import Path

import numpy as np

from chipbench import compare, reference, spec
from chipbench.peaks import peaks_for

# --seed is any whole number up to a little over 2**31; the program's data
# seeds derive 32-bit NumPy seeds as seed * 7919 + round * 1000 + client,
# so the program gets the seed reduced below 2**19 (a prime modulus). The
# secure-aggregation keys take the whole seed.
SEED_MOD = (1 << 19) - 1
TRACE_WINDOW_S = 8.0     # a traced run traces at most this long a window
WARM_CAP = 60            # rounds of set-up at most, waiting for drop counts
COUNTED = {
    "/jax/core/compile/jaxpr_trace_duration": "traces",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowerings",
    "/jax/core/compile/backend_compile_duration": "compiles",
    "/jax/compilation_cache/compile_requests_use_cache": "cache_requests",
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}


class WindowClosed(Exception):
    """Raised from the round hook to end ``Simulation.run``."""


def program_seed(seed: int) -> int:
    return int(seed) % SEED_MOD


def configure_jax(cell: spec.Cell, checkout: Path) -> str:
    """Set JAX up for the cell; returns the compile cache's directory.

    The matmul precision is the configuration's (``highest``: float32
    accuracy, which JAX on TPU gives only when asked). The persistent
    compilation cache is ``$JAX_COMPILATION_CACHE_DIR`` when set, else the
    fixed ``<checkout>/.jax_cache``, and every program is written to it,
    however short its compile."""
    import jax

    jax.config.update("jax_default_matmul_precision",
                      cell.config["matmul_precision"])
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        checkout / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileLog:
    """Counts JAX's tracing, lowering, compile and cache events."""

    def __init__(self):
        import jax

        self.counts = collections.Counter()
        self._jax = jax
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event in COUNTED:
            self.counts[COUNTED[event]] += 1

    def _duration(self, event, secs, **_):
        if event in COUNTED:
            self.counts[COUNTED[event]] += 1
            self.counts[COUNTED[event] + "_s"] += secs

    def snapshot(self) -> dict:
        return dict(self.counts)

    def close(self) -> None:
        self._jax.monitoring.unregister_event_listener(self._event)
        self._jax.monitoring.unregister_event_duration_listener(
            self._duration)


class GcLog:
    """Python garbage collections in the window: how many, how long."""

    def __init__(self):
        self.count, self.seconds, self._t = 0, 0.0, None

    def _callback(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.count += 1
            self.seconds += time.perf_counter() - self._t
            self._t = None

    def start(self) -> None:
        gc.callbacks.append(self._callback)

    def stop(self) -> None:
        if self._callback in gc.callbacks:
            gc.callbacks.remove(self._callback)


def sim_config(cell: spec.Cell, seed: int):
    """The ``SimConfig`` of the cell at ``seed``: the configuration's model
    and mechanisms, the traffic's federation protocol, eval and files off.
    The round count only bounds the loop, which the window ends."""
    from repro.core.types import SecureAggConfig, THGSConfig
    from repro.sim import SimConfig

    c, t, rounds = cell.config, cell.traffic, 10**6
    return SimConfig(
        name=cell.name, model=c["model"], dataset=c["dataset"],
        partition=t["partition"], noniid_k=t["noniid_k"],
        n_train=t["n_train"], n_test=t["n_test"], rounds=rounds,
        n_clients=t["n_clients"], clients_per_round=t["clients_per_round"],
        local_steps=t["local_steps"], local_batch=t["local_batch"],
        local_lr=t["local_lr"], server_lr=c["server_lr"],
        thgs=THGSConfig(time_varying=c["time_varying"], **c["thgs"]),
        sa=SecureAggConfig(seed=int(seed), **c["secagg"]),
        dropout_rate=t["dropout_rate"], eval_every=rounds + 1,
        seed=program_seed(seed), shard_clients="off", out_json=None)


def block_dropouts(traffic: dict, seed: int):
    """The traffic's dropout draw, or None where the mix leaves it to the
    program's sampler (no ``dropout_blocks``).

    ``dropout_blocks`` lists the dropped counts of one block of rounds:
    every block drops those counts in an order shuffled from the seed, and
    the dropped clients are drawn from the cohort from the seed. So every
    seed runs the same mix of rounds in another order, and any whole number
    of blocks drops ``mean(dropout_blocks) / cohort`` of the clients."""
    block = traffic.get("dropout_blocks")
    if not block:
        return None

    def dropouts_for(round_t, cohort, min_survivors=1):
        b, i = divmod(int(round_t), len(block))
        count = int(np.random.default_rng([seed, 0xD0, b]).permutation(
            block)[i])
        if count > len(cohort) - min_survivors:
            raise ValueError(f"{count} of {len(cohort)} clients cannot drop "
                             f"with {min_survivors} survivors needed")
        chosen = np.random.default_rng([seed, 0xD1, int(round_t)]).choice(
            np.asarray(cohort, int), size=count, replace=False)
        return sorted(int(c) for c in chosen)

    return dropouts_for


def flat_host(tree) -> dict:
    """leaf name -> host copy, in the tree's flatten order."""
    import jax

    leaves = jax.device_get(jax.tree_util.tree_leaves(tree))
    return dict(zip(reference.leaf_names(tree), (np.asarray(x)
                                                  for x in leaves)))


def probe_sgd(update, args: tuple, kwargs: dict) -> dict:
    """Each cohort client's step-1 loss (a forward pass at the round-start
    params), read through the program's own compiled local SGD: ``update``
    (``batched_client_update``) called again as in ``args``/``kwargs``, but
    with ``lr`` 0 and every step's batch the first one, so that each step
    runs at the round-start params and the mean loss is step 1's. ``lr`` is
    traced, the step count and shapes are the round's and each argument is
    passed as the round passed it (by position or by name), so the call is
    the round's own program; the batches are laid out on the host, so
    nothing compiles. Nothing of it feeds back into the round."""
    import jax
    import jax.numpy as jnp

    names = list(inspect.signature(update).parameters)
    call = dict(zip(names, args), **kwargs)
    call["batches_stacked"] = jax.tree_util.tree_map(
        lambda x: jnp.asarray(np.broadcast_to(np.asarray(x)[:, :1], x.shape)),
        call["batches_stacked"])
    call["lr"] = 0.0
    head = [call.pop(n) for n in names[:len(args)]]
    return {"step1": np.asarray(update(*head, **call)[1], np.float64)}


def _record_facts(rec) -> dict:
    return {"ks": rec.ks, "k_masks": rec.k_masks, "n_clients": rec.n_clients,
            "n_survivors": rec.n_survivors, "model_size": rec.model_size,
            "leaf_sizes": rec.leaf_sizes}


class _Spans:
    """``chipbench.*`` host annotations for the profiler, on only while a
    traced window runs."""

    def __init__(self):
        self.on = False
        self._round = None

    def wrap(self, name: str, fn):
        import jax

        def wrapped(*a, **kw):
            if not self.on:
                return fn(*a, **kw)
            with jax.profiler.TraceAnnotation(f"chipbench.{name}"):
                return fn(*a, **kw)
        return wrapped

    def next_round(self, last: bool = False) -> None:
        import jax

        if self._round is not None:
            self._round.__exit__(None, None, None)
            self._round = None
        if self.on and not last:
            self._round = jax.profiler.TraceAnnotation("chipbench.round")
            self._round.__enter__()


@dataclasses.dataclass
class Run:
    """What one run of a cell produced, for ``run.py`` to report."""

    window_s: float
    round_times: list
    window_dropped: list
    window_records: list
    warm_rounds: int
    setup_s: float
    compiles_setup: dict
    compiles_window: dict
    gc_window: tuple          # (collections, seconds) in the window
    memory_peak_bytes: int
    prog: reference.Outputs
    compare_rounds: list
    trace_path: str | None


class Driver:
    """The round hook of ``Simulation.run`` (see the module docstring)."""

    def __init__(self, sim, traffic: dict, seconds: float, log: CompileLog,
                 t_start: float, trace_dir: str | None = None):
        self.seconds, self.log = seconds, log
        self.t_start, self.trace_dir = t_start, trace_dir
        self.n_compare = int(traffic["compare_rounds"])
        self.through_drop = bool(traffic.get("compare_through_first_drop"))
        self.comparing = True
        C = traffic["clients_per_round"]
        block = traffic.get("dropout_blocks")
        self.need = (set(block) if block else
                     set(range(C - sim.min_survivors + 1))
                     if traffic["dropout_rate"] > 0 else {0})
        self.block = len(block) if block else 1
        self.seen, self.compare, self.losses = set(), [], []
        self.rounds = []
        self._deltas = self._probe = None
        self.phase = "warm"
        self.round_ends, self.dropped, self.records = [], [], []
        self.spans = _Spans()
        self.gc_log = GcLog()
        self._batches = None
        batches_for, fresh_state = sim._batches_for, sim._fresh_state

        def capture_batches(r, cohort):
            out = batches_for(r, cohort)
            if self.comparing:
                self._batches = {c: (np.asarray(x), np.asarray(y))
                                 for c, (x, y) in out.items()}
            return out

        def capture_fresh():
            state = fresh_state()
            self.params0 = flat_host(state.params)
            return state

        sim._batches_for = self.spans.wrap("batches", capture_batches)
        sim._fresh_state = capture_fresh

        from repro.core import fedavg

        update = fedavg.batched_client_update

        def capture_update(*a, **kw):
            # the probe first, so that its outputs are freed before the
            # round's own are made and the memory peak stays the round's
            self._probe = probe_sgd(update, a, kw)
            out = update(*a, **kw)
            self._deltas = flat_host(out[0])
            return out

        fedavg.batched_client_update = capture_update
        self.release = lambda: setattr(fedavg, "batched_client_update",
                                       update)

    def __call__(self, r: int, info: dict) -> None:
        import jax

        state = info["state"]
        if self.phase == "warm":
            if self.comparing:
                self._record_compared(r, info)
            self.seen.add(len(info["dropped"]))
            if not self.comparing and (
                    (self.need <= self.seen and (r + 1) % self.block == 0)
                    or r + 1 >= WARM_CAP):
                self.warm_rounds = r + 1
                jax.block_until_ready(state.params)
                # the objects that set-up left (traced programs, captures)
                # leave the collector's view, so that a full collection in
                # the window does not walk them; the program's own garbage
                # is still collected, and counted (``GcLog``)
                gc.collect()
                gc.freeze()
                self.gc_log.start()
                self.compiles_setup = self.log.snapshot()
                if self.trace_dir is not None:
                    self._start_trace()
                self.phase = "window"
                self.t0 = time.perf_counter()
                self.setup_s = self.t0 - self.t_start
                self.spans.next_round()
            return
        self.round_ends.append(time.perf_counter())
        self.dropped.append(len(info["dropped"]))
        self.records.append(_record_facts(info["record"]))
        if self.round_ends[-1] - self.t0 >= self.seconds:
            jax.block_until_ready(state.params)
            self.round_ends[-1] = time.perf_counter()
            self.gc_log.stop()
            gc.unfreeze()
            self.compiles_window = {
                k: v - self.compiles_setup.get(k, 0)
                for k, v in self.log.snapshot().items()}
            self.spans.next_round(last=True)
            if self.trace_dir is not None:
                self.spans.on = False
                jax.profiler.stop_trace()
            raise WindowClosed
        self.spans.next_round()

    def _record_compared(self, r: int, info: dict) -> None:
        """Keep round ``r``'s inputs and outputs for the comparison; the
        compared rounds are the first ``compare_rounds``, and with
        ``compare_through_first_drop`` run on to the first round in which a
        client dropped, so that the comparison covers recovery."""
        state = info["state"]
        self.compare.append({"cohort": [int(c) for c in info["cohort"]],
                             "dropped": [int(c) for c in info["dropped"]],
                             "batches": self._batches})
        self.losses.append(float(info["loss"]))
        cohort = sorted(int(c) for c in info["cohort"])
        res = [flat_host(state.residuals[c]) for c in cohort]
        self.rounds.append({
            "deltas": self._deltas, "params": flat_host(state.params),
            "residuals": {n: np.stack([rc[n] for rc in res])
                          for n in res[0]}, **self._probe})
        dropped_yet = any(rd["dropped"] for rd in self.compare)
        if r + 1 >= self.n_compare and (
                dropped_yet or not self.through_drop or r + 1 >= WARM_CAP):
            self.comparing = False
            self.release()
            per_client = [flat_host(state.residuals[c])
                          for c in sorted(state.residuals)]
            self.residuals = {n: np.stack([pc[n] for pc in per_client])
                              for n in per_client[0]}

    def _start_trace(self) -> None:
        import jax
        from repro.secagg.protocol import RoundProtocol

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        # the secure-aggregation control plane's host phases, annotated for
        # the idle-gap breakdown while the traced window runs
        for name in ("setup", "recover_seeds"):
            raw = RoundProtocol.__dict__[name]
            fn = raw.__func__ if isinstance(raw, classmethod) else raw
            wrapped = self.spans.wrap(f"secagg_{name}", fn)
            setattr(RoundProtocol, name, classmethod(wrapped)
                    if isinstance(raw, classmethod) else wrapped)
        self.spans.on = True
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)


def _memory_peak() -> int:
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return int(max(s.get("peak_bytes_in_use", 0) for s in stats))


def run_program(cell: spec.Cell, seed: int, seconds: float, t_start: float,
                trace_dir: str | None = None) -> Run:
    """Build the cell's Simulation, run set-up and the window, and return
    what the program produced; the program's state is freed on return."""
    from repro.secagg.protocol import RoundProtocol
    from repro.sim import Simulation

    log = CompileLog()
    saved = dict(RoundProtocol.__dict__)
    drv = None
    try:
        sim = Simulation(sim_config(cell, seed))
        dropouts_for = block_dropouts(cell.traffic, int(seed))
        if dropouts_for is not None:
            sim.sampler.dropouts_for = dropouts_for
        drv = Driver(sim, cell.traffic, seconds, log, t_start, trace_dir)
        try:
            sim.run(resume=False, hooks=[drv])
            raise RuntimeError("Simulation.run ended before the window did")
        except WindowClosed:
            pass
    finally:
        if drv is not None:
            drv.release()
        for name in ("setup", "recover_seeds"):
            setattr(RoundProtocol, name, saved[name])
        log.close()
        gc.unfreeze()
    times = np.diff([drv.t0] + drv.round_ends).tolist()
    run = Run(
        window_s=drv.round_ends[-1] - drv.t0, round_times=times,
        window_dropped=drv.dropped, window_records=drv.records,
        warm_rounds=drv.warm_rounds, setup_s=drv.setup_s,
        compiles_setup=drv.compiles_setup,
        compiles_window=drv.compiles_window,
        gc_window=(drv.gc_log.count, drv.gc_log.seconds),
        memory_peak_bytes=_memory_peak(),
        prog=reference.Outputs(losses=drv.losses, params0=drv.params0,
                               residuals=drv.residuals, rounds=drv.rounds),
        compare_rounds=drv.compare,
        trace_path=None)
    if trace_dir is not None:
        found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        run.trace_path = max(found, key=os.path.getmtime) if found else None
    del sim, drv
    gc.collect()
    return run


def check(cell: spec.Cell, seed: int, run: Run, dtype: str = "float32",
          fault: str | None = None) -> dict:
    """The comparison numbers of ``run`` against the plain reference (or
    of the control / a planted fault put in the program's place, for
    calibration): free-running and teacher-forced."""
    model = (cell.reference, cell.config, cell.traffic)
    ref = reference.run(*model, program_seed(seed), int(seed),
                        run.compare_rounds)
    other = run.prog
    if dtype != "float32" or fault is not None:
        other = reference.run(*model, program_seed(seed), int(seed),
                              run.compare_rounds, dtype=dtype, fault=fault)
    forced = reference.forced(*model, int(seed), run.compare_rounds, other)
    return compare.numbers(other, ref, forced)


def nearest_rank(values, q: float) -> float:
    s = sorted(values)
    return s[max(0, math.ceil(q * len(s)) - 1)]


def end_to_end(run: Run) -> dict:
    return {"round_s": run.window_s / len(run.round_times),
            "round_p90_s": nearest_rank(run.round_times, 0.9),
            "setup_s": run.setup_s}


def per_layer(cell: spec.Cell, run: Run, device_kind: str):
    """(metrics, busy_s, window_s, breakdown) from the run's trace."""
    from jax._src.profiler import ProfileData

    from chipbench.trace import View

    t = cell.traffic
    facts = {
        "rounds": run.window_records,
        "peaks": peaks_for(device_kind) if device_kind != "cpu" else None,
        "train_flops_per_round": 3 * cell.reference.forward_flops(1)
        * t["clients_per_round"] * t["local_steps"] * t["local_batch"],
    }
    view = View(ProfileData.from_file(run.trace_path), facts)
    out = {}
    for m in cell.per_layer:
        value = spec.metric_reader(m["name"]).read(view)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    breakdown = {"device_ops": view.top_ops(), "idle_gaps":
                 view.idle_by_host()}
    return out, view.busy_s, view.window_s, breakdown

