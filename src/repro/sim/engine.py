"""The multi-round federated simulation engine.

``Simulation`` owns everything `benchmarks/common.run_fl` used to improvise:
data synthesis + partitioning, the per-round cohort schedule (sampler.py),
dropout injection, driving ``core.fedavg.run_round``, the communication
ledger (ledger.py), streaming eval/metrics hooks, and checkpoint/resume
through ``checkpoint.store``.

Compile-once contract (DESIGN.md §9)
------------------------------------
The round program is jitted per *shape signature*: cohort size, batch shapes
and the per-leaf ``k``s. The scheduler therefore keeps the cohort shape fixed
— every round samples exactly ``clients_per_round`` clients, and a dropped
client still occupies its slot in the stacked batch (its upload is discarded
server-side, which is exactly the Bonawitz semantics: local compute happened,
the upload never arrived). With the cohort shape pinned, the only remaining
re-trace source is the time-varying ``k`` schedule, which THGSConfig already
quantizes to ``k_levels`` geometric levels. The seed driver re-traced whenever
the cohort size wobbled; this engine makes the fixed shape a checked invariant.

The fixed cohort shape is also what makes device sharding free: with
``shard_clients`` (default 'auto') the engine builds a 1-D ``clients`` mesh
over the local devices and ``run_round`` partitions the cohort across it
(DESIGN.md §11) — bit-exact with the single-device path, so results never
depend on the device count.
"""
from __future__ import annotations

import dataclasses
import json
import os
import re
import time
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro import checkpoint
from repro.core import costs
from repro.core.fedavg import (FederatedState, init_state, run_async_update,
                               run_round)
from repro.data import (client_batches, dirichlet, iid, make_dataset,
                        noniid_label_k)
from repro.data.datasets import SPECS
from repro.models.paper_models import PAPER_MODELS, accuracy, cross_entropy_loss
from repro.sim.config import SimConfig
from repro.sim.ledger import CommLedger
from repro.sim.sampler import ClientSampler

# hook(round_t, info) with info keys:
#   state, cohort, dropped, loss, record, acc (only on eval rounds)
RoundHook = Callable[[int, dict], None]


def publish_params_hook(publish_dir: str, every: int = 1) -> RoundHook:
    """A :data:`RoundHook` that publishes the post-round global params for
    serving subscribers (repro.serving, DESIGN.md §16).

    Publishes the bare params pytree — not the training state — via
    ``checkpoint.publish`` (atomic npz + manifest, manifest written last so
    its presence marks the step complete) at step ``round + 1``, every
    ``every`` rounds. This is the control-plane seam between training and
    serving: the trainer never talks to the server, it only drops complete
    checkpoints; the server's ``CheckpointWatcher`` polls them up.
    """
    def hook(round_t: int, info: dict) -> None:
        if (round_t + 1) % max(1, every) == 0:
            checkpoint.publish(publish_dir, round_t + 1,
                               info["state"].params)

    return hook


@dataclasses.dataclass
class SimResult:
    """Outcome of one simulation: metric trajectories + the comm ledger."""

    name: str
    rounds: int
    eval_every: int
    accuracies: list          # test accuracy, one entry per eval point
    losses: list              # federation-mean local loss, one per round
    wall_s: float
    ledger: CommLedger
    config: dict

    @property
    def final_acc(self) -> float:
        """Mean of the last three eval points (the Table 2 convergence acc)."""
        return float(np.mean(self.accuracies[-3:])) if self.accuracies else 0.0

    def rounds_to_reach(self, target_acc: float) -> Optional[int]:
        """First round (1-indexed, eval-cadence resolution) whose test
        accuracy reached ``target_acc``; None if never reached."""
        for i, a in enumerate(self.accuracies):
            if a >= target_acc:
                return (i + 1) * max(1, self.eval_every)
        return None

    def upload_bits_to_reach(self, target_acc: float,
                             accounting: str = "paper") -> Optional[int]:
        """Cumulative upload bits until ``target_acc`` (Table 2's
        rounds-to-target costing); None if the target was never reached."""
        r = self.rounds_to_reach(target_acc)
        if r is None:
            return None
        return self.ledger.upload_bits_through(r, accounting)

    def summary(self) -> dict:
        return {
            "name": self.name,
            "rounds": self.rounds,
            "eval_every": self.eval_every,
            "final_acc": self.final_acc,
            "accuracies": [float(a) for a in self.accuracies],
            "losses": [float(x) for x in self.losses],
            "wall_s": self.wall_s,
            "config": self.config,
            "ledger": self.ledger.summary(),
        }

    def to_json(self, path: str) -> str:
        return self.ledger.to_json(path, extra={
            "name": self.name,
            "rounds": self.rounds,
            "eval_every": self.eval_every,
            "final_acc": self.final_acc,
            "accuracies": [float(a) for a in self.accuracies],
            "losses": [float(x) for x in self.losses],
            "wall_s": self.wall_s,
            "config": self.config,
        })


class Simulation:
    """Config-driven multi-round federated simulation (see module docstring).

    Build once, ``run()`` to completion; ``run(resume=True)`` (the default)
    picks up from the latest checkpoint in ``cfg.ckpt_dir`` when one exists.
    """

    sim_mode = "sync"   # the cfg.mode this class implements (see simulate())

    def __init__(self, cfg: SimConfig):
        cfg.validate()
        if cfg.mode != self.sim_mode:
            raise ValueError(
                f"{type(self).__name__} runs mode={self.sim_mode!r} but the "
                f"config asks for mode={cfg.mode!r}; use simulate() (or "
                "AsyncSimulation directly) for async configs")
        self.cfg = cfg
        self.model = PAPER_MODELS[cfg.model]
        spec = SPECS[cfg.dataset]
        self.x, self.y = make_dataset(spec, cfg.n_train, seed=cfg.seed)
        self.xt, self.yt = make_dataset(spec, cfg.n_test, seed=cfg.seed + 1,
                                        train=False)
        if cfg.partition == "iid":
            self.parts = iid(self.y, cfg.n_clients, seed=cfg.seed)
        elif cfg.partition == "noniid":
            self.parts = noniid_label_k(self.y, cfg.n_clients, cfg.noniid_k,
                                        seed=cfg.seed)
        else:
            self.parts = dirichlet(self.y, cfg.n_clients,
                                   cfg.dirichlet_alpha, seed=cfg.seed)
        self.data_counts = {c: int(len(idx)) for c, idx in self.parts.items()}
        self.sampler = ClientSampler(
            cfg.n_clients, cfg.clients_per_round, mode=cfg.sampler,
            weights=self.data_counts if cfg.sampler == "weighted" else None,
            dropout_rate=cfg.dropout_rate, seed=cfg.seed)
        self.fed = cfg.fed()
        self.bits = (costs.PAPER_BITS if cfg.accounting == "paper"
                     else costs.TPU_BITS)
        self.loss_fn = cross_entropy_loss(self.model)
        self.client_weights = (self.data_counts if cfg.weight_by_data_count
                               else None)
        # injected dropout must stay within what the secure-aggregation
        # protocol can recover from: at least the Shamir threshold t of the
        # cohort has to survive (repro/secagg; below t the round would abort)
        self.min_survivors = (
            cfg.sa.t_for(cfg.clients_per_round)
            if cfg.thgs is not None and cfg.sa.enabled else 1)
        # client-parallel rounds: partition the (fixed-shape) cohort over a
        # 1-D clients mesh when the devices allow it (DESIGN.md §11)
        self.mesh = None
        if cfg.shard_clients != "off":
            from repro.launch.mesh import clients_mesh_for

            self.mesh = clients_mesh_for(cfg.clients_per_round)
            if cfg.shard_clients == "on" and self.mesh is None:
                raise RuntimeError(
                    "shard_clients='on' but no usable clients mesh: "
                    f"{len(jax.devices())} device(s) for a cohort of "
                    f"{cfg.clients_per_round} (need >1 devices evenly "
                    "dividing the cohort, e.g. XLA_FLAGS="
                    "--xla_force_host_platform_device_count=8 on CPU)")
        self.ledger = CommLedger()

    # ----------------------------------------------------------------- state
    def _fresh_state(self) -> FederatedState:
        params = self.model.init(jax.random.key(self.cfg.seed))
        return init_state(params, self.fed)

    def _batches_for(self, round_t: int, cohort: Sequence[int]) -> dict:
        """Fixed-shape [steps, batch, ...] stacks for every cohort member.

        Seeded by (seed, round, client): resume-safe and cohort-order
        independent.
        """
        cfg = self.cfg
        out = {}
        for c in cohort:
            xb, yb = client_batches(
                self.x, self.y, self.parts[int(c)], cfg.local_batch,
                cfg.local_steps,
                seed=cfg.seed * 7919 + round_t * 1000 + int(c))
            out[int(c)] = (jnp.asarray(xb), jnp.asarray(yb))
        return out

    # ------------------------------------------------------------ checkpoint
    def _sidecar_path(self, step: int) -> str:
        return os.path.join(self.cfg.ckpt_dir, f"sim_{step:08d}.json")

    # the four hooks AsyncSimulation extends to persist its parameter-version
    # ring alongside params/residuals
    def _ckpt_tree(self, state: FederatedState) -> dict:
        return {"params": state.params, "residuals": state.residuals}

    def _ckpt_like(self, state: FederatedState, meta: dict) -> dict:
        return {"params": state.params, "residuals": state.residuals}

    def _load_ckpt_tree(self, state: FederatedState, tree: dict) -> None:
        state.params = tree["params"]
        state.residuals = tree["residuals"]

    def _sidecar_extra(self) -> dict:
        return {}

    def _save_ckpt(self, round_done: int, state: FederatedState,
                   accs: list, losses: list) -> None:
        checkpoint.save(self.cfg.ckpt_dir, round_done, self._ckpt_tree(state))
        sidecar = {
            "round": round_done,
            "client_losses": {str(c): float(v)
                              for c, v in state.losses.items()},
            "accuracies": [float(a) for a in accs],
            "losses": [float(x) for x in losses],
            "ledger_entries": self.ledger.summary()["entries"],
        }
        sidecar.update(self._sidecar_extra())
        # tmp + rename so a crash mid-write never leaves a truncated sidecar
        # shadowing the last good (npz, sidecar) pair
        path = self._sidecar_path(round_done)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(sidecar, f)
        os.replace(tmp, path)

    def _try_resume(self, state: FederatedState,
                    accs: list, losses: list) -> int:
        """Restore the latest checkpoint; returns the round to start from."""
        cfg = self.cfg
        if not cfg.ckpt_dir or not os.path.isdir(cfg.ckpt_dir):
            return 0
        # newest (npz, sidecar)-consistent pair: a crash between the npz
        # write and the sidecar write must not orphan the earlier good ones,
        # and a sidecar that exists but doesn't parse (truncated by a crash
        # predating the atomic write, or by disk corruption) counts as
        # missing — fall back to the next older pair instead of dying
        steps = sorted(
            (int(m.group(1)) for f in os.listdir(cfg.ckpt_dir)
             if (m := re.match(r"step_(\d+)\.npz$", f))), reverse=True)
        step, meta = None, None
        for s in steps:
            if not os.path.exists(self._sidecar_path(s)):
                continue
            try:
                with open(self._sidecar_path(s)) as f:
                    meta = json.load(f)
            except (ValueError, OSError) as e:
                import warnings

                warnings.warn(
                    f"unreadable checkpoint sidecar {self._sidecar_path(s)} "
                    f"({e}); falling back to an older checkpoint",
                    RuntimeWarning, stacklevel=2)
                continue
            step = s
            break
        if step is None:
            return 0
        if step > cfg.rounds:
            raise ValueError(
                f"checkpoint at round {step} > rounds={cfg.rounds}; "
                "refusing to resume past the configured horizon")
        tree = checkpoint.restore(
            cfg.ckpt_dir, step, like=self._ckpt_like(state, meta))
        self._load_ckpt_tree(state, tree)
        state.losses = {int(c): float(v)
                        for c, v in meta["client_losses"].items()}
        state.round = step
        accs[:] = meta["accuracies"]
        losses[:] = meta["losses"]
        self.ledger.entries = CommLedger.from_entry_dicts(
            meta["ledger_entries"]).entries
        return step

    # ------------------------------------------------------------------- run
    def _record_round(self, r: int, state: FederatedState, batches: dict,
                      accs: list, losses: list, **info) -> dict:
        """Ledger entry, mean loss, eval and checkpoint after round ``r``;
        returns the hooks' ``info``."""
        cfg = self.cfg
        rec = state.comm_log[-1]
        self.ledger.record(rec)
        loss = float(np.mean([state.losses[c] for c in batches]))
        losses.append(loss)
        info.update(state=state, loss=loss, record=rec)
        if (r + 1) % max(1, cfg.eval_every) == 0:
            acc = accuracy(self.model, state.params, self.xt, self.yt)
            accs.append(acc)
            info["acc"] = acc
        if cfg.ckpt_dir and cfg.ckpt_every and (r + 1) % cfg.ckpt_every == 0:
            self._save_ckpt(r + 1, state, accs, losses)
        return info

    def run(self, *, resume: bool = True,
            hooks: Sequence[RoundHook] = ()) -> SimResult:
        cfg = self.cfg
        # fresh ledger per run: calling run() twice must not double-count
        # (and must not mutate a previously returned SimResult's ledger)
        self.ledger = CommLedger()
        state = self._fresh_state()
        accs: list = []
        losses: list = []
        start = self._try_resume(state, accs, losses) if resume else 0
        t0 = time.perf_counter()
        for r in range(start, cfg.rounds):
            with TraceAnnotation("fl.round", round=r) as round_span:
                with TraceAnnotation("fl.engine.sample"):
                    cohort = self.sampler.cohort_for(r)
                    # the compile-once contract: the stacked shapes never
                    # change
                    assert len(cohort) == cfg.clients_per_round, (
                        "fixed-cohort contract violated: "
                        f"{len(cohort)} != {cfg.clients_per_round}")
                    dropped = self.sampler.dropouts_for(
                        r, cohort, min_survivors=self.min_survivors)
                round_span.set_metadata(dropped=len(dropped))
                with TraceAnnotation("fl.engine.batches"):
                    batches = self._batches_for(r, cohort)
                state = run_round(
                    state, batches, self.loss_fn, self.fed,
                    cfg.thgs, cfg.sa, bits=self.bits,
                    client_weights=self.client_weights, dropped=dropped,
                    mesh=self.mesh, codec=cfg.codec,
                    topology=cfg.topology, tree_groups=cfg.tree_groups,
                    dp=cfg.dp)
                with TraceAnnotation("fl.engine.record"):
                    info = self._record_round(r, state, batches, accs,
                                              losses, cohort=cohort,
                                              dropped=dropped)
                with TraceAnnotation("fl.engine.hooks"):
                    for hook in hooks:
                        hook(r, info)
        self.state = state
        return SimResult(
            name=cfg.name,
            rounds=cfg.rounds,
            eval_every=cfg.eval_every,
            accuracies=accs,
            losses=losses,
            wall_s=time.perf_counter() - t0,
            ledger=self.ledger,
            config=cfg.to_dict(),
        )


class AsyncSimulation(Simulation):
    """FedBuff-style async simulation (DESIGN.md §13).

    Each server step ``t`` drains a buffer of ``B = cfg.buffer_size or
    cfg.clients_per_round`` *distinct* client reports. Report ``c`` trained
    from the parameter version ``tau_c`` server steps old, where the
    simulated staleness ``tau_c`` is drawn counter-based from
    ``(seed, 0xA5, t)`` — like the cohort sampler's draws, a pure function of
    the round index, which is what makes checkpoint/resume replay
    bit-identically (tests/test_async_sim.py). The server keeps a ring of
    the last ``max_staleness + 1`` parameter versions and applies the
    ``(1 + tau)^-0.5``-weighted aggregate through
    ``core.fedavg.run_async_update``; each update's taus land on the ledger
    entry as the ``staleness`` fact.
    """

    sim_mode = "async"
    _STALENESS_TAG = 0xA5

    def __init__(self, cfg: SimConfig):
        super().__init__(cfg)
        self.buffer = cfg.buffer_size or cfg.clients_per_round
        # B distinct reports per buffer: duplicate clients would clobber the
        # error-feedback residual write-back, so the buffer is sampled like a
        # cohort (without replacement); dropout is rejected by validate()
        self.sampler = ClientSampler(
            cfg.n_clients, self.buffer, mode=cfg.sampler,
            weights=self.data_counts if cfg.sampler == "weighted" else None,
            dropout_rate=0.0, seed=cfg.seed)
        self.mesh = None          # async runs the serial update path
        self.versions: list = []  # parameter ring, newest last

    def _staleness_for(self, round_t: int) -> list[int]:
        """Counter-based per-report staleness draws for server step
        ``round_t``: uniform over [0, min(t, ring, max_staleness)] — early
        steps cannot be staler than the number of versions that exist."""
        hi = min(round_t, len(self.versions) - 1, self.cfg.max_staleness)
        rng = np.random.default_rng(
            [self.cfg.seed, self._STALENESS_TAG, round_t])
        return [int(t) for t in rng.integers(0, hi + 1, size=self.buffer)]

    # ------------------------------------------------- checkpoint ring hooks
    def _ckpt_tree(self, state: FederatedState) -> dict:
        d = super()._ckpt_tree(state)
        d["ring"] = {str(i): v for i, v in enumerate(self.versions)}
        return d

    def _ckpt_like(self, state: FederatedState, meta: dict) -> dict:
        like = super()._ckpt_like(state, meta)
        like["ring"] = {str(i): state.params
                        for i in range(int(meta["ring_len"]))}
        return like

    def _load_ckpt_tree(self, state: FederatedState, tree: dict) -> None:
        super()._load_ckpt_tree(state, tree)
        ring = tree["ring"]
        self.versions = [ring[str(i)] for i in range(len(ring))]

    def _sidecar_extra(self) -> dict:
        return {"ring_len": len(self.versions)}

    # ------------------------------------------------------------------- run
    def run(self, *, resume: bool = True,
            hooks: Sequence[RoundHook] = ()) -> SimResult:
        cfg = self.cfg
        self.ledger = CommLedger()
        state = self._fresh_state()
        self.versions = [state.params]
        accs: list = []
        losses: list = []
        start = self._try_resume(state, accs, losses) if resume else 0
        t0 = time.perf_counter()
        for r in range(start, cfg.rounds):
            with TraceAnnotation("fl.round", round=r, dropped=0):
                with TraceAnnotation("fl.engine.sample"):
                    cohort = self.sampler.cohort_for(r)
                    assert len(cohort) == self.buffer, (
                        "fixed-buffer contract violated: "
                        f"{len(cohort)} != {self.buffer}")
                    taus = self._staleness_for(r)
                    client_params = {int(c): self.versions[-1 - tau]
                                     for c, tau in zip(cohort, taus)}
                with TraceAnnotation("fl.engine.batches"):
                    batches = self._batches_for(r, cohort)
                state = run_async_update(
                    state, batches, client_params, self.loss_fn, self.fed,
                    cfg.thgs, bits=self.bits,
                    staleness={int(c): tau for c, tau in zip(cohort, taus)},
                    client_weights=self.client_weights, codec=cfg.codec,
                    topology=cfg.topology, tree_groups=cfg.tree_groups)
                with TraceAnnotation("fl.engine.record"):
                    self.versions.append(state.params)
                    if len(self.versions) > cfg.max_staleness + 1:
                        self.versions = self.versions[
                            -(cfg.max_staleness + 1):]
                    info = self._record_round(r, state, batches, accs,
                                              losses, cohort=cohort,
                                              dropped=(), staleness=taus)
                with TraceAnnotation("fl.engine.hooks"):
                    for hook in hooks:
                        hook(r, info)
        self.state = state
        return SimResult(
            name=cfg.name,
            rounds=cfg.rounds,
            eval_every=cfg.eval_every,
            accuracies=accs,
            losses=losses,
            wall_s=time.perf_counter() - t0,
            ledger=self.ledger,
            config=cfg.to_dict(),
        )


def simulate(cfg: SimConfig, **run_kw) -> SimResult:
    """One-call convenience: build the right Simulation for ``cfg.mode``
    ('sync' -> Simulation, 'async' -> AsyncSimulation) and run it."""
    cls = AsyncSimulation if cfg.mode == "async" else Simulation
    return cls(cfg).run(**run_kw)
