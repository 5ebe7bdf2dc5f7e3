"""The program's own host spans (``fl.*``, DESIGN.md "Spans"): every stage of
a round is named, nested under ``fl.round``, and carries integer stats.

Only names, nesting and stats are asserted, never timings: the rounds run on
the CPU next to other tests."""
import json
import os
import subprocess
import sys

import jax
import pytest
from jax._src.profiler import ProfileData

from repro.core.types import SecureAggConfig, THGSConfig
from repro.sim import AsyncSimulation, SimConfig, Simulation

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ENGINE = {"fl.engine.sample", "fl.engine.batches", "fl.engine.record",
          "fl.engine.hooks"}
ROUND_STAGES = {"fl.local_sgd", "fl.host_read", "fl.schedule", "fl.restack",
                "fl.encode", "fl.decode", "fl.residuals", "fl.server_update"}
SECAGG = {"fl.secagg.setup", "fl.secagg.pair_seeds", "fl.secagg.recover"}

_SYNC = SimConfig(
    name="spans", partition="noniid", noniid_k=4, n_clients=6,
    clients_per_round=4, rounds=2, n_train=300, n_test=120,
    local_steps=2, local_batch=8, eval_every=1,
    thgs=THGSConfig(s0=0.1, alpha=0.9, s_min=0.02),
    sa=SecureAggConfig(mask_ratio=0.02), seed=5)

_ASYNC = SimConfig(
    name="spans_async", partition="noniid", noniid_k=4, n_clients=6,
    clients_per_round=3, rounds=1, n_train=300, n_test=120,
    local_steps=2, local_batch=8, eval_every=1,
    thgs=THGSConfig(s0=0.2, alpha=0.9, s_min=0.05, time_varying=False),
    sa=SecureAggConfig(enabled=False), mode="async", buffer_size=3,
    max_staleness=2, seed=9)


def program_spans(trace_dir) -> list:
    """``[(name, start_ns, end_ns, stats)]`` of every ``fl.*`` host event
    in the trace under ``trace_dir``, by start (outermost first)."""
    path = max((os.path.join(d, f) for d, _, fs in os.walk(trace_dir)
                for f in fs if f.endswith(".xplane.pb")),
               key=os.path.getmtime)
    out = [(e.name, e.start_ns, e.end_ns, dict(e.stats))
           for p in ProfileData.from_file(path).planes
           for ln in p.lines for e in ln.events if e.name.startswith("fl.")]
    return sorted(out, key=lambda s: (s[1], -s[2]))


def by_round(spans) -> list:
    """``[(round span, [spans inside it])]``, one entry per ``fl.round``."""
    rounds = [s for s in spans if s[0] == "fl.round"]
    return [(r, [s for s in spans if s is not r
                 and r[1] <= s[1] and s[2] <= r[2]]) for r in rounds]


def traced(sim, tmp) -> list:
    with jax.profiler.trace(str(tmp)):
        sim.run(resume=False)
    return program_spans(tmp)


@pytest.fixture(scope="module")
def sync_sim(tmp_path_factory):
    sim = Simulation(_SYNC)
    # one dropped client a round, after mask agreement: recovery runs
    sim.sampler.dropouts_for = (
        lambda r, cohort, min_survivors=1: [int(cohort[0])])
    return sim, traced(sim, tmp_path_factory.mktemp("sync_trace"))


@pytest.fixture(scope="module")
def sync_run(sync_sim):
    sim, spans = sync_sim
    return spans, len(jax.tree_util.tree_leaves(sim.state.params))


def test_every_span_lies_in_a_round(sync_run):
    spans, _ = sync_run
    rounds = by_round(spans)
    assert len(rounds) == _SYNC.rounds
    assert sum(len(inside) for _, inside in rounds) == len(spans) - 2


def test_round_has_every_stage(sync_run):
    spans, _ = sync_run
    for _, inside in by_round(spans):
        assert {s[0] for s in inside} == ENGINE | ROUND_STAGES | SECAGG


def test_stage_spans_do_not_overlap(sync_run):
    """Each host line of a round lies in at most one stage span: below
    ``fl.round`` the stage spans are disjoint."""
    spans, _ = sync_run
    for _, inside in by_round(spans):
        for a, b in zip(inside, inside[1:]):
            assert a[2] <= b[1], (a[0], b[0])


def test_round_stats(sync_run):
    spans, _ = sync_run
    rounds = [r for r, _ in by_round(spans)]
    assert [r[3] for r in rounds] == [{"round": t, "dropped": 1}
                                      for t in range(_SYNC.rounds)]


def test_per_leaf_spans_and_counts(sync_run):
    spans, n_leaves = sync_run
    C = _SYNC.clients_per_round
    t = _SYNC.sa.t_for(C)
    for _, inside in by_round(spans):
        for name in ("fl.encode", "fl.decode"):
            leaves = [s[3]["leaf"] for s in inside if s[0] == name]
            assert leaves == list(range(n_leaves))
        stats = {s[0]: s[3] for s in inside if s[3] and "leaf" not in s[3]}
        assert stats == {"fl.host_read": {"values": C},
                         "fl.secagg.setup": {"shares": C * (C - 1)},
                         "fl.secagg.recover": {"shares": t * 1}}


def test_decode_spans_count_scatter_steps(sync_sim):
    """Each ``fl.decode`` span carries its leaf's decode-kernel grid: the
    stream's chunks plus the dense buffer's tiles, the stream being every
    client's ``k + C*k_mask`` slots and, with a drop, ``C*C*k_mask``
    recovery slots (read back from the round's ledger facts)."""
    from repro.kernels.stream_decode import grid_steps

    sim, spans = sync_sim
    for (_, inside), rec in zip(by_round(spans), sim.state.comm_log):
        C, dropped = rec.n_clients, rec.n_clients != rec.n_survivors
        want = [grid_steps(C * (k + C * km) + (C * C * km if dropped else 0),
                           size)
                for k, km, size in zip(rec.ks, rec.k_masks, rec.leaf_sizes)]
        assert [s[3]["scatter_steps"] for s in inside
                if s[0] == "fl.decode"] == want


def test_stats_are_ints(sync_run):
    spans, _ = sync_run
    values = [v for s in spans for v in s[3].values()]
    assert values and all(type(v) is int for v in values)


def test_async_round_shares_the_names(tmp_path):
    spans = traced(AsyncSimulation(_ASYNC), tmp_path)
    ((r, inside),) = by_round(spans)
    assert r[3] == {"round": 0, "dropped": 0}
    assert {s[0] for s in inside} == ENGINE | ROUND_STAGES
    n_leaves = sum(1 for s in inside if s[0] == "fl.encode")
    assert [s[3]["leaf"] for s in inside if s[0] == "fl.decode"] == list(
        range(n_leaves))
    assert {s[0]: s[3] for s in inside if s[0] == "fl.host_read"} == {
        "fl.host_read": {"values": _ASYNC.buffer_size}}


SHARDED = r"""
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path[:0] = ["src", "tests"]
import jax
from repro.sim import Simulation
from test_round_spans import by_round, program_spans, _SYNC
import dataclasses
cfg = dataclasses.replace(_SYNC, rounds=1, shard_clients="on")
sim = Simulation(cfg)
sim.sampler.dropouts_for = lambda r, cohort, min_survivors=1: [int(cohort[0])]
with jax.profiler.trace(sys.argv[1]):
    sim.run(resume=False)
((r, inside),) = by_round(program_spans(sys.argv[1]))
print(json.dumps({"names": sorted({s[0] for s in inside}),
                  "leaves": [s[3]["leaf"] for s in inside
                             if s[0] == "fl.encode_decode"],
                  "steps": [s[3]["scatter_steps"] for s in inside
                            if s[0] == "fl.encode_decode"],
                  "n_leaves": len(jax.tree_util.tree_leaves(
                      sim.state.params))}))
"""


def test_sharded_round_spans_encode_decode_per_leaf(tmp_path):
    """The client-parallel round runs one ``fl.encode_decode`` per leaf in
    place of ``fl.encode`` and ``fl.decode`` (two fake CPU devices, in a
    process of its own so this one keeps its single device)."""
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [os.path.join(ROOT, "src"), os.path.join(ROOT, "tests")]))
    out = subprocess.run([sys.executable, "-c", SHARDED, str(tmp_path)],
                         cwd=ROOT, env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    doc = json.loads(out.stdout.strip().splitlines()[-1])
    assert set(doc["names"]) == (ENGINE | ROUND_STAGES | SECAGG | {
        "fl.encode_decode"}) - {"fl.encode", "fl.decode"}
    assert doc["leaves"] == list(range(doc["n_leaves"]))
    assert len(doc["steps"]) == doc["n_leaves"] and min(doc["steps"]) >= 2
