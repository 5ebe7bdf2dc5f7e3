"""Device idle time by program stage (``stages.py``) and its three layers.

On a synthetic trace, the arithmetic; on a trace recorded on a TPU v5e
(``data/trace_mlp_spans``: a ``--trace 1 --seconds 2`` run of
``mlp_t2_secagg_drop`` with the program's ``fl.*`` spans, two rounds, of
which the first is whole: a span still open when the trace stops is not
recorded), the readers' values and the idle time of each layer, the clocks and the coverage of the spans.
The host events of the PJRT task threads, which no reader reads, were
dropped from that trace to keep it small."""
import gzip
import json
from types import SimpleNamespace as NS

import pytest
from jax._src.profiler import ProfileData

from chipbench_testing import DATA

from chipbench import spec, stages
from chipbench.trace import View

FIXTURE = DATA / "trace_mlp_spans"
READERS = ("local_sgd_ms", "encode_ms", "decode_ms",
           "stream_scatter_add_roofline", "device_idle", "round_mfu",
           "upload_vs_dense")


def load(path) -> tuple:
    """``(view, program spans)`` of a recorded trace."""
    raw = gzip.decompress((path / "trace.xplane.pb.gz").read_bytes())
    facts = json.loads((path / "facts.json").read_text())
    profile = ProfileData.from_serialized_xspace(raw)
    return View(profile, facts), stages.program_spans(profile)


@pytest.fixture(scope="module")
def traced():
    return load(FIXTURE)


@pytest.fixture(scope="module")
def view(traced):
    return traced[0]


@pytest.fixture(scope="module")
def spans(traced):
    return traced[1]


@pytest.fixture(scope="module")
def recorded():
    return json.loads((FIXTURE / "metrics.json").read_text())


# ------------------------------------------------------------ synthetic
def _ev(name, start, end, **stats):
    return NS(name=name, start_ns=start, end_ns=end, stats=list(stats.items()))


def _profile(busy, spans):
    """One TPU whose operations run over ``busy``, and a host thread with
    the harness's round span over [0, 100] and the program's ``spans``."""
    ops = [_ev(f"%op.{i} = f32[] add()", s, e) for i, (s, e) in
           enumerate(busy)]
    tpu = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops)])
    host = NS(name="/host:CPU", lines=[NS(name="python3", events=[
        _ev("chipbench.round", 0, 100)] + [_ev(*s) for s in spans])])
    return NS(planes=[tpu, host])


def test_idle_goes_to_the_innermost_span():
    spans = [("fl.round", 2, 98), ("fl.engine.sample", 2, 6),
             ("fl.local_sgd", 8, 30), ("fl.host_read", 30, 50),
             ("fl.secagg.setup", 52, 55), ("fl.encode", 60, 70),
             ("fl.engine.hooks", 90, 98)]
    profile = _profile([(10, 20), (40, 45), (65, 80)], spans)
    view, spans = View(profile, {}), stages.program_spans(profile)
    assert view.window_s == pytest.approx(100e-9)
    got = {k: v * 1e9 for k, v in stages.idle_by_stage(view, spans).items()}
    assert got == pytest.approx({
        None: 2 + 2,                    # before fl.round, after it
        "fl.round": 2 + 2 + 5 + 10,     # its self time
        "fl.engine.sample": 4,          # starts with fl.round, inside it
        "fl.local_sgd": 2 + 10,
        "fl.host_read": 10 + 5,
        "fl.secagg.setup": 3,
        "fl.encode": 5,
        "fl.engine.hooks": 8})
    assert sum(got.values()) == pytest.approx(100 - 10 - 5 - 15)
    assert stages.idle_ms(view, spans) == pytest.approx({
        "idle_engine_ms": 4e-6, "idle_secagg_ms": 3e-6,
        "idle_run_round_ms": (12 + 15 + 5) * 1e-6})


def test_program_spans_keep_their_stats():
    profile = NS(planes=[
        NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=[
            _ev("fl.op", 0, 1)])]),
        NS(name="/host:CPU", lines=[NS(name="python3", events=[
            _ev("fl.encode", 1, 2, leaf=3),
            _ev("fl.round", 0, 9, round=4, dropped=1),
            _ev("chipbench.round", 0, 9)])])])
    assert [(s.name, s.stats) for s in stages.program_spans(profile)] == [
        ("fl.round", {"round": 4, "dropped": 1}), ("fl.encode", {"leaf": 3})]
    assert [s.name for s in View(profile, {}).spans] == ["chipbench.round"]


# ------------------------------------------------------ the chip's trace
@pytest.mark.parametrize("name", READERS)
def test_reader_reproduces_the_chip_run(view, recorded, name):
    value = spec.metric_reader(name).read(view)
    assert value == pytest.approx(recorded["metrics"][name], rel=1e-12)
    assert value > 0


@pytest.mark.parametrize("name", sorted(stages.LAYERS))
def test_layer_idle_reproduces_the_chip_run(view, spans, recorded, name):
    value = stages.idle_ms(view, spans)[name]
    assert value == pytest.approx(recorded["metrics"][name], rel=1e-12)
    assert value > 0


def test_window(view, recorded):
    assert view.n_rounds == recorded["n_rounds"] == 2
    assert view.window_s == pytest.approx(recorded["window_s"], rel=1e-12)
    assert view.busy_s == pytest.approx(recorded["busy_s"], rel=1e-12)


def _modules(view, pattern):
    return [m for m in view.devices[0].modules if pattern in m.name]


def _named(spans, name):
    return [s for s in spans if s.name == name]


# The trace puts each device program up to 1.32 ms before the host call
# that dispatched it (1.03 ms in ``trace_mlp``), measured over every program
# of these traces against its ``PjitFunction`` host event: host and device
# share one clock to within this skew.
CLOCK_SKEW_NS = 2e6


def test_encode_runs_after_its_span_starts(view, spans):
    """Each leaf's encode program starts on the device after the host
    entered that leaf's ``fl.encode``, to within the clock skew."""
    encodes = _named(spans, "fl.encode")
    mods = _modules(view, "jit_encode_leaf_batch")
    assert len(encodes) == len(mods) == 16
    for i, (s, m) in enumerate(zip(encodes, mods)):
        assert s.stats["leaf"] == i % 8
        assert m.start >= s.start - CLOCK_SKEW_NS


def test_losses_read_waits_for_local_sgd(view, spans):
    reads = _named(spans, "fl.host_read")
    mods = _modules(view, "jit_batched_client_update")
    assert len(reads) == len(mods) == 2
    for r, m in zip(reads, mods):
        assert r.stats == {"values": 5}
        assert r.end >= m.end - CLOCK_SKEW_NS


def test_idle_layers_account_for_the_idle_time(view, spans):
    idle_ms = 1e3 * (view.window_s - view.busy_s) / view.n_rounds
    named = sum(stages.idle_ms(view, spans).values())
    assert 0.9 * idle_ms <= named <= idle_ms * (1 + 1e-9)


def test_stages_cover_the_round(view, spans):
    """The host time inside a whole ``fl.round`` that no stage span covers
    is under 5% of it: a new stage without a span shows here."""
    (r,) = [s for s in _named(spans, "fl.round")
            if view.start <= s.start and s.end <= view.end]
    kids = sorted((s.start, s.end) for s in spans
                  if s is not r and r.start <= s.start and s.end <= r.end)
    covered, reach = 0.0, r.start
    for a, b in kids:
        covered += max(0.0, b - max(a, reach))
        reach = max(reach, b)
    assert 1 - covered / (r.end - r.start) < 0.05
    assert r.stats["round"] >= 0 and r.stats["dropped"] in (0, 1, 2)


def test_layers_need_program_spans():
    """The trace of a program without spans (``trace_mlp``) and an empty
    trace read None, never 0."""
    view, spans = load(DATA / "trace_mlp")
    assert spans == [] and stages.idle_ms(view, spans) is None
    empty = View(NS(planes=[]), {})
    assert stages.idle_ms(empty, []) is None
    assert stages.idle_by_stage(empty, []) is None
