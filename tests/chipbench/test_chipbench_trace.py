"""The reduction from a profiler trace to the per-layer metrics, on a small
trace recorded on a TPU v5e (``data/trace_mlp``: a ``--trace 1`` run of
``mlp_t2_secagg_drop`` with a one-second window) and the facts of its
traced rounds."""
import gzip
import json

import pytest
from jax._src.profiler import ProfileData

from chipbench_testing import DATA

from chipbench import spec
from chipbench.peaks import PEAKS, peaks_for
from chipbench.trace import View

FIXTURE = DATA / "trace_mlp"


@pytest.fixture(scope="module")
def view():
    raw = gzip.decompress((FIXTURE / "trace.xplane.pb.gz").read_bytes())
    facts = json.loads((FIXTURE / "facts.json").read_text())
    return View(ProfileData.from_serialized_xspace(raw), facts)


@pytest.fixture(scope="module")
def recorded():
    return json.loads((FIXTURE / "metrics.json").read_text())


def test_window_and_busy_time(view, recorded):
    assert view.devices and view.devices[0].name == "/device:TPU:0"
    assert view.n_rounds >= 1
    assert 0 < view.busy_s < view.window_s
    assert view.window_s == pytest.approx(recorded["window_s"], rel=1e-12)
    assert view.busy_s == pytest.approx(recorded["busy_s"], rel=1e-12)


@pytest.mark.parametrize("name", ["local_sgd_ms", "encode_ms", "decode_ms",
                                  "device_idle", "round_mfu",
                                  "upload_vs_dense"])
def test_reader_reproduces_the_chip_run(view, recorded, name):
    value = spec.metric_reader(name).read(view)
    assert value == pytest.approx(recorded["metrics"][name], rel=1e-12)
    if name in ("device_idle", "round_mfu"):
        assert 0 < value <= 100


def test_scatter_roofline_from_the_raw_events(view):
    """The kernel's share of its roofline, worked out again from the raw
    trace: its custom calls' device time against the bytes of the slots and
    the dense leaves the traced round decoded, at the v5e's HBM rate."""
    raw = ProfileData.from_serialized_xspace(gzip.decompress(
        (FIXTURE / "trace.xplane.pb.gz").read_bytes()))
    plane = next(p for p in raw.planes if p.name == "/device:TPU:0")
    ops = next(ln for ln in plane.lines if ln.name == "XLA Ops")
    kernel_ns = sum(e.duration_ns for e in ops.events
                    if e.name.startswith("%stream_scatter_add.")
                    and view.start <= e.start_ns < view.end)
    r = view.facts["rounds"][0]
    C, dropped = r["n_clients"], r["n_survivors"] < r["n_clients"]
    slots = sum(C * (k + C * km) + (C * C * km if dropped else 0)
                for k, km in zip(r["ks"], r["k_masks"]))
    least_s = (8 * slots + 4 * sum(r["leaf_sizes"])) / 819e9
    value = spec.metric_reader("stream_scatter_add_roofline").read(view)
    assert value == pytest.approx(100 * least_s / (kernel_ns * 1e-9),
                                  rel=1e-9)
    assert 0 < value < 1


def test_layers_fit_inside_the_busy_time(view):
    layers = sum(view.module_s(p) for p in (
        "batched_client_update", "encode_leaf_batch", "decode_leaf_batch"))
    assert 0 < layers <= view.busy_s * (1 + 1e-9) + 1e-3


def test_breakdown_lists(view):
    for rows in (view.top_ops(), view.idle_by_host()):
        assert 0 < len(rows) <= 10
        assert all(isinstance(n, str) and s > 0 for n, s in rows)
        assert [s for _, s in rows] == sorted((s for _, s in rows),
                                              reverse=True)


def test_unknown_device_has_no_peaks():
    assert peaks_for("TPU v5 lite") is PEAKS["TPU v5 lite"]
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("TPU v9 imaginary")


def test_readers_read_nothing_from_an_empty_trace():
    class Empty:
        planes = ()

    empty = View(Empty(), {"rounds": [], "peaks": None,
                           "train_flops_per_round": 1})
    for m in ("local_sgd_ms", "encode_ms", "decode_ms", "device_idle",
              "round_mfu", "stream_scatter_add_roofline", "upload_vs_dense"):
        assert spec.metric_reader(m).read(empty) is None
