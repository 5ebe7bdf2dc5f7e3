"""The run path on the CPU: it refuses to run without the chip, and a
tiny rehearsal (the MNIST-MLP cell of ``data/``, not a chip number) prints a
well-formed result line, also for a cell added as data only."""
import json
import shutil

import pytest

from chipbench_testing import (DATA, any_device, jax_settings,  # noqa: F401
                               load_script, tiny_argv)

run_script = load_script("run")


def _last_json(text: str) -> dict:
    return json.loads(text.strip().splitlines()[-1])


def test_cpu_run_exits_nonzero_without_result(capsys):
    with pytest.raises(SystemExit) as exc:
        run_script.main(["--workload", "mlp_t2_secagg_drop", "--seed", "1",
                         "--seconds", "10", "--trace", "0"])
    assert exc.value.code not in (0, None)
    for line in capsys.readouterr().out.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_well_formed_line(trace, capsys, jax_settings):
    doc = run_script.main(tiny_argv(trace=trace),
                          bench_file=DATA / "BENCHMARK.json", root=DATA,
                          chip_check=any_device)
    captured = capsys.readouterr()
    assert _last_json(captured.out) == doc
    assert list(doc)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(doc)
    assert doc["correct"] is True and doc["failed"] == 0
    assert doc["attempted"] >= 1
    assert set(doc["checks"]) == {"loss", "update1", "change_n",
                                  "residual_n"}
    assert captured.err.strip().splitlines()[-1].startswith(
        "check residual_n:")
    device = doc["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(device)
    if trace:
        # no TPU plane on the CPU: only the counted metric can be read
        assert set(doc["metrics"]) == {"upload_vs_dense"}
        assert {"busy_s", "window_s"} <= set(device)
        assert set(doc["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert set(doc["metrics"]) == {"round_s", "round_p90_s", "setup_s"}
        for m in doc["metrics"].values():
            assert m["value"] > 0 and m["unit"] == "s"


def test_cell_added_as_data_only_runs(tmp_path, capsys, jax_settings):
    root = tmp_path / "bench"
    shutil.copytree(DATA, root)
    traffic = json.loads((root / "traffic" / "tiny_drop.json").read_text())
    traffic.update(name="tiny_calm", dropout_rate=0.0,
                   compare_through_first_drop=False)
    (root / "traffic" / "tiny_calm.json").write_text(json.dumps(traffic))
    shutil.copy(root / "limits" / "tiny_drop.json",
                root / "limits" / "tiny_calm_cell.json")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny_calm_cell", "config": "tiny_mlp",
                               "traffic": "tiny_calm", "chips": 1,
                               "why": "no dropout"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    doc = run_script.main(tiny_argv("tiny_calm_cell", seed=8),
                          bench_file=root / "BENCHMARK.json", root=root,
                          chip_check=any_device)
    assert _last_json(capsys.readouterr().out) == doc
    assert doc["correct"] is True and doc["attempted"] >= 1
