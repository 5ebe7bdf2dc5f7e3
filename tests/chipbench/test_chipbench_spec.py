"""BENCHMARK.json, and the files each of its cells resolves to."""
import json
import re
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_testing import DATA, REPO

from chipbench import compare, spec

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WORKLOADS = [w["name"] for w in BENCH["workloads"]]
CONFIGS = sorted(p.stem for p in (REPO / "chipbench" / "configs").glob(
    "*.json"))
TRAFFIC_KEYS = {"partition", "noniid_k", "n_train", "n_test", "n_clients",
                "clients_per_round", "local_steps", "local_batch",
                "local_lr", "dropout_rate", "compare_rounds"}


def test_benchmark_file_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for p in BENCH["paths"]:
        assert (REPO / p).is_dir() and not p.startswith("/") and ".." not in p
    script = BENCH["command"][1]
    assert any(script.startswith(p + "/") for p in BENCH["paths"])
    assert 1 <= BENCH["run_seconds"] <= 51
    for entry in (BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"]
                  + BENCH["per_layer"]):
        assert NAME.match(entry["name"]), entry["name"]
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and UNIT.match(m["unit"])
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and UNIT.match(m["unit"])
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert callable(spec.metric_reader(m["name"]).read)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_resolves_to_its_files(workload):
    cell = spec.resolve(workload)
    assert cell.chips in (1, 4)
    assert TRAFFIC_KEYS <= set(cell.traffic)
    names = compare.compared(cell.limits)
    assert names and all(cell.limits[k] > 0 for k in names)
    for fn in ("init", "forward", "forward_flops"):
        assert callable(getattr(cell.reference, fn))
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer


def test_unknown_workload_is_refused():
    with pytest.raises(KeyError, match="no workload"):
        spec.resolve("no_such_cell")


def test_every_benchmark_config_has_its_files():
    for c in BENCH["configs"]:
        path = REPO / c["file"]
        assert path.stem in CONFIGS and path.with_suffix(".py").is_file()
        config = json.loads(path.read_text())
        assert config["name"] == c["name"]
        assert set(c["reduced"]) <= set(config)


@pytest.mark.parametrize("name", CONFIGS)
def test_config_file_is_the_program_model(name):
    """The configuration's sizes are the program's model's, its reference
    draws the program's initial weights from a seed, and its forward pass
    agrees with the program's on the CPU at float32."""
    from repro.models.paper_models import PAPER_MODELS

    path = REPO / "chipbench" / "configs" / f"{name}.json"
    config = json.loads(path.read_text())
    model = PAPER_MODELS[config["model"]]
    ref = spec.load_module(path.with_suffix(".py"))
    key = jax.random.key(3)
    prog, mine = model.init(key), ref.init(key)
    leaves = jax.tree_util.tree_leaves(prog)
    assert sum(x.size for x in leaves) == config["params"]
    assert len(leaves) == config["leaves"]
    assert (jax.tree_util.tree_structure(prog)
            == jax.tree_util.tree_structure(mine))
    for a, b in zip(leaves, jax.tree_util.tree_leaves(mine)):
        np.testing.assert_array_equal(a, b)
    x = jax.random.normal(jax.random.key(4), (4, 32, 32, 3), jnp.float32)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(ref.forward(mine, x),
                                   model.apply(prog, x),
                                   rtol=2e-4, atol=2e-5)


def test_cell_added_as_data_only_resolves(tmp_path):
    """A new cell is an entry of BENCHMARK.json plus its data files."""
    root = tmp_path / "bench"
    shutil.copytree(DATA, root)
    traffic = json.loads((root / "traffic" / "tiny_drop.json").read_text())
    traffic.update(name="tiny_calm", dropout_rate=0.0)
    (root / "traffic" / "tiny_calm.json").write_text(json.dumps(traffic))
    shutil.copy(root / "limits" / "tiny_drop.json",
                root / "limits" / "tiny_calm_cell.json")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny_calm_cell", "config": "tiny_mlp",
                               "traffic": "tiny_calm", "chips": 1,
                               "why": "no dropout"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.resolve("tiny_calm_cell", root / "BENCHMARK.json", root)
    assert cell.traffic["dropout_rate"] == 0.0
    assert cell.config["model"] == "mnist_mlp"
