"""Shared set-up of the benchmark's CPU tests: the checkout and ``src`` on
``sys.path``, the small recorded data, and a fixture that puts back the JAX
settings a benchmark run changes (compile cache, matmul precision)."""
import importlib.util
import sys
from pathlib import Path

import jax
import pytest

REPO = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
for _p in (str(REPO / "src"), str(REPO)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

_SETTINGS = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes",
             "jax_default_matmul_precision")


def load_script(name: str):
    """``chipbench/<name>.py`` as a module (the scripts are not imported
    as part of the package)."""
    spec = importlib.util.spec_from_file_location(
        f"chipbench_script_{name}", REPO / "chipbench" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def jax_settings():
    """Run the test, then restore JAX's settings and drop its caches."""
    from jax.experimental.compilation_cache import compilation_cache

    saved = {k: getattr(jax.config, k) for k in _SETTINGS}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
    jax.clear_caches()


def tiny_cnn():
    """The program-side model of ``data/configs/tiny_cnn.json``, built from
    the program's own VGG layers: two 3x3 SAME convolutions of 8 channels,
    each with bias, batch-statistics BatchNorm, ReLU and a 2x2 max pool,
    then a 392-10 head, on 28x28x1."""
    from repro.models import paper_models as pm

    def init(key):
        ks = jax.random.split(key, 3)
        return {"c0": pm._conv(ks[0], 3, 3, 1, 8), "bn0": pm._bn(8),
                "c1": pm._conv(ks[1], 3, 3, 8, 8), "bn1": pm._bn(8),
                "head": pm._dense(ks[2], 392, 10)}

    def apply(p, x):
        h = x
        for i in range(2):
            h = pm._conv2d(h, p[f"c{i}"]["w"], p[f"c{i}"]["b"])
            h = pm._pool(jax.nn.relu(pm._apply_bn(p[f"bn{i}"], h)))
        return h.reshape(h.shape[0], -1) @ p["head"]["w"] + p["head"]["b"]

    return pm.PaperModel("tiny_cnn", init, apply, (28, 28, 1))


@pytest.fixture
def tiny_cnn_model(monkeypatch):
    """The tiny conv + BatchNorm model in the program's model table, for the
    ``tiny_cnn_drop`` cell of ``data/``."""
    from repro.models.paper_models import PAPER_MODELS

    monkeypatch.setitem(PAPER_MODELS, "tiny_cnn", tiny_cnn())


def tiny_argv(workload: str = "tiny_drop", seed: int = 2147483659,
              trace: int = 0, seconds: float = 1.0) -> list:
    return ["--workload", workload, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace)]


def any_device(n: int):
    """Stands in for the run's chip check: the rehearsal runs on the CPU."""
    return jax.devices()
