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


def tiny_argv(workload: str = "tiny_drop", seed: int = 2147483659,
              trace: int = 0, seconds: float = 1.0) -> list:
    return ["--workload", workload, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace)]


def any_device(n: int):
    """Stands in for the run's chip check: the rehearsal runs on the CPU."""
    return jax.devices()
