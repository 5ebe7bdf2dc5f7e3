"""chip_smoke.py refuses to run anywhere but a TPU (in-process, no child)."""
import importlib.util
import json
from pathlib import Path

import jax
import pytest

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_device_check_raises_on_cpu(chip_smoke):
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(RuntimeError, match="needs a TPU"):
        chip_smoke.require_tpu()


@pytest.mark.parametrize("argv", [[], ["--four-chips"]])
def test_main_fails_before_any_result_line(chip_smoke, capsys, argv):
    with pytest.raises(RuntimeError, match="needs a TPU"):
        chip_smoke.main(argv)
    for line in capsys.readouterr().out.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)
