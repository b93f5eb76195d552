"""Rehearsal of chip_smoke.py on the CPU: its body at 1/32 of the
deployment size, and its refusal to run without a TPU."""
import importlib.util
from pathlib import Path

import pytest

SCALE = 1 / 32


@pytest.fixture(scope="module")
def chip_smoke():
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_body_tiny(chip_smoke):
    out = chip_smoke.smoke(scale=SCALE, log=lambda line: None)
    live = chip_smoke.LIVE_MB * SCALE
    assert all(out["fill"]["live_mb"][d] >= live
               for d in chip_smoke.DEVICES)
    assert out["spill_reload"]["spills"] >= 1
    assert out["spill_reload"]["reloads"] >= 1
    for ph in out.values():
        assert ph["objects_verified"] > 0


def test_main_refuses_cpu(chip_smoke, capsys):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main()
    assert "no TPU" in str(e.value.code)
    assert capsys.readouterr().out == ""
