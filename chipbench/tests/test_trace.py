"""The trace reduction on a small trace cut from a traced table1.media
window on one TPU v5e, against a plain count on a 1 us grid."""
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import trace

DATA = Path(__file__).resolve().parent / "data" / "trace_table1_media.json"


@pytest.fixture(scope="module")
def ev():
    return json.loads(DATA.read_text())


def _grid(ev):
    """Busy microseconds of the window, by marking a boolean grid."""
    (w0, wd), = [(s, d) for n, s, d in ev["host"] if n == "cb.window"]
    busy = np.zeros(int(wd // 1000) + 1, bool)
    for evs in ev["device"].values():
        for line, _, s, d in evs:
            if line in trace.OPS_LINES:
                a = int((s - w0) // 1000)
                b = int(np.ceil((s + d - w0) / 1000))
                busy[max(a, 0):max(b, 0)] = True
    return busy, wd


def test_busy_matches_a_grid_count(ev):
    red = trace.reduce(ev)
    busy, wd = _grid(ev)
    assert red["window_s"] == pytest.approx(wd / 1e9)
    # the grid rounds each interval out to whole microseconds
    assert red["busy_s"] <= busy.sum() * 1e-6
    assert red["busy_s"] == pytest.approx(busy.sum() * 1e-6, rel=0.05)


def test_programs_and_idle_add_up(ev):
    red = trace.reduce(ev)
    assert set(red["module_s"]) >= {"jit_gather", "jit__scatter_into"}
    idle = sum(red["idle_s"].values())
    assert idle + red["busy_s"] == pytest.approx(red["window_s"], rel=1e-9)
    assert max(red["idle_s"], key=red["idle_s"].get).startswith("cb.")
    brk = trace.breakdown(red)
    assert brk["device_ops"][0][0] in red["module_s"]
    assert len(brk["idle_gaps"]) <= 10


def test_pause_is_left_out_of_the_window(ev):
    (w0, wd), = [(s, d) for n, s, d in ev["host"] if n == "cb.window"]
    paused = dict(ev, host=ev["host"] + [["cb.pause", w0, wd / 2]])
    red, full = trace.reduce(paused), trace.reduce(ev)
    assert red["window_s"] == pytest.approx(full["window_s"] / 2, rel=1e-6)
    assert red["busy_s"] <= full["busy_s"]


def test_no_device_events_reads_nothing(ev):
    assert trace.reduce(dict(ev, device={})) is None
