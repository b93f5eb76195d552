"""Each metric reader on a small recorded run record, against the
number worked out by hand."""
import pytest

from chipbench import harness

MB = 2 ** 20


def _record(trace=True):
    reps = [  # report index 10.. (the window's first report is 10)
        {"kind": "h2g", "size_mb": 10.0, "wall_ms": 5.0, "n_chunks": 5,
         "hop_trace": ["b0:h2g", "b0:landed"]},
        {"kind": "g2g", "size_mb": 20.0, "wall_ms": 2.0, "n_chunks": 10,
         "hop_trace": ["b0:g2g", "b0:landed", "b1:g2g", "b1:landed"]},
        {"kind": "spill", "size_mb": 4.0, "wall_ms": 4.0, "n_chunks": 2,
         "hop_trace": ["b0:g2h", "b0:landed"]},
        {"kind": "prefetch", "size_mb": 6.0, "wall_ms": 1.0, "n_chunks": 3,
         "hop_trace": ["b0:h2g", "b0:landed"]},
    ]
    calls = [
        {"op": "store", "ep": "host", "device": False, "nbytes": 10 * MB,
         "wall_s": 0.010, "plans": [10, 10], "ok": True},
        {"op": "fetch", "ep": "gpu0", "device": True, "nbytes": 10 * MB,
         "wall_s": 0.006, "plans": [10, 11], "ok": True},
        {"op": "store", "ep": "gpu0", "device": True, "nbytes": 20 * MB,
         "wall_s": 0.024, "plans": [11, 11], "ok": True},
        {"op": "fetch", "ep": "gpu1", "device": True, "nbytes": 20 * MB,
         "wall_s": 0.004, "plans": [11, 12], "ok": True},
        {"op": "store", "ep": "gpu1", "device": True, "nbytes": 4 * MB,
         "wall_s": 0.014, "plans": [12, 13], "ok": True},
        {"op": "consume", "ep": "gpu0", "device": True, "nbytes": 0,
         "wall_s": 0.002, "plans": [13, 14], "ok": True},
    ]
    return {
        "calls": calls, "reports": reps, "first_report": 10,
        "batch_chunks": 5, "window_s": 0.5, "finished": 2,
        "setup_s": 12.5, "peaks": {"hbm_GBps": 819.0},
        "trace": {"window_s": 0.5, "busy_s": 0.01, "n_devices": 1,
                  "module_s": {"jit_gather": 0.0001,
                               "jit__scatter_into": 0.0003,
                               "jit__grow_pool": 1.0},
                  "idle_s": {}} if trace else None,
    }


def _programs_bytes():
    # h2g 5 scatter; g2g 5+5 gather and scatter; spill 2 gather;
    # prefetch 3 scatter; device stores 10 and 2 slabs, one scatter each
    slabs = 5 + 2 * 10 + 2 + 3 + 10 + 2
    return 2 * slabs * 2 * MB


EXPECTED = {
    "pass_GBps": 30 * MB / 0.5 / 1e9,
    "fetch_p95_ms": 4.0 + 0.95 * 2.0,
    "store_p95_ms": 14.0 + 0.9 * 10.0,
    "setup_s": 12.5,
    "control_ms_per_call": ((6 - 5) + (4 - 2) + (2 - 1)) / 3,
    "put_GBps": 34 * MB / 1e9 / (0.010 + 0.024 + 0.014 - 0.004),
    "staged_GBps": 20 * MB / 1e9 / 0.010,
    "g2g_GBps": 20 * MB / 1e9 / 0.002,
    "spill_MB_per_req": 2.0,
    "reload_MB_per_req": 3.0,
    "copy_roofline": 100 * _programs_bytes() / 819e9 / 0.0004,
    "device_idle": 98.0,
}


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader(metric):
    got = harness.load_reader(metric)(_record())
    assert got == pytest.approx(EXPECTED[metric], rel=1e-9)


@pytest.mark.parametrize("metric", ["copy_roofline", "device_idle"])
def test_trace_reader_is_silent_without_a_trace(metric):
    assert harness.load_reader(metric)(_record(trace=False)) is None
