"""The program's spans and counters as ``chipbench.program_trace`` reads
them: the reduction with both span families on recorded chip traces and
hand-made ones, and the readers of ``PER_LAYER`` on a hand-made record
(``test_cells`` runs a traced cell through them)."""
import json
from pathlib import Path

import pytest

from chipbench import harness, program_trace, trace
from repro.core import spans

DATA = Path(__file__).resolve().parent / "data"
MB = 2 ** 20
#: readers of the program's spans and counters (``metrics/<name>.py``)
PER_LAYER = ("facade_self_ms_per_call", "put_dev_GBps", "hostcopy_GBps",
             "link_wait_share")

#: program spans of a traced window: inclusive and self seconds, count
SPANS = {
    "ft.store": {"incl_s": 0.040, "self_s": 0.002, "count": 3},
    "ft.fetch": {"incl_s": 0.010, "self_s": 0.001, "count": 2},
    "ft.consume": {"incl_s": 0.002, "self_s": 0.0005, "count": 1},
    "ft.plan": {"incl_s": 0.0003, "self_s": 0.0003, "count": 4},
    "ft.sim.run": {"incl_s": 0.004, "self_s": 0.0007, "count": 6},
    "ft.put.dev": {"incl_s": 0.030, "self_s": 0.001, "count": 2},
    "ft.put.host": {"incl_s": 0.008, "self_s": 0.0005, "count": 1},
    "ft.put.pad": {"incl_s": 0.012, "self_s": 0.012, "count": 3},
    "ft.put.h2d": {"incl_s": 0.006, "self_s": 0.006, "count": 2},
    "ft.put.scatter": {"incl_s": 0.001, "self_s": 0.001, "count": 2},
    "ft.put.sync": {"incl_s": 0.009, "self_s": 0.009, "count": 2},
    "ft.put.write": {"incl_s": 0.004, "self_s": 0.004, "count": 1},
    "ft.exec.h2g": {"incl_s": 0.005, "self_s": 0.0005, "count": 1},
    "ft.exec.g2g": {"incl_s": 0.002, "self_s": 0.001, "count": 1},
    "ft.h2g.stage": {"incl_s": 0.002, "self_s": 0.002, "count": 1},
    "ft.h2g.h2d": {"incl_s": 0.001, "self_s": 0.001, "count": 1},
    "ft.h2g.scatter": {"incl_s": 0.0005, "self_s": 0.0005, "count": 1},
    "ft.g2g.gather": {"incl_s": 0.0005, "self_s": 0.0005, "count": 2},
    "ft.g2g.scatter": {"incl_s": 0.0005, "self_s": 0.0005, "count": 2},
    "ft.sync": {"incl_s": 0.0015, "self_s": 0.0015, "count": 5},
}
COUNTERS = {"put.dev": 24 * MB, "put.host": 10 * MB, "pad": 34 * MB,
            "stage": 10 * MB, "write": 10 * MB, "h2d": 34 * MB, "d2h": 0}
EXPECTED = {
    "facade_self_ms_per_call": 1e3 * (0.002 + 0.001 + 0.0005 + 0.0003
                                      + 0.0007) / (3 + 2 + 1),
    "put_dev_GBps": 24 * MB / 1e9 / 0.030,
    "hostcopy_GBps": (34 + 10 + 10) * MB / 1e9 / (0.012 + 0.004 + 0.002),
    "link_wait_share": 100 * (0.006 + 0.009 + 0.001 + 0.0015)
    / (0.030 + 0.008 + 0.005 + 0.002),
}
#: spans that name one host call, not a call, a plan or an object write
#: that holds others
LEAVES = {v for k, v in vars(spans).items() if k.isupper()} - {
    spans.PREFIX, spans.PUT_DEV, spans.PUT_HOST, spans.GROW}


@pytest.fixture(scope="module")
def ev():
    """The benchmark's recorded media trace, which has no ``ft.`` span."""
    return json.loads((DATA / "trace_table1_media.json").read_text())


@pytest.fixture(scope="module")
def ft_ev():
    """Two steps of a traced table1.media window on one TPU v5e, with the
    program's ``ft.`` spans."""
    return json.loads((DATA / "trace_table1_media_ft.json").read_text())


def test_reduction_without_program_spans_is_unchanged(ev):
    """A trace with no ``ft.`` spans reduces to what ``trace.reduce``
    gives, and holds no program span."""
    red = program_trace.reduce(ev)
    assert red == trace.reduce(ev)
    assert red["window_s"] == pytest.approx(1.247731284, rel=1e-12)
    assert red["busy_s"] == pytest.approx(0.009788554, rel=1e-12)
    assert red["module_s"] == pytest.approx(
        {"jit__scatter_into": 0.007358857, "jit_gather": 0.002755062},
        rel=1e-12)
    assert red["idle_s"] == pytest.approx(
        {"cb.store.host": 0.212946471, "cb.fetch.gpu": 0.118115222,
         "cb.store.gpu": 0.802056883, "cb.sim_run": 0.002545452,
         "cb.fetch.host": 0.102278702}, rel=1e-12)
    assert program_trace.spans(ev) == {}


def test_spans_inclusive_self_and_count():
    """Nesting per thread, clipped to the window less its pause."""
    host = [["cb.window", 100, 1000], ["cb.pause", 500, 100]]
    program = [["ft.store", 200, 500, "main"],    # 200-700, 100 paused
               ["ft.put.dev", 200, 300, "main"],  # same start: a child
               ["ft.put.pad", 250, 100, "main"],
               ["ft.sync", 520, 50, "main"],      # inside the pause
               ["ft.sim.run", 800, 400, "main"],  # 800-1200, clipped 300
               ["ft.store", 300, 100, "other"],   # another thread
               ["ft.plan", 1150, 10, "main"]]     # starts after the window
    sp = program_trace.spans({"host": host, "program": program,
                              "device": {}})
    assert sp["ft.store"] == {"incl_s": 500e-9, "self_s": 200e-9,
                              "count": 2}
    assert sp["ft.put.dev"] == {"incl_s": 300e-9, "self_s": 200e-9,
                                "count": 1}
    assert sp["ft.put.pad"]["self_s"] == sp["ft.put.pad"]["incl_s"] == 100e-9
    assert sp["ft.sync"] == {"incl_s": 0.0, "self_s": 0.0, "count": 0}
    assert sp["ft.sim.run"] == {"incl_s": 300e-9, "self_s": 300e-9,
                                "count": 1}
    assert sp["ft.plan"]["count"] == 0


def test_idle_goes_to_the_innermost_span_of_either_family(ev):
    """A program span inside each benchmark store span takes its idle
    time; the rest of the attribution stays as it was."""
    red0 = trace.reduce(ev)
    inner = [["ft.put.pad", s + 1, d - 2, "python"]
             for n, s, d in ev["host"] if n == "cb.store.gpu"]
    red = program_trace.reduce(dict(ev, program=inner))
    assert red["idle_s"]["ft.put.pad"] == pytest.approx(
        red0["idle_s"]["cb.store.gpu"], rel=1e-3)
    assert red["idle_s"].get("cb.store.gpu", 0.0) < 1e-3 * red0[
        "idle_s"]["cb.store.gpu"]
    for k in red0["idle_s"].keys() - {"cb.store.gpu"}:
        assert red["idle_s"][k] == pytest.approx(red0["idle_s"][k])
    assert sum(red["idle_s"].values()) == pytest.approx(
        sum(red0["idle_s"].values()), rel=1e-12)
    for k in ("window_s", "busy_s", "module_s"):
        assert red[k] == red0[k]


def test_a_span_that_starts_with_its_parent_is_inner():
    """Two spans that start together: the shorter takes the idle."""
    host = [["cb.window", 0, 1000], ["cb.store.gpu", 100, 800]]
    program = [["ft.put.sync", 100, 500, "main"],
               ["ft.put.dev", 100, 700, "main"]]
    device = {"/device:TPU:0": [["XLA Ops", "op", 0, 50],
                                ["XLA Ops", "op", 950, 50]]}
    red = program_trace.reduce({"host": host, "program": program,
                                "device": device})
    assert red["idle_s"] == pytest.approx({"ft.put.sync": 900e-9})


def test_idle_gaps_fall_under_program_leaves(ft_ev):
    red = program_trace.reduce(ft_ev)
    idle = red["idle_s"]
    assert sum(v for k, v in idle.items() if k.startswith("ft.")) \
        >= 0.9 * sum(idle.values())
    assert trace.breakdown(red)["idle_gaps"][0][0] in LEAVES
    assert set(red["module_s"]) >= {"jit_gather", "jit__scatter_into"}
    # the benchmark's own reduction of the same trace is unchanged by
    # the program's spans
    base = trace.reduce(ft_ev)
    for k in ("window_s", "busy_s", "module_s"):
        assert red[k] == base[k]
    assert sum(idle.values()) == pytest.approx(
        sum(base["idle_s"].values()), rel=1e-12)


def test_spans_of_a_chip_trace(ft_ev):
    sp = program_trace.spans(ft_ev)
    assert {"ft.store", "ft.fetch", "ft.consume", spans.PUT_DEV,
            spans.PUT_PAD, spans.SYNC, spans.H2G_STAGE} <= set(sp)
    for name, v in sp.items():
        assert 0.0 <= v["self_s"] <= v["incl_s"], name
    # a put's leaves lie inside it
    leaves = sum(sp[n]["incl_s"] for n in sp
                 if n.startswith("ft.put.") and n not in (
                     spans.PUT_DEV, spans.PUT_HOST))
    whole = sp[spans.PUT_DEV]["incl_s"] + sp.get(
        spans.PUT_HOST, {"incl_s": 0.0})["incl_s"]
    assert leaves <= whole


def _record(trace=True):
    return {"trace": {} if trace else None,
            "spans": SPANS if trace else None, "counters": COUNTERS}


@pytest.mark.parametrize("metric", PER_LAYER)
def test_reader(metric):
    got = harness.load_reader(metric)(_record())
    assert got == pytest.approx(EXPECTED[metric], rel=1e-9)


@pytest.mark.parametrize("metric", PER_LAYER)
def test_reader_is_silent_without_a_trace(metric):
    assert harness.load_reader(metric)(_record(trace=False)) is None


@pytest.mark.parametrize("metric", PER_LAYER)
def test_reader_is_silent_without_program_spans(metric):
    """A traced program that has no ``ft.`` spans or counters."""
    rec = dict(_record(), spans={}, counters={})
    assert harness.load_reader(metric)(rec) is None


@pytest.mark.parametrize("metric", PER_LAYER)
def test_reader_is_silent_on_a_benchmark_record(metric):
    """The record of an untraced ``harness.run_cell`` holds no spans or
    counters."""
    assert harness.load_reader(metric)({"trace": {}}) is None
