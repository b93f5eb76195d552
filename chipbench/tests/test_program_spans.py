"""The program's own spans and byte counters, traced on the CPU through
the facade with the real data plane, and the names of the copy programs
that the trace reduction matches."""
import dataclasses
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from chipbench import harness, program_trace, trace
from repro.core import spans
from repro.core.api import FAASTUBE, FaaSTube
from repro.core.backend_jax import COUNTERS, SLAB_SHAPE, JaxBackend, nbytes_of
from repro.core.elastic_pool import SLAB_BYTES
from repro.core.linksim import LinkSim
from repro.core.pathfinder import PathFinder
from repro.core.pinned_buffer import CircularPinnedBuffer
from repro.core.topology import dgx_v100
from repro.core.transfer import CUT_THROUGH, STORE_FORWARD, TransferEngine
from repro.kernels.chunked_copy.ops import gather
from repro.kernels.chunked_copy.pipeline import _scatter_into

ROOT = Path(__file__).resolve().parents[2]
FACADE = ("ft.store", "ft.fetch", "ft.consume")
#: (op, data_id, MB, endpoint): host input, device edges past the 48 MB
#: cap (spills), a reload, a g2g, a g2h and consumes (prefetches)
CALLS = [("store", "in", 6.0, "host"), ("fetch", "in", 6.0, "gpu0")]
CALLS += [("store", f"d{i}", 16.0, "gpu0") for i in range(4)]
CALLS += [("fetch", "d0", 16.0, "gpu2"), ("fetch", "d3", 16.0, "gpu1"),
          ("fetch", "d2", 16.0, "host"), ("consume", "in", 6.0, "gpu0"),
          ("consume", "d3", 16.0, "gpu0"), ("consume", "d1", 16.0, "gpu0")]


def _rows_bytes(nbytes: int) -> int:
    return -(-nbytes // SLAB_BYTES) * SLAB_BYTES


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The calls above, one ``sim.run`` each, inside a ``cb.window``
    under the profiler; the extracted trace and the backend."""
    cfg = dataclasses.replace(FAASTUBE, store_cap_mb=48.0, name="ft-small")
    be = JaxBackend()
    tube = FaaSTube(dgx_v100(), cfg, backend=be)
    tdir = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(tdir):
        with jax.profiler.TraceAnnotation("cb.window"):
            for op, did, mb, ep in CALLS:
                if op == "store":
                    tube.store("p", did, mb, ep, tube.sim.now)
                elif op == "fetch":
                    tube.fetch("c", did, ep, tube.sim.now)
                else:
                    tube.consume(did, ep, tube.sim.now)
                tube.sim.run()
    return program_trace.extract(trace.find_xplane(tdir)), be


def _intervals(ev, pred):
    return [(h[1], h[1] + h[2], h[3]) for h in ev["program"] if pred(h[0])]


def _inside(inner, outer) -> bool:
    return all(any(s0 <= s and e <= e0 and t0 == t for s0, e0, t0 in outer)
               for s, e, t in inner)


def test_puts_lie_inside_stores(traced):
    ev, _ = traced
    puts = _intervals(ev, lambda n: n.startswith("ft.put."))
    assert len(puts) > len(CALLS)
    assert _inside(puts, _intervals(ev, lambda n: n == "ft.store"))


def test_plans_lie_inside_facade_calls_or_sim_run(traced):
    ev, be = traced
    execs = _intervals(ev, lambda n: n.startswith("ft.exec."))
    assert _inside(execs, _intervals(
        ev, lambda n: n in FACADE or n == "ft.sim.run"))
    assert len(execs) == len(be.reports)
    kinds = {r.kind for r in be.reports}
    assert {"h2g", "spill", "reload", "g2g", "g2h"} <= kinds
    assert sorted(h[0] for h in ev["program"]
                  if h[0].startswith("ft.exec.")) \
        == sorted("ft.exec." + r.kind for r in be.reports)


def test_counters_are_the_bytes_the_calls_moved(traced):
    """Every put writes its padded rows; a staged plan stages, uploads
    or downloads each of its rows once, and a plan that ends on a host
    writes them there; g2g moves no host bytes."""
    _, be = traced
    assert all(r.staging == CUT_THROUGH for r in be.reports)
    want = dict.fromkeys(COUNTERS, 0)
    for op, did, mb, ep in CALLS:
        if op != "store":
            continue
        n = nbytes_of(mb)
        dev = ep != "host"
        want["put.dev" if dev else "put.host"] += n
        want["pad"] += _rows_bytes(n)
        want["h2d" if dev else "write"] += _rows_bytes(n)
    for r in be.reports:
        rows = r.n_chunks * SLAB_BYTES
        if r.kind in ("h2g", "reload", "prefetch"):
            want["stage"] += rows
            want["h2d"] += rows
        elif r.kind in ("g2h", "spill"):
            want["d2h"] += rows
            want["stage"] += rows
            want["write"] += rows
    assert be.counters == want


def test_self_never_exceeds_inclusive(traced):
    ev, _ = traced
    sp = program_trace.spans(ev)
    assert {"ft.store", "ft.fetch", "ft.consume", "ft.plan", "ft.sim.run",
            "ft.put.dev", "ft.put.host", spans.PUT_PAD, spans.SYNC,
            spans.H2G_STAGE, spans.G2H_D2H} <= set(sp)
    for name, v in sp.items():
        assert 0.0 <= v["self_s"] <= v["incl_s"], name
        assert v["count"] > 0, name
    assert sp["ft.store"]["count"] == sum(c[0] == "store" for c in CALLS)
    # each store's self time leaves its put out
    assert sp["ft.store"]["self_s"] < sp["ft.put.dev"]["incl_s"]


def test_window_counter_delta():
    """The counters a window adds, and none for a program that keeps no
    counters."""
    spec = harness.load_spec("table1.media", ROOT)
    cells = [harness.Cell(spec, 7, scale=1 / 32, table={}) for _ in "ab"]
    for c in cells:
        c.backend.put_object("x", "gpu0", size_mb=1.0)
    del cells[1].backend.counters         # its store keeps counting
    before = [dict(getattr(c.backend, "counters", {})) for c in cells]
    for c in cells:
        c.window(0.0)
        c.backend.put_object("y", "gpu0", size_mb=3.0)
    got = [program_trace.counter_delta(b, c.backend)
           for b, c in zip(before, cells)]
    assert got[0] == dict.fromkeys(COUNTERS, 0) | {
        "put.dev": nbytes_of(3.0), "pad": 2 * SLAB_BYTES,
        "h2d": 2 * SLAB_BYTES}
    assert got[1] == {}


@pytest.mark.parametrize("staging", [CUT_THROUGH, STORE_FORWARD])
def test_counters_of_a_walk_through_host(staging):
    """g2g staged through the host: one download, one upload, and the
    host copies of each walk."""
    topo = dgx_v100()
    eng = TransferEngine(LinkSim(topo), PathFinder(topo),
                         CircularPinnedBuffer(), topo, g2g="host",
                         staging=staging)
    be = JaxBackend()
    be.put_object("w", "gpu0", size_mb=11.0)
    before = dict(be.counters)
    rep = be.execute(eng.compile("g2g", "t", "gpu0", "gpu4", 11.0,
                                 data_id="w"))
    rows = rep.n_chunks * SLAB_BYTES
    moved = {k: be.counters[k] - before[k] for k in COUNTERS}
    # cut-through: the download lands in a ring window that the upload
    # reads; store-forward lands the object in the host store, then
    # reads it back out
    sf = staging == STORE_FORWARD
    want = {"stage": 2 * rows if sf else rows, "d2h": rows, "h2d": rows,
            "write": rows if sf else 0}
    assert moved == dict.fromkeys(COUNTERS, 0) | want


@pytest.mark.parametrize("fn, name", [(gather, "gather"),
                                      (_scatter_into, "scatter")])
def test_copy_programs_keep_their_names(fn, name):
    """The trace names a program after its jitted function; the copy
    roofline finds the slab copies by these substrings."""
    from chipbench.metrics.copy_roofline import PROGRAMS
    pool = jax.ShapeDtypeStruct((4, *SLAB_SHAPE), jnp.uint8)
    idx = jax.ShapeDtypeStruct((2,), jnp.int32)
    rows = jax.ShapeDtypeStruct((2, *SLAB_SHAPE), jnp.uint8)
    args = (pool, idx) if name == "gather" else (pool, rows, idx)
    text = fn.lower(*args, use_pallas=False).as_text()
    module = re.search(r"module @(\S+)", text).group(1)
    assert name in PROGRAMS and name in module
    assert all(p not in module for p in PROGRAMS if p != name)
