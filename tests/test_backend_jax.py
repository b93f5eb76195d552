"""Differential conformance: the jax data plane vs the numpy oracle.

Every TransferPlan kind the simulator can compile must, when executed
by the real backend, land byte-identical payloads at the destination
(`synth_payload` is the oracle both sides regenerate independently),
report progress on trigger-batch multiples, and keep the observable
cut_through / store_forward contrast.  And the cardinal rule: arming
the backend on a FaaSTube run changes NOTHING in the simulated event
stream — completion times, progress series and stats stay identical to
a plain run.

Runs on CPU jax (pallas interpret mode) — no GPU anywhere.
"""
import dataclasses

import numpy as np
import pytest

from repro.core.api import FAASTUBE, FaaSTube
from repro.core.backend_jax import (
    JaxBackend,
    nbytes_of,
    synth_payload,
)
from repro.core.linksim import LinkSim
from repro.core.pathfinder import PathFinder
from repro.core.pinned_buffer import CircularPinnedBuffer
from repro.core.topology import cluster, dgx_v100
from repro.core.transfer import (
    CUT_THROUGH,
    STORE_FORWARD,
    TransferEngine,
)


def make_engine(topo_fn=dgx_v100, **kw):
    topo = topo_fn()
    return TransferEngine(LinkSim(topo), PathFinder(topo),
                          CircularPinnedBuffer(), topo, **kw)


def run_plan(eng, be, kind, src, dst, size_mb, did, **exec_kw):
    plan = eng.compile(kind, "t", src, dst, size_mb, data_id=did)
    rep = be.execute(plan, **exec_kw)
    return plan, rep


def oracle(did, size_mb):
    return synth_payload(did, nbytes_of(size_mb))


# kind-case -> (topo builder, plan kind, src, dst, engine kwargs)
MATRIX = {
    "h2g": (dgx_v100, "h2g", "host", "gpu1", {}),
    "g2h": (dgx_v100, "g2h", "gpu1", "host", {}),
    "g2g_direct": (dgx_v100, "g2g", "gpu0", "gpu1", {"g2g": "direct"}),
    "g2g_striped": (dgx_v100, "g2g", "gpu0", "gpu5",
                    {"g2g": "multipath"}),
    "g2g_host": (dgx_v100, "g2g", "gpu0", "gpu4", {"g2g": "host"}),
    "internode": (lambda: cluster(2), "internode", "n0:gpu0", "n1:gpu1",
                  {}),
    "spill": (dgx_v100, "spill", "gpu1", "host", {}),
    "reload": (dgx_v100, "reload", "host", "gpu3", {}),
    "h2h": (lambda: cluster(2), "h2h", "n0:host", "n1:host", {}),
}
SIZE_MB = 11.0          # 6 chunks, ragged 1 MB tail, 2 trigger batches


@pytest.mark.parametrize("staging", [CUT_THROUGH, STORE_FORWARD])
@pytest.mark.parametrize("case", sorted(MATRIX))
def test_matrix_byte_identical(case, staging):
    topo_fn, kind, src, dst, kw = MATRIX[case]
    eng = make_engine(topo_fn, staging=staging, **kw)
    be = JaxBackend()
    did = f"{case}-{staging}"
    plan, rep = run_plan(eng, be, kind, src, dst, SIZE_MB, did)
    assert rep is not None and rep.n_chunks == 6
    np.testing.assert_array_equal(be.read_object(did, plan.dst),
                                  oracle(did, SIZE_MB))
    # the source copy survives the move (transfers copy, not migrate)
    np.testing.assert_array_equal(be.read_object(did, plan.src),
                                  oracle(did, SIZE_MB))
    mbs = [mb for mb, _ in rep.events]
    assert mbs == sorted(mbs) and mbs[-1] == SIZE_MB
    # multipath hops stripe: explicit g2g multipath, and the engine's
    # default parallel-h2g mode (h2g / g2h / reload all compile with
    # multipath=True under h2g="parallel")
    want_stripes = 2 if case in ("g2g_striped", "h2g", "g2h",
                                 "reload") else 1
    assert rep.stripes == want_stripes


def test_progress_on_trigger_batch_multiples():
    eng = make_engine()
    be = JaxBackend()
    seen = []
    _, rep = run_plan(eng, be, "h2g", "host", "gpu1", 32.0, "prog",
                      on_progress=seen.append)
    assert seen == [10.0, 20.0, 30.0, 32.0]
    assert [mb for mb, _ in rep.events] == seen
    # sub-batch transfer: a single ragged event
    seen2 = []
    run_plan(eng, be, "h2g", "host", "gpu2", 4.0, "prog2",
             on_progress=seen2.append)
    assert seen2 == [4.0]


@pytest.mark.parametrize("staging", [CUT_THROUGH, STORE_FORWARD])
def test_staging_modes_observably_differ(staging):
    """SF materializes the whole object per hop; CT hands off one
    trigger batch at a time through bounded ring windows."""
    eng = make_engine(lambda: cluster(2), staging=staging)
    be = JaxBackend()
    did = f"obs-{staging}"
    _, rep = run_plan(eng, be, "internode", "n0:gpu0", "n1:gpu1", 24.0,
                      did)
    np.testing.assert_array_equal(be.read_object(did, "n1:gpu1"),
                                  oracle(did, 24.0))
    if staging == STORE_FORWARD:
        assert rep.peak_staging_mb >= 24.0
        # hop-major trace: every batch of hop 0 precedes hop 1
        h0 = [i for i, t in enumerate(rep.hop_trace) if t.startswith("h0")]
        h1 = [i for i, t in enumerate(rep.hop_trace) if t.startswith("h1")]
        assert max(h0) < min(h1)
    else:
        assert rep.peak_staging_mb <= 10.0      # one trigger-batch window
        # batch-major trace: b0 walks g2h -> net -> h2g before b1 enters
        b0 = [t for t in rep.hop_trace if t.startswith("b0:")]
        assert b0[:3] == ["b0:g2h", "b0:net", "b0:h2g"]
    # ring windows fully drain
    assert all(r.in_flight_mb == 0.0 for r in be.rings.values())


def test_zero_regenerations():
    """Pre-put sources are moved, never re-synthesized: after setup the
    backend's put path must go cold."""
    eng = make_engine()
    be = JaxBackend()
    for i, dev in enumerate(["host", "gpu0", "gpu2"]):
        be.put_object(f"z{i}", dev, size_mb=6.0)

    def boom(*a, **k):
        raise AssertionError("backend regenerated a source object")

    be.put_object = boom
    for i, (kind, src, dst) in enumerate([("h2g", "host", "gpu1"),
                                          ("g2g", "gpu0", "gpu1"),
                                          ("g2h", "gpu2", "host")]):
        did = f"z{i}"
        plan, _ = run_plan(eng, be, kind, src, dst, 6.0, did)
        np.testing.assert_array_equal(be.read_object(did, plan.dst),
                                      oracle(did, 6.0))


def _facade_run(backend):
    tube = FaaSTube(dgx_v100(), FAASTUBE, backend=backend)
    trace = {"ready": [], "progress": []}
    tube.store("prod", "x", 24.0, "host", 0.0)
    tube.store("prod", "y", 16.0, "gpu0", 0.0)
    tube.fetch("cons", "x", "gpu1", 0.0,
               on_ready=lambda s, t: trace["ready"].append(("x", t)),
               on_progress=lambda s, h: trace["progress"].append(
                   (h.data_id if hasattr(h, "data_id") else "x",
                    h.done_mb)))
    tube.fetch("cons", "y", "gpu4", 1.0,
               on_ready=lambda s, t: trace["ready"].append(("y", t)))
    tube.sim.run()
    trace["now"] = tube.sim.now
    return trace, tube


def test_sim_trace_identical_with_backend_armed():
    """The cardinal rule: backend="jax" moves real bytes strictly
    outside the event stream — the simulated trace is unchanged."""
    plain, _ = _facade_run(None)
    armed, tube = _facade_run("jax")
    assert plain == armed
    # and the real bytes actually landed where the sim says they are
    np.testing.assert_array_equal(
        tube.backend.read_object("x", "gpu1"), oracle("x", 24.0))
    np.testing.assert_array_equal(
        tube.backend.read_object("y", "gpu4"), oracle("y", 16.0))


def test_facade_spill_reload_real_bytes():
    """Capacity pressure spills REAL bytes to the host store; a fetch
    demand-reloads them back byte-identical."""
    cfg = dataclasses.replace(FAASTUBE, store_cap_mb=48.0,
                              name="ft-small")
    tube = FaaSTube(dgx_v100(), cfg, backend="jax")
    for i in range(4):
        tube.store("prod", f"d{i}", 16.0, "gpu0", float(i))
    tube.sim.run()
    assert "host" in tube.backend.where("d0")       # victim spilled out
    tube.fetch("cons", "d0", "gpu2", 100.0)
    tube.sim.run()
    np.testing.assert_array_equal(
        tube.backend.read_object("d0", "gpu2"), oracle("d0", 16.0))


def test_pallas_arm_byte_identical():
    """use_pallas=True (interpret mode on CPU) is interchangeable with
    the jnp reference arm."""
    eng = make_engine()
    be = JaxBackend(use_pallas=True)
    plan, _ = run_plan(eng, be, "h2g", "host", "gpu1", 6.0, "pal")
    np.testing.assert_array_equal(be.read_object("pal", plan.dst),
                                  oracle("pal", 6.0))


def test_ring_windows_bounded_and_drained():
    eng = make_engine()
    be = JaxBackend()
    for i in range(3):
        run_plan(eng, be, "h2g", "host", f"gpu{i}", 32.0, f"r{i}")
    ring = be.rings["host"]
    assert ring.stalls == 0
    assert ring.peak_mb <= ring.size_mb
    assert ring.in_flight_mb == 0.0


def test_put_object_replaces_stale_copy():
    be = JaxBackend()
    be.put_object("u", "gpu0", size_mb=4.0)
    fresh = np.arange(nbytes_of(4.0), dtype=np.uint8) % 251
    be.put_object("u", "gpu0", payload=fresh)
    np.testing.assert_array_equal(be.read_object("u", "gpu0"), fresh)
    used = be.store_for("gpu0").used_mb
    assert used == 4.0          # the stale copy's slabs were freed
