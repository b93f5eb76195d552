"""Smoke run of the FaaSTube data plane on one TPU chip.

This is a bring-up check, not a benchmark: it proves that the main path
(``FaaSTube(..., backend="jax")`` -> ``TransferEngine`` -> ``JaxBackend``
-> ``kernels/chunked_copy``) runs on the chip at deployment size and
moves the right bytes.  The times it prints are labelled as smoke
readings.

    python chip_smoke.py

Phases, all through the facade's own ``store`` / ``fetch`` /
``consume`` / ``sim.run``:

``pallas``
    one Table-1 workflow round (h2g input, g2g edges, g2h output) on a
    backend with ``use_pallas=True``, so the Pallas kernels run
    compiled on the chip.
``fill``
    the edges of all six Table-1 workflows (4-128 MB): host->device
    inputs, device->device edges, device->host outputs, repeated with
    fresh ids until each of two device endpoints holds ``LIVE_MB`` of
    live objects.
``spill_reload``
    inputs are consumed, then producers keep storing on one device past
    ``store_cap_mb`` until victims spill device->host; one spilled
    object is fetched back (a demand reload, host->device).

After every phase each object in every store is compared byte for byte
with ``synth_payload``.  Any mismatch or exception fails the run.  The
last line of standard output is the JSON result; it is printed only when
JAX runs on a TPU and every phase passed.
"""
from __future__ import annotations

import gc
import json
import os
import sys
import time
from dataclasses import replace
from pathlib import Path

ROOT = Path(__file__).resolve().parent
#: the facade's simulated per-device store capacity; the backend's
#: physical store is twice this (``FaaSTube.__init__``), so two device
#: stores of 4 GiB fit one 16 GB v5e with room for a pool doubling
STORE_CAP_MB = 2048.0
#: live objects each of the two device endpoints must hold after fill
LIVE_MB = 3072.0
DEVICES = ("gpu0", "gpu1")


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str):
    if not cond:
        raise SmokeFailure(msg)


def compile_cache() -> str:
    """Use ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it
    itself); otherwise keep the cache at a fixed ``<repo>/.jax_cache``,
    so one checkout's runs share it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def _tube(cap_mb: float, backend):
    from repro.core.api import FAASTUBE, FaaSTube
    from repro.core.topology import dgx_v100
    cfg = replace(FAASTUBE, store_cap_mb=cap_mb, name="ft-smoke")
    return FaaSTube(dgx_v100(), cfg, backend=backend)


class Phase:
    """Times the facade calls of one phase and summarizes what the
    backend did during it."""

    def __init__(self, name: str, tube):
        self.name = name
        self.tube = tube
        self.first_s = None
        self.rest_s = 0.0
        self.n_reports = len(tube.backend.reports)
        self.stats0 = dict(tube.stats)

    def call(self, fn, *args):
        t0 = time.perf_counter()
        fn(*args, self.tube.sim.now)
        self.tube.sim.run()
        dt = time.perf_counter() - t0
        if self.first_s is None:
            self.first_s = dt
        else:
            self.rest_s += dt

    def summary(self, verified: tuple[int, float], verify_s: float) -> dict:
        import jax
        be = self.tube.backend
        reps = be.reports[self.n_reports:]
        kinds: dict[str, int] = {}
        for r in reps:
            kinds[r.kind] = kinds.get(r.kind, 0) + 1
        mem = jax.devices()[0].memory_stats() or {}
        return {
            "phase": self.name,
            "plans": len(reps),
            "plan_mb": sum(r.size_mb for r in reps),
            "plan_kinds": kinds,
            "spills": self.tube.stats["migrations"]
            - self.stats0["migrations"],
            "reloads": self.tube.stats["reloads"] - self.stats0["reloads"],
            "first_call_s": self.first_s,
            "rest_s": self.rest_s,
            "verify_s": verify_s,
            "objects_verified": verified[0],
            "mb_verified": verified[1],
            "live_mb": {ep: st.used_mb for ep, st in be.stores.items()},
            "store_device": {
                ep: (str(next(iter(st.slabs.devices()))) if st.device
                     else "host numpy")
                for ep, st in be.stores.items()},
            "peak_bytes_in_use": mem.get("peak_bytes_in_use"),
        }


def verify_all(backend) -> tuple[int, float]:
    """Every object in every store equals its ``synth_payload``."""
    import numpy as np

    from repro.core.backend_jax import synth_payload
    n, mb = 0, 0.0
    for ep, st in backend.stores.items():
        for did, obj in st.objects.items():
            got = backend.read_object(did, ep)
            check(np.array_equal(got, synth_payload(did, obj.nbytes)),
                  f"bytes of {did!r} at {ep} differ from synth_payload")
            n += 1
            mb += obj.nbytes / 2 ** 20
    return n, mb


def workflow_round(ph: Phase, wf, tag: str, scale: float, a: str,
                   b: str, *, fetch: bool = True) -> list[str]:
    """One workflow's Table-1 traffic with fresh ids: host inputs
    fetched to ``a``; edges stored on ``a`` and fetched to ``b`` (to
    the host when the consumer is a CPU stage); outputs stored on ``b``
    and fetched to the host.  ``fetch=False`` stores the edges only (a
    producer outrunning its consumers).  Returns the input ids."""
    tube = ph.tube
    kinds = {s.name: s.kind for s in wf.stages}
    inputs = []
    if fetch:
        for stage, mb in wf.input_mb.items():
            did = f"{tag}/{wf.name}/{stage}/in"
            ph.call(tube.store, "client", did, mb * scale, "host")
            ph.call(tube.fetch, stage, did, a)
            inputs.append(did)
    for s in wf.stages:
        for dep, mb in s.deps:
            did = f"{tag}/{wf.name}/{dep}->{s.name}"
            ph.call(tube.store, dep, did, mb * scale, a)
            if fetch:
                dst = "host" if kinds[s.name] == "cpu" else b
                ph.call(tube.fetch, s.name, did, dst)
    if fetch:
        for stage, mb in wf.output_mb.items():
            did = f"{tag}/{wf.name}/{stage}/out"
            ph.call(tube.store, stage, did, mb * scale, b)
            ph.call(tube.fetch, "client", did, "host")
    return inputs


def _finish(ph: Phase, log) -> dict:
    t0 = time.perf_counter()
    verified = verify_all(ph.tube.backend)
    out = ph.summary(verified, time.perf_counter() - t0)
    log("smoke phase " + json.dumps(out, sort_keys=True))
    return out


def smoke(scale: float = 1.0, log=print) -> dict:
    """The smoke body at ``scale`` times the deployment size: object
    sizes, store capacity and the live target all scale together."""
    from repro.core.backend_jax import JaxBackend
    from repro.core.migration import HOST
    from repro.serving.workflow import DRIVING, WORKFLOWS

    out = {}
    cap = STORE_CAP_MB * scale

    # -- pallas: one h2g -> g2g -> g2h round on the Pallas kernels
    tube = _tube(cap, JaxBackend(store_mb=2 * cap, host_mb=2 * cap,
                                 use_pallas=True))
    ph = Phase("pallas", tube)
    workflow_round(ph, DRIVING, "pallas", scale, *DEVICES)
    out["pallas"] = _finish(ph, log)
    check(set(out["pallas"]["plan_kinds"]) >= {"h2g", "g2g", "g2h"},
          f"pallas round missed a plan kind: {out['pallas']['plan_kinds']}")
    del tube, ph
    gc.collect()                      # the facade holds cycles: free its stores

    # -- fill: Table-1 traffic until both endpoints hold LIVE_MB
    tube = _tube(cap, "jax")
    be = tube.backend
    ph = Phase("fill", tube)
    inputs: list[str] = []
    r = 0
    while min(be.store_for(d).used_mb for d in DEVICES) < LIVE_MB * scale:
        a, b = DEVICES if r % 2 == 0 else DEVICES[::-1]
        for wf in WORKFLOWS.values():
            inputs += workflow_round(ph, wf, f"r{r}", scale, a, b)
        r += 1
        check(r <= 6, "fill did not reach the live target in 6 rounds")
    out["fill"] = _finish(ph, log)
    check(out["fill"]["spills"] == 0, "fill spilled before the cap")

    # -- spill_reload: consume inputs, then store past store_cap_mb
    ph = Phase("spill_reload", tube)
    for did in inputs:
        ph.call(tube.consume, did, "host")
    k = 0
    while tube.stats["migrations"] == ph.stats0["migrations"]:
        check(k < 2, "storing past store_cap_mb spilled nothing")
        for wf in WORKFLOWS.values():
            workflow_round(ph, wf, f"p{k}", scale, DEVICES[0], "",
                           fetch=False)
        k += 1
    spilled = [d for d, it in tube.items[DEVICES[0]].items()
               if it.state == HOST]
    check(bool(spilled), "no stored object reached the HOST state")
    ph.call(tube.fetch, "consumer", spilled[0], DEVICES[1])
    out["spill_reload"] = _finish(ph, log)
    kinds = out["spill_reload"]["plan_kinds"]
    check(kinds.get("spill", 0) >= 1 and kinds.get("reload", 0) >= 1,
          f"spill/reload plans did not run through the backend: {kinds}")
    check(out["spill_reload"]["reloads"] >= 1, "no demand reload")
    return out


def main() -> int:
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU: JAX found {len(devs)} "
                 f"{d.platform!r} device(s); this run needs a TPU chip")
    print(f"chip_smoke: smoke run, not a benchmark; device_kind="
          f"{d.device_kind!r} count={len(devs)} bytes_limit="
          f"{(d.memory_stats() or {}).get('bytes_limit')}")
    print(f"chip_smoke: compile cache {compile_cache()}")
    sys.path.insert(0, str(ROOT / "src"))
    smoke()
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
