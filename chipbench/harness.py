"""One cell of the benchmark: the FaaSTube facade with the real JAX data
plane, driven by the delayed-consumers traffic, one closed-loop client.

Everything that belongs to a cell is found by name: the cell in
``BENCHMARK.json``, its configuration file, its traffic file
(``traffic/<traffic>.json``) and one reader per metric
(``metrics/<metric>.py``, a ``read(record)`` that returns a number or
None).  Adding a cell or a metric adds files and edits none here.

The payload seam.  ``FaaSTube.store`` asks its backend for the payload
of every object it stores (``JaxBackend.put_object`` with no payload),
and the program would make it with ``synth_payload`` inside the timed
call.  A producer's output is an input of the benchmark, not work of the
data plane, so ``TableBackend.put_object`` takes the bytes from a table
made once at set-up, keyed by object id.  A miss is counted and fails
the run.  Before each store the harness writes the storing request's
index over the first bytes of the entry (``reference.stamp``).  The
check never reads the table: it recomputes every payload, stamp and all,
from ``reference.py``.

Store sizes.  A store starts at ``SlabStore.START_MB`` and grows by its
own rule when an allocation does not fit, which compiles a new pool
shape.  Set-up first drives the cell's warm-up and two periods of its
traffic through a facade whose backend only counts slab rows
(``RowLedger``), then grows each real store once to the first doubling
of ``START_MB`` that holds that peak.  The window, which repeats the same
period, then never grows a pool.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench import reference, trace
from chipbench.traffic import MB, DelayedConsumers, nbytes_of
from repro.core import topology
from repro.core.api import SYSTEMS, FaaSTube
from repro.core.backend_jax import JaxBackend, SlabStore
from repro.core.elastic_pool import BLOCK_MB, blocks_for
from repro.core.linksim import BATCH_CHUNKS
from repro.core.transfer import is_device

BENCH_DIR = Path(__file__).resolve().parent
#: rows the check reads back from a device pool per call
READ_ROWS = 8
#: events of a lowering to MLIR: one per compilation, cache hit or not
COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
PRESIZE_ID = "cb.presize"
#: the stamp of a store made at set-up, outside any request
WARM_REQUEST = -1


# ----------------------------------------------------------------- cells --
def load_spec(name: str, root: Path) -> dict:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its
    configuration, traffic and the metrics it reports."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"chipbench: unknown workload {name!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[name]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])

    def applies(m):
        return name in m.get("workloads", [name])
    return {
        "cell": cell,
        "config": json.loads((root / entry["file"]).read_text()),
        "traffic": json.loads(
            (BENCH_DIR / "traffic" / f"{cell['traffic']}.json").read_text()),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def load_reader(metric: str):
    path = BENCH_DIR / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{metric.replace('.', '_').replace('-', '_')}",
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------------ the seam ----
class TableBackend(JaxBackend):
    """``JaxBackend`` whose stores take their payloads from the set-up
    table, and which notes which plan kind delivered each copy."""

    def __init__(self, table: dict, **kw):
        super().__init__(**kw)
        self.table = table
        self.misses = 0
        self.sources_missing = 0
        #: data_id -> {endpoint: plan kind (or "put") that wrote it}
        self.delivered: dict[str, dict[str, str]] = {}
        #: data_id -> endpoints whose copy a spill freed since it landed
        self.spilled_from: dict[str, set] = {}

    def put_object(self, data_id, endpoint, payload=None, size_mb=None):
        if payload is None:
            n = nbytes_of(size_mb)
            payload = self.table.get(data_id)
            if payload is None or payload.nbytes != n:
                self.misses += 1
                payload = reference.payload(data_id, n)
        obj = super().put_object(data_id, endpoint, payload=payload)
        self.delivered.setdefault(data_id, {})[endpoint] = "put"
        return obj

    def execute(self, plan, *, on_progress=None):
        if plan.data_id and not plan.local \
                and plan.data_id not in self.store_for(plan.src):
            # the program would make the source up; that is a lost object
            self.sources_missing += 1
        rep = super().execute(plan, on_progress=on_progress)
        if rep is not None:
            self.delivered.setdefault(plan.data_id, {})[plan.dst] = plan.kind
        return rep

    def drop_object(self, data_id, endpoint=None):
        super().drop_object(data_id, endpoint)
        if endpoint is None:                  # consumed: every copy
            self.spilled_from.pop(data_id, None)
        else:                                 # a spill freed this copy
            self.spilled_from.setdefault(data_id, set()).add(endpoint)
        copies = self.delivered.get(data_id)
        if copies is not None:
            for ep in list(copies):
                if ep not in self.stores or data_id not in self.stores[ep]:
                    del copies[ep]
            if not copies:
                del self.delivered[data_id]


class RowLedger:
    """A backend that moves no bytes: it keeps, per endpoint, the slab
    rows a ``SlabStore`` would hold for the same calls, and their peak."""

    def __init__(self):
        self.reports: list = []
        self.stores: dict[str, SimpleNamespace] = {}
        self.peak_blocks: dict[str, int] = {}

    def store_for(self, endpoint: str) -> SimpleNamespace:
        return self.stores.setdefault(
            endpoint, SimpleNamespace(objects={}, used=0))

    def _hold(self, endpoint: str, data_id: str, nbytes: int):
        st = self.store_for(endpoint)
        self._drop(st, data_id)
        st.objects[data_id] = nbytes
        st.used += blocks_for(nbytes / MB)
        self.peak_blocks[endpoint] = max(
            self.peak_blocks.get(endpoint, 0), st.used)

    @staticmethod
    def _drop(st, data_id: str):
        nbytes = st.objects.pop(data_id, None)
        if nbytes is not None:
            st.used -= blocks_for(nbytes / MB)

    def put_object(self, data_id, endpoint, payload=None, size_mb=None):
        self._hold(endpoint, data_id, nbytes_of(size_mb))

    def execute(self, plan, *, on_progress=None):
        if not plan.data_id or plan.local:
            return None
        src = self.store_for(plan.src)
        if plan.data_id not in src.objects:
            self._hold(plan.src, plan.data_id, nbytes_of(plan.size_mb))
        self._hold(plan.dst, plan.data_id, src.objects[plan.data_id])
        return None

    def drop_object(self, data_id, endpoint=None):
        for ep, st in self.stores.items():
            if endpoint in (None, ep):
                self._drop(st, data_id)


def make_table(objects, workers: int) -> dict:
    """Every payload the cell can store, made once, in threads."""
    uniq = {o.data_id: o.nbytes for o in objects}
    with ThreadPoolExecutor(max_workers=workers) as ex:
        futs = {i: ex.submit(reference.payload, i, n)
                for i, n in uniq.items()}
        return {i: f.result() for i, f in futs.items()}


@jax.jit
def _read_rows(pool, idx):
    """``pool[idx]`` for ``READ_ROWS`` indices, one dynamic slice each
    (an XLA gather over the pool would stage a multiple of it)."""
    return jnp.stack([lax.dynamic_index_in_dim(pool, idx[k], keepdims=False)
                      for k in range(READ_ROWS)])


def read_copy(store, data_id: str) -> np.ndarray:
    """The bytes a slab store holds for ``data_id``."""
    obj = store.objects[data_id]
    rows = np.asarray(obj.rows, np.int32)
    if store.device:
        out = np.empty((len(rows), *store.slabs.shape[1:]), np.uint8)
        for s in range(0, len(rows), READ_ROWS):
            part = rows[s:s + READ_ROWS]
            idx = np.full(READ_ROWS, part[-1], np.int32)
            idx[:len(part)] = part
            out[s:s + len(part)] = np.asarray(
                _read_rows(store.slabs, idx))[:len(part)]
    else:
        out = store.slabs[rows]
    return out.reshape(-1)[:obj.nbytes]


# ------------------------------------------------------------- the cell ---
class Cell:
    """Set-up, window and check of one cell, in one process.  ``table``
    None drives a ``RowLedger`` in place of the real backend."""

    def __init__(self, spec: dict, seed: int, *, scale: float = 1.0,
                 backend_cls=TableBackend, table: dict | None = None):
        cfg = spec["config"]
        self.gen = DelayedConsumers(cfg, spec["traffic"], seed, scale=scale)
        self.hold = self.gen.hold
        self.table = table
        tube_cfg = dataclasses.replace(
            SYSTEMS[cfg["tube"]["base"]],
            store_cap_mb=cfg["tube"]["store_cap_mb"] * scale)
        if table is None:
            self.backend = RowLedger()
        else:
            self.backend = backend_cls(
                table, store_mb=cfg["backend"]["store_mb"] * scale,
                host_mb=cfg["backend"]["host_mb"] * scale)
        self.tube = FaaSTube(getattr(topology, cfg["topology"])(), tube_cfg,
                             backend=self.backend)
        self.r = 0
        self.recording = False
        self.calls: list[dict] = []
        self.finished = 0
        self.paused_s = 0.0
        self.compiles = 0
        self.snapshots: list[dict] = []
        self.store_peak_mb: dict[str, float] = {}
        self._fetched: dict[str, set] = {}

    # -- facade calls ------------------------------------------------------
    def _call(self, op: str, ep: str, nbytes: int, fn, *args, **kw):
        tube = self.tube
        done = []
        if op != "consume":
            kw["on_ready"] = lambda sim, t: done.append(t)
        first = len(self.backend.reports)
        where = "gpu" if is_device(ep) else "host"
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"cb.{op}.{where}"):
            fn(*args, tube.sim.now, **kw)
            with jax.profiler.TraceAnnotation("cb.sim_run"):
                tube.sim.run()
        dt = time.perf_counter() - t0
        if self.recording:
            peak = self.store_peak_mb
            on_chip = 0.0
            for name, st in self.backend.stores.items():
                peak[name] = max(peak.get(name, 0.0), st.used_mb)
                on_chip += st.used_mb if st.device else 0.0
            peak["devices"] = max(peak.get("devices", 0.0), on_chip)
            self.calls.append({
                "op": op, "ep": ep, "device": where == "gpu",
                "nbytes": nbytes, "wall_s": dt,
                "plans": [first, len(self.backend.reports)],
                "ok": op == "consume" or bool(done)})

    def _store(self, o, ep: str, request: int, **kw):
        if self.table is not None and o.data_id in self.table:
            entry = self.table[o.data_id]
            entry[:reference.STAMP_BYTES] = reference.stamp(request)
        self._call("store", ep, o.nbytes, self.tube.store, o.producer,
                   o.data_id, o.size_mb, ep, **kw)

    def _fetch(self, o, ep: str):
        self._fetched.setdefault(o.data_id, set()).add(ep)
        self._call("fetch", ep, o.nbytes, self.tube.fetch, o.consumer,
                   o.data_id, ep)

    def _consume(self, o, ep: str):
        self._call("consume", ep, 0, self.tube.consume, o.data_id, ep)

    def step(self, r: int):
        """Start request ``r``, then finish request ``r - hold``."""
        req = self.gen.request(r)
        for o in req.inputs:
            self._store(o, "host", r)
            self._fetch(o, req.a)
        for o in req.edges:
            self._store(o, req.a, r, consumer_pos=float(r + self.hold))
        if r >= self.hold:
            self.finish(self.gen.request(r - self.hold))

    def finish(self, req):
        for o in req.edges:
            self._fetch(o, "host" if o.dst_kind == "host" else req.b)
        for o in req.outputs:
            self._store(o, req.b, req.index)
            self._fetch(o, "host")
        self.snapshot(req)
        for o in req.objects:
            self._consume(o, req.a)
            self._fetched.pop(o.data_id, None)
        if self.recording:
            self.finished += 1

    # -- set-up -------------------------------------------------------------
    def presize(self, targets: dict[str, float]):
        """Grow each store once to ``targets[endpoint]`` MB by the
        store's own growth rule, so that the window never grows a pool
        (which compiles, and copies the pool).  The rows are allocated
        and freed through the store's own ``alloc`` and ``drop``: no
        payload moves."""
        be = self.backend
        for ep, mb in targets.items():
            st = be.store_for(ep)
            if st.pool.capacity_mb < mb:
                st.alloc(PRESIZE_ID, nbytes_of(mb - BLOCK_MB))
                st.drop(PRESIZE_ID)
        for ep in self.gen.devices:        # the check's reader, per pool
            st = be.store_for(ep)
            _read_rows(st.slabs, np.zeros(READ_ROWS, np.int32)) \
                .block_until_ready()

    def warm_up(self):
        """Compile every program the window can run, then drive the
        cell's own traffic until the first request has finished.

        The pools are at their final shape (``presize``), so a program
        is keyed by its slab count alone: each object size of the cell
        is stored once on a device (the whole-object scatter of a
        store), and one object of each size from 1 to a trigger batch
        moves host-to-device, device-to-device and device-to-host (the
        per-batch gathers and scatters of every plan kind, spills,
        reloads and prefetches included)."""
        a, b = self.gen.devices
        sizes = {}
        for o in self.gen.all_objects():
            sizes.setdefault(o.nbytes, o)
        for o in sizes.values():
            self._store(o, a, WARM_REQUEST)
            self._consume(o, a)
        for o in self.gen.shape_objects(BATCH_CHUNKS):
            self._store(o, "host", WARM_REQUEST)
            self._fetch(o, a)
            self._consume(o, a)
            self._store(o, a, WARM_REQUEST)
            self._fetch(o, b)
            self._fetch(o, "host")
            self._consume(o, a)
        self._fetched.clear()
        while self.r <= self.hold:
            self.step(self.r)
            self.r += 1

    # -- the window ---------------------------------------------------------
    def _on_compile(self, event, duration, **kw):
        if self.recording and event == COMPILE_EVENT:
            self.compiles += 1

    def window(self, seconds: float):
        be = self.backend
        self.calls, self.snapshots, self.store_peak_mb = [], [], {}
        self.finished, self.paused_s, self.compiles = 0, 0.0, 0
        self.first_report = len(be.reports)
        self.misses0 = be.misses
        self.sources0 = be.sources_missing
        stats0 = dict(self.tube.stats)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_compile)
        self.recording = True
        try:
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("cb.window"):
                while time.perf_counter() - t0 - self.paused_s < seconds:
                    with jax.profiler.TraceAnnotation("cb.step"):
                        self.step(self.r)
                    self.r += 1
            self.window_s = time.perf_counter() - t0 - self.paused_s
        finally:
            self.recording = False
            jax.monitoring.unregister_event_duration_listener(
                self._on_compile)
        self.spills = self.tube.stats["migrations"] - stats0["migrations"]

    def window_counts(self) -> dict:
        """What the last window counted that decides ``correct``."""
        be = self.backend
        return {"table_misses": be.misses - self.misses0,
                "sources_missing": be.sources_missing - self.sources0,
                "window_compiles": self.compiles}

    def snapshot(self, req):
        """Read back every copy of a finished request's objects before
        they are consumed (the clock is paused meanwhile): a digest of
        each copy, the plan kind that wrote it, and where the index and
        the fetches say it should be."""
        if not self.recording:
            return
        t0 = time.perf_counter()
        be = self.backend
        with jax.profiler.TraceAnnotation("cb.pause"):
            for o in req.objects:
                rec = self.tube.index.global_table.get(o.data_id)
                copies = {}
                for ep in be.where(o.data_id):
                    data = read_copy(be.stores[ep], o.data_id)
                    copies[ep] = {
                        "kind": be.delivered.get(o.data_id, {}).get(ep, "?"),
                        "nbytes": int(data.nbytes),
                        "digest": reference.digest(data)}
                self.snapshots.append({
                    "data_id": o.data_id, "nbytes": o.nbytes,
                    "request": req.index,
                    "index": rec.device if rec is not None else None,
                    "fetched": sorted(self._fetched.get(o.data_id, ())),
                    "spilled_from": sorted(
                        be.spilled_from.get(o.data_id, ())),
                    "copies": copies})
        self.paused_s += time.perf_counter() - t0

    # -- what the window produced -------------------------------------------
    def record(self) -> dict:
        reps = self.backend.reports[self.first_report:]
        return {
            "calls": self.calls,
            "reports": [{"kind": r.kind, "size_mb": r.size_mb,
                         "wall_ms": r.wall_ms, "n_chunks": r.n_chunks,
                         "hop_trace": list(r.hop_trace)} for r in reps],
            "first_report": self.first_report,
            "batch_chunks": self.backend.batch_chunks,
            "window_s": self.window_s,
            "finished": self.finished,
            "spills": self.spills,
            "plan_counts": {k: sum(r.kind == k for r in reps)
                            for k in sorted({r.kind for r in reps})},
            "store_peak_mb": self.store_peak_mb,
            "pool_mb": {name: st.pool.capacity_mb
                        for name, st in self.backend.stores.items()},
        }


def check(snapshots: list, workers: int) -> dict:
    """Compare every copy read back with the reference: its bytes, that
    each fetch's destination holds one (or a spill has since moved it
    to the host), and that the index points at one.  Returns the counts,
    and the copies checked by plan kind."""
    want: dict[tuple, set] = {}
    for s in snapshots:
        want.setdefault((s["data_id"], s["nbytes"]), set()).add(s["request"])
    with ThreadPoolExecutor(max_workers=workers) as ex:
        futs = {k: ex.submit(reference.stamped_digests, *k, reqs)
                for k, reqs in want.items()}
        ref = {k: f.result() for k, f in futs.items()}
    wrong = missing = index_wrong = checked = 0
    by_kind: dict[str, int] = {}
    for s in snapshots:
        expected = ref[s["data_id"], s["nbytes"]][s["request"]]
        for ep, c in s["copies"].items():
            checked += 1
            by_kind[c["kind"]] = by_kind.get(c["kind"], 0) + 1
            if c["nbytes"] != s["nbytes"] or c["digest"] != expected:
                wrong += 1
        missing += sum(ep not in s["copies"] and ep not in s["spilled_from"]
                       for ep in s["fetched"])
        if s["index"] not in s["copies"]:
            index_wrong += 1
    return {"copies_checked": checked, "copies_wrong": wrong,
            "copies_missing": missing, "index_wrong": index_wrong,
            "by_kind": by_kind}


def limits(counts: dict, window: dict) -> dict:
    """Each number that decides ``correct``, with its limit."""
    return {
        "copies_wrong": {"value": counts["copies_wrong"], "max": 0},
        "copies_missing": {"value": counts["copies_missing"], "max": 0},
        "index_wrong": {"value": counts["index_wrong"], "max": 0},
        "copies_checked": {"value": counts["copies_checked"], "min": 1},
        "table_misses": {"value": window["table_misses"], "max": 0},
        "sources_missing": {"value": window["sources_missing"], "max": 0},
        "window_compiles": {"value": window["window_compiles"], "max": 0},
    }


def passes(checks: dict) -> bool:
    return all(c["value"] <= c.get("max", c["value"])
               and c["value"] >= c.get("min", c["value"])
               for c in checks.values())


def memory_peak_bytes() -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def pool_targets(spec: dict, seed: int, scale: float = 1.0) -> dict:
    """MB each store of the cell grows to at set-up: the first doubling
    of ``SlabStore.START_MB`` that holds the most slab rows the store
    keeps over the warm-up and two periods of the cell's traffic (a
    ``RowLedger`` run of the same calls), and never above the store's
    capacity."""
    dry = Cell(spec, seed, scale=scale)
    dry.warm_up()
    while dry.r < dry.hold + 1 + 2 * dry.gen.period:
        dry.step(dry.r)
        dry.r += 1
    be = spec["config"]["backend"]
    out = {}
    for ep, blocks in sorted(dry.backend.peak_blocks.items()):
        cap = (be["store_mb"] if is_device(ep) else be["host_mb"]) * scale
        mb = SlabStore.START_MB
        while mb < blocks * BLOCK_MB and mb < cap:
            mb *= 2
        out[ep] = min(mb, cap)
    return out


def build(spec: dict, seed: int, *, scale: float = 1.0,
          backend_cls=TableBackend, workers: int = 8) -> Cell:
    """The cell of ``seed``, set up: payload table made, stores grown to
    their targets, every program compiled, the first request
    finished."""
    targets = pool_targets(spec, seed, scale)
    gen = DelayedConsumers(spec["config"], spec["traffic"], seed,
                           scale=scale)
    table = make_table(gen.all_objects() + gen.shape_objects(BATCH_CHUNKS),
                       workers)
    cell = Cell(spec, seed, scale=scale, backend_cls=backend_cls,
                table=table)
    cell.presize(targets)
    cell.warm_up()
    return cell


def run_cell(spec: dict, seed: int, seconds: float, traced: bool, *,
             t_start: float, peaks: dict | None, scale: float = 1.0,
             backend_cls=TableBackend, workers: int = 8,
             log=print) -> dict:
    """Set up, measure and check one cell; returns the result object,
    with ``memory_peak_bytes`` (and, traced, ``busy_s`` and ``window_s``)
    at its top level, where ``run`` moves them under ``device``."""
    cell = build(spec, seed, scale=scale, backend_cls=backend_cls,
                 workers=workers)
    setup_s = time.perf_counter() - t_start
    red = None
    if traced:
        tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
        try:
            with jax.profiler.trace(tdir):
                cell.window(seconds)
            red = trace.reduce(trace.extract(trace.find_xplane(tdir)))
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
    else:
        cell.window(seconds)
    mem = memory_peak_bytes()
    rec = cell.record()
    rec.update(setup_s=setup_s, trace=red, peaks=peaks)
    window = cell.window_counts()
    log(f"chipbench: window {rec['window_s']:.3f}s, "
        f"{sum(c['op'] == 'fetch' for c in rec['calls'])} fetch calls, "
        f"{sum(c['op'] == 'store' for c in rec['calls'])} store calls, "
        f"{rec['finished']} requests finished, {rec['spills']} spills, "
        f"{sum(rec['plan_counts'].get(k, 0) for k in ('reload', 'prefetch'))}"
        f" reloads and prefetches, compilations in window "
        f"{window['window_compiles']}, payload-table misses "
        f"{window['table_misses']}, sources missing "
        f"{window['sources_missing']}")
    log("chipbench: plans in window "
        + json.dumps(rec["plan_counts"], sort_keys=True)
        + "; store live peak MB " + json.dumps(rec["store_peak_mb"],
                                               sort_keys=True)
        + "; store pool MB " + json.dumps(rec["pool_mb"], sort_keys=True)
        + f"; memory_peak_bytes {mem}; read-back pause "
        f"{cell.paused_s:.3f}s")
    snapshots = cell.snapshots
    del cell                  # free the program's state before the check
    gc.collect()
    counts = check(snapshots, workers)
    checks = limits(counts, window)
    log(f"chipbench: copies checked by plan kind {counts['by_kind']}")
    metrics = {}
    for m in spec["per_layer" if traced else "end_to_end"]:
        val = load_reader(m["name"])(rec)
        if val is not None:
            metrics[m["name"]] = {"value": val, "unit": m["unit"]}
    calls = [c for c in rec["calls"] if c["op"] != "consume"]
    out = {"correct": passes(checks), "attempted": len(calls),
           "failed": sum(not c["ok"] for c in calls), "metrics": metrics,
           "memory_peak_bytes": mem,
           "store_live_peak_bytes": int(
               rec["store_peak_mb"].get("devices", 0.0) * MB),
           "store_pool_bytes": int(sum(
               mb for ep, mb in rec["pool_mb"].items() if is_device(ep))
               * MB)}
    if red is not None:
        out["busy_s"] = red["busy_s"]
        out["window_s"] = red["window_s"]
        out["breakdown"] = trace.breakdown(red)
    out["checks"] = checks
    return out


def default_workers() -> int:
    return max(1, min(8, (os.cpu_count() or 2) - 2))
