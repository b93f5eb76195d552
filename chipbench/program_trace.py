"""The program's own spans and byte counters in a traced window.

The data plane annotates its work with ``ft.`` host spans
(``repro.core.spans``) and counts the bytes it copies in
``JaxBackend.counters``.  ``chipbench.trace`` keeps only the benchmark's
``cb.`` spans; this module reads the program's beside them:

- ``extract`` is ``trace.extract`` plus ``"program"``: every ``ft.``
  host span as ``[name, start_ns, dur_ns, thread]``;
- ``spans`` gives, inside the active windows, each ``ft.`` name's
  inclusive seconds, self seconds (less the ``ft.`` spans nested in it
  on the same thread) and count; it needs no device plane;
- ``reduce`` is ``trace.reduce`` with each idle gap charged to the
  innermost span of either family.

Run one cell traced, with the program's spans read into the record and
the readers of ``PER_LAYER`` applied to it (copies are not read back,
so there is no ``correct``: ``chipbench.run`` checks them):

    python3 -m chipbench.program_trace --workload <cell> --seed <n> \\
        --seconds <s>

Its last line is one JSON object: ``metrics`` (the cell's end-to-end
metrics and every per-layer metric of the cell and of ``PER_LAYER``),
``breakdown`` (by ``reduce``), ``spans`` and ``counters``.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from bisect import bisect_right

from chipbench import trace

T_START = time.perf_counter()
#: the program's span prefix (``repro.core.spans.PREFIX``)
PREFIX = "ft."
#: readers of the program's spans and counters (``metrics/<name>.py``)
PER_LAYER = ("facade_self_ms_per_call", "put_dev_GBps", "hostcopy_GBps",
             "link_wait_share")


def extract(xplane_path: str) -> dict:
    """``trace.extract`` with ``"program"``: ``[[name, start_ns, dur_ns,
    thread], ...]`` of the ``ft.`` host spans."""
    from jax.profiler import ProfileData
    ev = trace.extract(xplane_path)
    ev["program"] = [
        [e.name, e.start_ns, e.duration_ns, line.name]
        for plane in ProfileData.from_file(xplane_path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events
        if e.name.startswith(PREFIX)]
    return ev


def reduce(ev: dict) -> dict | None:
    """``trace.reduce`` with the program's spans among the host spans.
    Of two spans that start together the longer is the outer, so it
    goes first (``trace`` sorts by start alone, and keeps ties' order)."""
    host = ev["host"] + [h[:3] for h in ev.get("program", ())]
    return trace.reduce(dict(ev, host=sorted(host,
                                             key=lambda h: (h[1], -h[2]))))


def _overlap(s, e, windows, starts) -> float:
    """ns of ``[s, e)`` inside sorted disjoint ``windows``."""
    out, k = 0.0, max(bisect_right(starts, s) - 1, 0)
    while k < len(windows) and windows[k][0] < e:
        out += max(0.0, min(e, windows[k][1]) - max(s, windows[k][0]))
        k += 1
    return out


def spans(ev: dict) -> dict:
    """``{name: {"incl_s", "self_s", "count"}}`` of every ``ft.`` span:
    its seconds inside the active windows, those seconds less the
    ``ft.`` spans nested in it on the same thread, and how many start
    inside them."""
    windows = trace.active_windows(ev["host"])
    starts = [w[0] for w in windows]
    threads: dict = {}
    for h in ev.get("program", ()):
        threads.setdefault(h[3], []).append(h)
    incl: dict[str, float] = {}
    own: dict[str, float] = {}
    count: dict[str, int] = {}
    for hs in threads.values():
        stack = []                            # (end_ns, name), open spans
        for name, s, d, _ in sorted(hs, key=lambda h: (h[1], -h[2])):
            e = s + d
            while stack and stack[-1][0] <= s:
                stack.pop()
            ns = _overlap(s, e, windows, starts)
            incl[name] = incl.get(name, 0.0) + ns
            own[name] = own.get(name, 0.0) + ns
            if stack:
                own[stack[-1][1]] -= ns
            k = bisect_right(starts, s) - 1
            count[name] = count.get(name, 0) + (k >= 0
                                                and s < windows[k][1])
            stack.append((e, name))
    return {n: {"incl_s": incl[n] / 1e9, "self_s": own[n] / 1e9,
                "count": count[n]} for n in sorted(incl)}


def counter_delta(before: dict, backend) -> dict:
    """The bytes ``backend.counters`` gained since ``before``; ``{}``
    for a program that keeps no counters."""
    return {k: v - before.get(k, 0)
            for k, v in getattr(backend, "counters", {}).items()}


def run(spec: dict, seed: int, seconds: float, *, peaks: dict | None,
        t_start: float, scale: float = 1.0, workers: int = 8,
        log=print) -> dict:
    """Set up the cell, trace one window, and read it."""
    import jax
    from chipbench import harness
    cell = harness.build(spec, seed, scale=scale, workers=workers)
    cell.snapshot = lambda req: None          # no read-back pauses
    setup_s = time.perf_counter() - t_start
    before = dict(getattr(cell.backend, "counters", {}))
    tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
    try:
        with jax.profiler.trace(tdir):
            cell.window(seconds)
        ev = extract(trace.find_xplane(tdir))
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    rec = cell.record()
    red = reduce(ev)
    rec.update(setup_s=setup_s, trace=red, peaks=peaks, spans=spans(ev),
               counters=counter_delta(before, cell.backend))
    log(f"chipbench: window {rec['window_s']:.3f}s, "
        f"{len(ev['program'])} program spans")
    names = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    names += [n for n in PER_LAYER if n not in names]
    metrics = {}
    for name in names:
        val = harness.load_reader(name)(rec)
        if val is not None:
            metrics[name] = val
    idle = red["idle_s"] if red else {}
    total = sum(idle.values())
    return {"metrics": metrics,
            "breakdown": trace.breakdown(red, top=20) if red else None,
            "idle_ft_share": (sum(v for k, v in idle.items()
                                  if k.startswith(PREFIX)) / total
                              if total else None),
            "window_s": red["window_s"] if red else None,
            "busy_s": red["busy_s"] if red else None,
            "spans": rec["spans"], "counters": rec["counters"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="chipbench.program_trace")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from chipbench import run as bench
    sys.path.insert(0, str(bench.ROOT / "src"))
    from chipbench import harness
    spec = harness.load_spec(args.workload, bench.ROOT)
    import jax
    d = jax.devices()[0]
    if d.platform != "tpu":
        sys.exit(f"chipbench: no TPU: JAX found {d.platform!r}")
    print(f"chipbench: compile cache {bench.compile_cache(bench.ROOT)}",
          flush=True)
    out = run(spec, args.seed, args.seconds,
              peaks=bench.load_peaks(d.device_kind), t_start=T_START,
              workers=harness.default_workers(),
              log=lambda s: print(s, flush=True))
    out["device"] = d.device_kind
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
