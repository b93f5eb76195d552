"""The program's own spans and byte counters in a traced window.

The data plane annotates its work with ``ft.`` host spans
(``repro.core.spans``) and counts the bytes it copies in
``JaxBackend.counters``.  ``chipbench.trace`` keeps only the benchmark's
``cb.`` spans; this module reads the program's beside them:

- ``extract`` is ``trace.extract`` plus ``"program"``: every ``ft.``
  host span as ``[name, start_ns, dur_ns, thread]``;
- ``spans`` gives, inside the measured window (``trace.windows``),
  each ``ft.`` name's inclusive seconds, self seconds (less the ``ft.``
  spans nested in it on the same thread) and count;
- ``reduce`` is ``trace.reduce`` with each idle gap charged to the
  innermost span of either family.

A traced ``chipbench.run`` reads them into its record
(``harness.run_cell``), where the readers under ``metrics/`` find them.
"""
from __future__ import annotations

from bisect import bisect_right

from chipbench import trace

#: the program's span prefix (``repro.core.spans.PREFIX``)
PREFIX = "ft."


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
    its seconds inside the measured window, those seconds less the
    ``ft.`` spans nested in it on the same thread, and how many start
    inside it."""
    windows = trace.windows(ev)
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
