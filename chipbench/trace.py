"""Reduction of a profiler trace to device busy time, op times and the
host spans that idle gaps fall in.

``extract`` reads the ``.xplane.pb`` the JAX profiler wrote and keeps
only what the reduction needs, as plain lists: every event of the
device planes, and the host spans this benchmark annotates (names that
start with ``cb.``).  ``reduce`` works on that and nothing else, so it
can be checked on a small recorded trace.

The measured window is the ``cb.window`` span less its ``cb.pause``
spans (where the check reads copies back).  Device busy time is the
union of the op intervals of each device plane's ``XLA Ops`` and
``Async XLA Ops`` lines inside that window; program time is read from
its ``XLA Modules`` line.
"""
from __future__ import annotations

import glob
import os
import re

SPAN_PREFIX = "cb."
#: device lines whose events are operations (busy time); XLA Ops nest
#: (a while op holds its body's ops), so only their union is summed
OPS_LINES = ("XLA Ops", "Async XLA Ops")
#: one event per program run, named ``<jit name>(<fingerprint>)``
MODULES_LINE = "XLA Modules"
_SUFFIX = re.compile(r"\(\d+\)$")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def extract(xplane_path: str) -> dict:
    """``{"device": {plane: [[line, name, start_ns, dur_ns], ...]},
    "host": [[name, start_ns, dur_ns], ...]}``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    device: dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            evs = device.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name not in OPS_LINES + (MODULES_LINE,):
                    continue
                for e in line.events:
                    evs.append([line.name, e.name, e.start_ns,
                                e.duration_ns])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        host.append([e.name, e.start_ns, e.duration_ns])
    return {"device": {k: v for k, v in device.items() if v},
            "host": host}


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, windows):
    """Parts of sorted disjoint ``intervals`` inside sorted disjoint
    ``windows``."""
    out, j = [], 0
    for s, e in intervals:
        while j < len(windows) and windows[j][1] <= s:
            j += 1
        k = j
        while k < len(windows) and windows[k][0] < e:
            a, b = max(s, windows[k][0]), min(e, windows[k][1])
            if b > a:
                out.append([a, b])
            k += 1
    return out


def _length(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def active_windows(host) -> list:
    """The ``cb.window`` spans less the ``cb.pause`` spans, in ns."""
    wins = _union([[s, s + d] for n, s, d in host if n == "cb.window"])
    pauses = _union([[s, s + d] for n, s, d in host if n == "cb.pause"])
    out = []
    for s, e in wins:
        cur = s
        for ps, pe in pauses:
            if pe <= cur or ps >= e:
                continue
            if ps > cur:
                out.append([cur, ps])
            cur = max(cur, pe)
        if cur < e:
            out.append([cur, e])
    return out


def _attribute(spans, gaps) -> dict:
    """Idle ns by the innermost host span covering each gap's midpoint.
    The spans nest (one thread's annotations), so the innermost covering
    span is the latest-started one still open: a stack sweep."""
    idle: dict[str, float] = {}
    spans = sorted(spans, key=lambda h: h[1])
    stack, i = [], 0
    for gs, ge in sorted(gaps):
        t = (gs + ge) / 2
        while i < len(spans) and spans[i][1] <= t:
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][1] + stack[-1][2] <= t:
            stack.pop()
        name = stack[-1][0] if stack else "(no span)"
        idle[name] = idle.get(name, 0.0) + (ge - gs)
    return idle


def reduce(ev: dict) -> dict | None:
    """Busy and window seconds, seconds per program, and idle seconds by
    the host span they fall in; None when the trace holds no device op
    inside the window."""
    windows = active_windows(ev["host"])
    window_ns = _length(windows)
    if not windows or not ev["device"]:
        return None
    busy_ns, module_ns, idle = 0.0, {}, {}
    spans = [h for h in ev["host"]
             if h[0] not in ("cb.window", "cb.pause")]
    for evs in ev["device"].values():
        for line, n, s, d in evs:
            if line == MODULES_LINE:
                part = _length(_clip([[s, s + d]], windows))
                if part > 0:
                    name = _SUFFIX.sub("", n)
                    module_ns[name] = module_ns.get(name, 0.0) + part
        busy = _clip(_union([[s, s + d] for line, n, s, d in evs
                             if line in OPS_LINES]), windows)
        busy_ns += _length(busy)
        # idle gaps: the window less the busy intervals
        gaps = []
        for ws, we in windows:
            cur = ws
            for bs, be in busy:
                if be <= ws or bs >= we:
                    continue
                if bs > cur:
                    gaps.append([cur, bs])
                cur = max(cur, be)
            if cur < we:
                gaps.append([cur, we])
        for k, v in _attribute(spans, gaps).items():
            idle[k] = idle.get(k, 0.0) + v
    n_dev = len(ev["device"])
    if busy_ns <= 0:
        return None
    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_ns / n_dev / 1e9,
        "n_devices": n_dev,
        "module_s": {k: v / 1e9 for k, v in module_ns.items()},
        "idle_s": {k: v / n_dev / 1e9 for k, v in idle.items()},
    }


def breakdown(red: dict, top: int = 10) -> dict:
    """The contract's ``breakdown``: the device programs that took most
    time, and the idle time by what the host was doing."""
    def most(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                [:top]]
    return {"device_ops": most(red["module_s"]),
            "idle_gaps": most(red["idle_s"])}
