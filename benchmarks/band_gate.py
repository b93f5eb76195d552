"""Band-regression gate: diff a regenerated benchmark report against the
committed baseline and FAIL on drift (CI used to only upload artifacts,
so a silently shifted band was invisible until someone read the JSON).

    python -m benchmarks.band_gate BASELINE FRESH [--float-tol PCT]
    python -m benchmarks.band_gate --baseline-dir DIR FRESH... [--float-tol PCT]

The second form gates N regenerated reports in one invocation: each
FRESH file is diffed against ``DIR/<basename>``, every file is checked
even after the first drift (the full per-field old -> new diff prints
for each), and the exit code aggregates across all of them.  A FRESH
file with no baseline in DIR fails the gate — that is exactly the
"new BENCH file silently left out of the band diff" hole this closes.

The simulator is deterministic (seeded arrival traces, fixed-order event
heap), so everything except wall-clock measurements must reproduce
bit-for-bit on any machine:

  * ints (event counts, migrations, reloads, misses) compare exactly;
  * floats (p99s, MB, % cuts) compare within --float-tol percent
    (default 1%) to absorb rounding-at-print differences;
  * wall-clock derived fields (``wall_s``, ``events_per_sec``,
    ``coalesce_speedup_x``, ...) are machine-dependent and skipped.

Keys present only on one side are reported but do not fail the gate:
CI's smoke runs regenerate a *subset* of the committed full sweep (e.g.
only the tightest memstress cap), and a new code version may add fields
the old baseline lacks.  Only a *changed value* is a regression.
"""
from __future__ import annotations

import json
import os
import sys

#: machine-dependent measurements — never compared
SKIP_KEYS = {
    "wall_s", "wall_clock", "total_wall_s", "events_per_sec",
    "chunk_exact_events_per_sec", "coalesce_speedup_x",
    "contended_speedup_x",
    # the real-bytes calibration (BENCH_calibrate): fitted bandwidths
    # and error magnitudes move with the machine; the deterministic
    # shape (boundaries, the *_ok flags) stays gated
    "bw_gbps", "lat_ms", "slope_ms_per_mb", "intercept_ms",
    "holdout_err_pct", "median_err_pct",
    "sim_ms", "measured_ms", "sim_vs_real_x",
}


def _diff(base, fresh, path, drifts, only, float_tol):
    if isinstance(base, dict) and isinstance(fresh, dict):
        for k in base:
            p = f"{path}.{k}" if path else str(k)
            if k in SKIP_KEYS:
                continue
            if k not in fresh:
                only.append(("baseline-only", p))
                continue
            _diff(base[k], fresh[k], p, drifts, only, float_tol)
        for k in fresh:
            if k not in base and k not in SKIP_KEYS:
                only.append(("fresh-only", f"{path}.{k}" if path else str(k)))
        return
    if isinstance(base, bool) or isinstance(fresh, bool) \
            or not isinstance(base, (int, float)) \
            or not isinstance(fresh, (int, float)):
        if base != fresh:
            drifts.append((path, base, fresh))
        return
    if isinstance(base, int) and isinstance(fresh, int):
        if base != fresh:
            drifts.append((path, base, fresh))
        return
    tol = max(abs(base) * float_tol / 100.0, 0.11)   # one rounding ulp
    if abs(base - fresh) > tol:
        drifts.append((path, base, fresh))


def gate(baseline_path: str, fresh_path: str,
         float_tol: float = 1.0) -> int:
    with open(baseline_path) as f:
        base = json.load(f)
    with open(fresh_path) as f:
        fresh = json.load(f)
    drifts: list[tuple] = []
    only: list[tuple] = []
    _diff(base, fresh, "", drifts, only, float_tol)
    for side, p in only:
        print(f"band_gate,note,{side},{p},")
    for p, b, fr in drifts:
        print(f"band_gate,DRIFT,{p},{b} -> {fr},")
    n = len(drifts)
    verdict = "FAIL" if n else "ok"
    print(f"band_gate,{verdict},{baseline_path} vs {fresh_path},"
          f"{n} drifted / {len(only)} one-sided,")
    return 1 if n else 0


def gate_dir(baseline_dir: str, fresh_paths: list[str],
             float_tol: float = 1.0) -> int:
    """Gate every FRESH report against ``baseline_dir/<basename>``;
    never stops at the first drifted file."""
    rc = 0
    for fresh in fresh_paths:
        baseline = os.path.join(baseline_dir, os.path.basename(fresh))
        if not os.path.exists(baseline):
            print(f"band_gate,FAIL,{fresh},no baseline in {baseline_dir},")
            rc = 1
            continue
        rc |= gate(baseline, fresh, float_tol)
    n = len(fresh_paths)
    print(f"band_gate,{'FAIL' if rc else 'ok'},{baseline_dir},"
          f"{n} reports gated,")
    return rc


def main(argv=None) -> int:
    args = list(argv if argv is not None else sys.argv[1:])
    float_tol = 1.0
    if "--float-tol" in args:
        i = args.index("--float-tol")
        float_tol = float(args[i + 1])
        del args[i:i + 2]
    if "--baseline-dir" in args:
        i = args.index("--baseline-dir")
        base_dir = args[i + 1]
        del args[i:i + 2]
        if not args:
            print(__doc__, file=sys.stderr)
            return 2
        return gate_dir(base_dir, args, float_tol)
    if len(args) != 2:
        print(__doc__, file=sys.stderr)
        return 2
    return gate(args[0], args[1], float_tol)


if __name__ == "__main__":
    sys.exit(main())
