"""The control and the planted faults, on the chip at a cell's own size.

    python3 -m chipbench.control --workload <cell> --seeds 1,2,3 \\
        --seconds <s> --faults none,flip,half,skip,stale

For each seed: one set-up, then one window per fault (``none`` is the
sound program), each checked as a run checks its window.  Prints one
JSON line per window with every number that decides ``correct``.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

from chipbench.run import ROOT, compile_cache


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="chipbench.control")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--faults", default="none,flip,half,skip,stale")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from chipbench import harness
    from chipbench.faults import FaultyBackend
    if jax.devices()[0].platform != "tpu":
        sys.exit("chipbench.control: no TPU")
    compile_cache(ROOT)
    spec = harness.load_spec(args.workload, ROOT)
    workers = harness.default_workers()
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = harness.build(spec, seed, backend_cls=FaultyBackend,
                             workers=workers)
        for fault in args.faults.split(","):
            cell.backend.fault = None if fault == "none" else fault
            t0 = time.perf_counter()
            cell.window(args.seconds)
            cell.backend.fault = None
            counts = harness.check(cell.snapshots, workers)
            checks = harness.limits(counts, cell.window_counts())
            print(json.dumps({
                "workload": args.workload, "seed": seed, "fault": fault,
                "correct": harness.passes(checks),
                "readings": {k: c["value"] for k, c in checks.items()},
                "by_kind": counts["by_kind"],
                "seconds": time.perf_counter() - t0}), flush=True)
        del cell
        gc.collect()          # the facade holds cycles: free its pools
    return 0


if __name__ == "__main__":
    sys.exit(main())
