"""Run one benchmark cell once, on the chip this process finds.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

It fails at once, printing no result, where JAX finds no TPU or fewer
chips than the cell asks for.  Standard output: the device, the compile
cache, the window's counts, and as its last line one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and last ``checks``: each number that
decides ``correct`` with its limit).  The same checks are the last lines
of standard error.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def compile_cache(root: Path) -> str:
    """``JAX_COMPILATION_CACHE_DIR`` where it is set (JAX reads it
    itself), else ``<checkout>/.jax_cache``: a fixed path inside the
    checkout, so that only a cell's first run there compiles."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    # the copy programs compile in well under the default one second;
    # cache them all, so set-up is the same work in every run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def load_peaks(kind: str) -> dict:
    table = json.loads((BENCH_DIR / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise SystemExit(f"chipbench: device kind {kind!r} is not in "
                         f"peaks.json ({sorted(table['devices'])})")
    return table["devices"][kind]


def parse(argv):
    ap = argparse.ArgumentParser(prog="chipbench.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    sys.path.insert(0, str(ROOT / "src"))
    from chipbench import harness
    spec = harness.load_spec(args.workload, ROOT)
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        sys.exit(f"chipbench: no TPU: JAX found {len(devs)} "
                 f"{d.platform!r} device(s); this benchmark runs on a TPU")
    chips = spec["cell"]["chips"]
    if len(devs) < chips:
        sys.exit(f"chipbench: {args.workload} needs {chips} chips, "
                 f"JAX found {len(devs)}")
    peaks = load_peaks(d.device_kind)
    print(f"chipbench: platform={d.platform} device_kind={d.device_kind!r} "
          f"count={len(devs)}", flush=True)
    print(f"chipbench: compile cache {compile_cache(ROOT)}", flush=True)
    out = harness.run_cell(spec, args.seed, args.seconds, bool(args.trace),
                           t_start=T_START, peaks=peaks,
                           workers=harness.default_workers(),
                           log=lambda s: print(s, flush=True))
    checks = out.pop("checks")
    out["device"] = {"platform": d.platform, "kind": d.device_kind,
                     "count": len(devs),
                     "memory_peak_bytes": out.pop("memory_peak_bytes")}
    for k in ("busy_s", "window_s", "store_live_peak_bytes",
              "store_pool_bytes"):
        if k in out:
            out["device"][k] = out.pop(k)
    out["checks"] = checks
    for name, c in checks.items():
        lim = f"<= {c['max']}" if "max" in c else f">= {c['min']}"
        print(f"check {name} {c['value']} limit {lim}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
