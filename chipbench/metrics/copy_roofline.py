"""Share of the HBM roofline reached by the slab gather and scatter
programs: the bytes their shapes move (``copybytes``), at the chip's
peak HBM bandwidth, over their device time in the trace."""
from chipbench import copybytes

#: device programs (XLA module names) of the slab copies
PROGRAMS = ("gather", "scatter")


def read(rec):
    red, peaks = rec["trace"], rec["peaks"]
    if red is None or peaks is None:
        return None
    dev_s = sum(s for name, s in red["module_s"].items()
                if any(p in name for p in PROGRAMS))
    if dev_s <= 0:
        return None
    progs = []
    for r in rec["reports"]:
        p = copybytes.plan_programs(r["hop_trace"], r["n_chunks"],
                                    rec["batch_chunks"])
        if p is None:
            return None
        progs += p
    for c in rec["calls"]:
        if c["op"] == "store" and c["device"]:
            progs += copybytes.put_programs(c["nbytes"])
    nbytes = sum(copybytes.copy_bytes(m) for _, m in progs)
    return 100.0 * nbytes / (peaks["hbm_GBps"] * 1e9) / dev_s
