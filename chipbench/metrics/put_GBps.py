"""GB (1e9 bytes) written per second by store calls, the time of the
plans they ran (spills) left out: the slab store's write path."""


def read(rec):
    reps = rec["reports"]
    first = rec["first_report"]
    nbytes = secs = 0.0
    for c in rec["calls"]:
        if c["op"] != "store":
            continue
        a, b = c["plans"]
        nbytes += c["nbytes"]
        secs += c["wall_s"] - sum(
            r["wall_ms"] for r in reps[a - first:b - first]) / 1e3
    return nbytes / secs / 1e9 if secs > 0 else None
