"""GB (1e9 bytes) per second of the plans staged through the host ring:
h2g, g2h, spill, reload and prefetch, over their own wall time."""
MB = 2 ** 20
KINDS = ("h2g", "g2h", "spill", "reload", "prefetch")


def read(rec):
    reps = [r for r in rec["reports"] if r["kind"] in KINDS]
    ms = sum(r["wall_ms"] for r in reps)
    if ms <= 0:
        return None
    return sum(r["size_mb"] for r in reps) * MB / 1e9 / (ms / 1e3)
