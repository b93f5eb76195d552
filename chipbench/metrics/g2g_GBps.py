"""GB (1e9 bytes) per second of device-to-device plans, over their own
wall time."""
MB = 2 ** 20


def read(rec):
    reps = [r for r in rec["reports"] if r["kind"] == "g2g"]
    ms = sum(r["wall_ms"] for r in reps)
    if ms <= 0:
        return None
    return sum(r["size_mb"] for r in reps) * MB / 1e9 / (ms / 1e3)
