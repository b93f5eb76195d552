"""95th percentile of every store call in the window: admission, any
spills it forced, and the write into the slab store."""
import numpy as np


def read(rec):
    ms = [c["wall_s"] * 1e3 for c in rec["calls"] if c["op"] == "store"]
    return float(np.percentile(ms, 95)) if ms else None
