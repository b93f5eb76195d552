"""95th percentile of every fetch call in the window, from the call
until its bytes are at the destination (the fetch and ``sim.run``),
reloads included."""
import numpy as np


def read(rec):
    ms = [c["wall_s"] * 1e3 for c in rec["calls"] if c["op"] == "fetch"]
    return float(np.percentile(ms, 95)) if ms else None
