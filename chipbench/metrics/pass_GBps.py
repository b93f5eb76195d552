"""Payload GB (1e9 bytes) that completed fetch calls delivered, over the
window's wall time."""


def read(rec):
    done = sum(c["nbytes"] for c in rec["calls"]
               if c["op"] == "fetch" and c["ok"])
    return done / rec["window_s"] / 1e9
