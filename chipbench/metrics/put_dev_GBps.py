"""GB (1e9 bytes) written per second into device slab stores: the
program's ``put.dev`` byte counter over the time of its ``ft.put.dev``
spans (pad, upload, scatter and wait of one object write)."""
NAME = "ft.put.dev"


def read(rec):
    sp = rec.get("spans") or {}
    nbytes = (rec.get("counters") or {}).get("put.dev", 0)
    secs = sp[NAME]["incl_s"] if NAME in sp else 0.0
    return nbytes / 1e9 / secs if nbytes and secs > 0 else None
