"""GB (1e9 bytes) per second of the program's host-memory copies: the
padded copy of a put, rows into the staging ring or a staged batch, and
rows of host destination stores (counters ``pad``, ``stage`` and
``write``), over the time of the spans that make them (``ft.*.pad``,
``ft.*.stage``, ``ft.*.write`` and the network hop's ``ft.net.copy``)."""
COUNTERS = ("pad", "stage", "write")
SUFFIXES = (".pad", ".stage", ".write")
NET = "ft.net.copy"


def read(rec):
    sp = rec.get("spans") or {}
    cnt = rec.get("counters") or {}
    nbytes = sum(cnt.get(k, 0) for k in COUNTERS)
    secs = sum(v["incl_s"] for n, v in sp.items()
               if n.endswith(SUFFIXES) or n == NET)
    return nbytes / 1e9 / secs if nbytes and secs > 0 else None
