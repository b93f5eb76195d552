"""Share of the time inside object writes (``ft.put.dev``,
``ft.put.host``) and plan executions (``ft.exec.*``) that the host spent
uploading, downloading or waiting on a device pool: the ``ft.*.h2d``,
``ft.*.d2h`` and ``ft.*sync`` spans (``ft.sync`` between and after
batches, ``ft.put.sync`` after a put's scatter).  An upload may return
before its bytes have moved; its wait then shows in the next sync."""
WAITS = (".h2d", ".d2h", ".sync")
PUTS = ("ft.put.dev", "ft.put.host")


def read(rec):
    sp = rec.get("spans") or {}
    whole = sum(v["incl_s"] for n, v in sp.items()
                if n in PUTS or n.startswith("ft.exec."))
    if whole <= 0:
        return None
    return 100.0 * sum(v["incl_s"] for n, v in sp.items()
                       if n.endswith(WAITS)) / whole
