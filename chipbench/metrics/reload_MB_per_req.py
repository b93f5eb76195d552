"""MB (2**20 bytes) reloaded host-to-device, on demand or by prefetch,
per request finished in the window."""


def read(rec):
    if not rec["finished"]:
        return None
    return sum(r["size_mb"] for r in rec["reports"]
               if r["kind"] in ("reload", "prefetch")) / rec["finished"]
