"""MB (2**20 bytes) spilled device-to-host per request finished in the
window."""


def read(rec):
    if not rec["finished"]:
        return None
    return sum(r["size_mb"] for r in rec["reports"]
               if r["kind"] == "spill") / rec["finished"]
