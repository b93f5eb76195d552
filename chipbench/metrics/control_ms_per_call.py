"""Host milliseconds per fetch or consume call spent outside the plans
it ran: the facade, migration policy, LinkSim's ``sim.run`` and plan
compilation."""


def read(rec):
    reps = rec["reports"]
    first = rec["first_report"]
    calls = [c for c in rec["calls"] if c["op"] in ("fetch", "consume")]
    if not calls:
        return None
    ms = 0.0
    for c in calls:
        a, b = c["plans"]
        ms += c["wall_s"] * 1e3 - sum(
            r["wall_ms"] for r in reps[a - first:b - first])
    return ms / len(calls)
