"""Host milliseconds per facade call spent in the facade, plan building
and LinkSim's ``sim.run`` themselves: the self time of the program's
``ft.store``, ``ft.fetch``, ``ft.consume``, ``ft.plan`` and
``ft.sim.run`` spans, the data plane's spans nested in them left out,
over the count of facade calls."""
CALLS = ("ft.store", "ft.fetch", "ft.consume")
CONTROL = CALLS + ("ft.plan", "ft.sim.run")


def read(rec):
    sp = rec.get("spans") or {}
    calls = sum(sp[n]["count"] for n in CALLS if n in sp)
    if not calls:
        return None
    return 1e3 * sum(sp[n]["self_s"] for n in CONTROL if n in sp) / calls
