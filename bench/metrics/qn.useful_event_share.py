"""Share of the simulated lane-events that a lane's own budget needed
[%]: ``qn.events_useful`` over ``qn.events_total`` in the window (the
rest is padding of lanes and of scan lengths)."""


def read(ctx):
    c = ctx["counters"]
    total = c.get("qn.events_total", 0)
    return 100.0 * c.get("qn.events_useful", 0) / total if total else None
