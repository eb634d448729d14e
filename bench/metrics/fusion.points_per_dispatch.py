"""Points the scheduler sent to the device per fused dispatch in the
window: ``fusion.points_dispatched`` over ``fusion.groups`` (groups with
a miss, one dispatch each)."""


def read(ctx):
    c = ctx["counters"]
    groups = c.get("fusion.groups", 0)
    return c.get("fusion.points_dispatched", 0) / groups if groups else None
