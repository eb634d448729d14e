"""Share of the QN points requested in the window that the shared
``EvalCache`` served [%]: ``fusion.points_cached`` over ``fusion.points``."""


def read(ctx):
    c = ctx["counters"]
    points = c.get("fusion.points", 0)
    return 100.0 * c.get("fusion.points_cached", 0) / points if points \
        else None
