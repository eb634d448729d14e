"""QN points the search requested per job settled in the window:
``fusion.points`` over the jobs settled."""


def read(ctx):
    done = len(ctx["done"])
    return ctx["counters"].get("fusion.points", 0) / done if done else None
