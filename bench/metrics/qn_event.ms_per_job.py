"""Device time of the ``qn_event`` kernel per job settled in the traced
window [ms]: the summed durations of the kernel's device events, over all
chips, divided by the jobs settled."""


def read(ctx):
    k = ctx["trace"].kernel_s.get("qn_event", 0.0)
    done = len(ctx["done"])
    return 1e3 * k / done if k > 0 and done else None
