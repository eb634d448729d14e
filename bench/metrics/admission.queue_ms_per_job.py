"""Wait of an admitted job from its submit to its activation [ms]: the
change of ``admission.queue_us`` over that of ``admission.admit`` in the
window.  A program without the counter gives no reading."""


def read(ctx):
    c = ctx["counters"]
    admitted = c.get("admission.admit", 0)
    if "admission.queue_us" not in c or not admitted:
        return None
    return c["admission.queue_us"] / admitted / 1e3
