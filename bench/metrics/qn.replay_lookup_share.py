"""Share of the window's replay draws that the ``qn_event`` kernel's
lookup copied out of the sample lists [%]: the change of
``qn.replay_lookups`` over that of ``qn.replay_draws``.  100 where every
replay dispatch runs the lookup kernel; 0 where the draws are gathered in
XLA.  A program without the counters, or a window without replay draws,
gives no reading."""


def read(ctx):
    c = ctx["counters"]
    draws = c.get("qn.replay_draws", 0)
    if "qn.replay_lookups" not in c or not draws:
        return None
    return 100.0 * c["qn.replay_lookups"] / draws
