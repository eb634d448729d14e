"""Host time of a service round that is not spent waiting on the device
[ms]: the change of ``service.round_us`` less that of ``qn.sync_wait_us``
(the host blocked fetching results), over the change of
``service.rounds``.  Host work that overlaps the device counts here too.
A program without the counters gives no reading."""


def read(ctx):
    c = ctx["counters"]
    rounds = c.get("service.rounds", 0)
    if "service.round_us" not in c or "qn.sync_wait_us" not in c \
            or not rounds:
        return None
    return (c["service.round_us"] - c["qn.sync_wait_us"]) / rounds / 1e3
