"""Jobs active in a service round, averaged over the window's rounds: the
change of ``service.job_rounds`` over that of ``service.rounds``.  A
program without the counter gives no reading."""


def read(ctx):
    c = ctx["counters"]
    rounds = c.get("service.rounds", 0)
    if "service.job_rounds" not in c or not rounds:
        return None
    return c["service.job_rounds"] / rounds
