"""Host time of one service round: each ``SolverService.step()`` (the
harness's ``bench.round`` annotation) less the device-busy time inside
it, averaged over the rounds of the traced window [ms]."""


def read(ctx):
    rounds = ctx["trace"].rounds
    host = [(e - s) - busy for s, e, busy in rounds]
    return 1e3 * sum(host) / len(host) if host else None
