"""XLA compiles inside the window: the change of ``qn.compiles``
(``repro.obs.compile``) across it."""


def read(ctx):
    return ctx["compiles"]
