"""Device milliseconds of the FedAuto weight solve per round: the
``solve_weights`` programs' device time in the traced window over the rounds
traced."""


def read(ctx):
    ns, n = ctx.module_time(("solve_weights",))
    return ns * 1e-6 / ctx.rounds if n and ctx.rounds else None
