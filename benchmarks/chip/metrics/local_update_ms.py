"""Device milliseconds per local update: the ``local_update`` programs'
device time in the traced window over their executions."""


def read(ctx):
    ns, n = ctx.module_time(("local_update",))
    return ns * 1e-6 / n if n else None
