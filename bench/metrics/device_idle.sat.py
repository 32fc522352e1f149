"""Share of the traced stretch in which no operation ran on the fuser's
chip (1 - busy union / stretch), in %."""


def read(ctx):
    return 100.0 * ctx.trace.idle_share(0)
