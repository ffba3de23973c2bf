"""Share of the traced window in which no operation ran on the chip, %."""


def read(ctx):
    return ctx.trace.idle_pct() if ctx.trace.ops else None
