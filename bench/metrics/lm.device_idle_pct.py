"""Share of the traced window in which no operation ran on the device
(1 - union of the device's operation intervals / window), in the serving
cells."""


def read(ctx):
    return ctx.trace.idle_pct
