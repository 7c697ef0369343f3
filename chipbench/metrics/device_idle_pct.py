"""device_idle_pct: the share of the traced window in which no operation ran
on the busiest device."""


def read(ctx):
    return ctx["trace"]["idle_pct"]
