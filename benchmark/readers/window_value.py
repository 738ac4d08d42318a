"""A value the mode took over the whole window, by the host's clock or from
the program's own counters; the metric's ``key`` names it."""


def read(metric, ctx):
    return ctx["window"].get(metric["key"])
