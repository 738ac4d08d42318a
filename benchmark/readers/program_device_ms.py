"""Median device time of one run of a compiled program, from the trace's
``XLA Modules`` line; the metric's ``program`` names it."""

import statistics


def read(metric, ctx):
    trace = ctx["trace"]
    runs = trace["programs"].get(metric["program"]) if trace else None
    if not runs:
        return None
    return 1e3 * statistics.median(runs)
