"""From the ``expert_rows`` a sparse model's decode ticks record (rows each
held expert computed, summed over layers; ``obs/schema.py``
``TICK_RECORD_FIELDS``), over the window's ticks as
``program_records.ticks_of_window`` finds them. The metric's ``stat``:

* ``expert_rows_max_over_mean``: the busiest held expert's rows over the
  mean of all held experts' rows, summed over the window: 1.0 is an even
  load; the deployment's slowest chip waits at this ratio.

A program that records no such field gives nothing to read: ``None``.
"""

from benchmark.readers import program_records


def expert_rows_max_over_mean(ticks):
    rows = [t["expert_rows"] for t in ticks if t.get("expert_rows")]
    if not rows:
        return None
    totals = [sum(col) for col in zip(*rows)]
    mean = sum(totals) / len(totals)
    return max(totals) / mean if mean else None


def read(metric, ctx):
    requests = program_records.requests_of_window(
        program_records.recent("span"))
    ticks = program_records.ticks_of_window(program_records.recent("tick"),
                                            requests)
    return {"expert_rows_max_over_mean": expert_rows_max_over_mean}[
        metric["stat"]](ticks)
