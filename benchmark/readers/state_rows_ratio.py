"""States the decode program read and wrote over those it had to, from the
program's own tick records over the window (``program_records``' rule for
which ticks are the window's): ``state_rows_touched`` (the rows of the
fixed-shape step x the layers that hold a state) summed over ``state_rows``
(the rows that decoded x those layers). 1.0 is a step that walks the live
rows alone; a step that touches every slot reads slots over live rows. A
program that keeps no ``state_rows`` gives nothing to read."""

from benchmark.readers import program_records


def read(metric, ctx):
    requests = program_records.requests_of_window(
        program_records.recent("span"))
    ticks = [t for t in program_records.ticks_of_window(
        program_records.recent("tick"), requests) if t.get("state_rows")]
    if not ticks:
        return None
    return (sum(t["state_rows_touched"] for t in ticks)
            / sum(t["state_rows"] for t in ticks))
