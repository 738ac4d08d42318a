"""Share of the memory roofline a sparse model's decode program reaches:
the least bytes of the traced ticks (``moe_decode_bytes.py``: everything
outside the routed experts once a tick, each held expert of each layer that
got a row once, keys and values of the live positions with a window layer
counting no more than its window) over the chip's memory bandwidth, over
the device time of the traced runs of the program. Least bytes on top, so it
cannot pass 100%.

The bytes come from the program's own tick records (``experts_touched``,
``kv_positions``). The reader is handed no stamps of the traced interval:
the harness opens it ``TRACE_AFTER_S`` into the window, so the traced ticks
are taken to be the records' decode ticks from that moment on, as many as
the trace holds runs of the program: their mean bytes a tick, times those
runs. The coupling to the harness's sleep is a guess the reader cannot
check (the profiler takes its own time to start): PERF.md section 7 asks a
``benchmark`` PR to hand the readers the capture's stamps."""

from benchmark.readers import moe_decode_bytes, program_records

#: ``modes/serve.py`` sleeps this long after opening the window before it
#: starts the profiler (min(2, seconds / 4); the cells run 50 s)
TRACE_AFTER_S = 2.0


def read(metric, ctx):
    trace, table = ctx["trace"], ctx["peaks"]
    runs = trace["programs"].get(metric["program"]) if trace else None
    if not runs or table is None:
        return None
    requests = program_records.requests_of_window(
        program_records.recent("span"))
    ticks = program_records.ticks_of_window(program_records.recent("tick"),
                                            requests)
    if not ticks:
        return None
    lo = requests[0]["t_submit"] + TRACE_AFTER_S
    traced = [t for t in ticks
              if "experts_touched" in t and t["t0"] >= lo][:len(runs)]
    if not traced:
        return None
    model = ctx["cell"].config["model"]
    per_tick = sum(moe_decode_bytes.tick_bytes(
        model, t["experts_touched"], t["kv_positions"])
        for t in traced) / len(traced)
    return 100.0 * len(runs) * per_tick / table["hbm_bytes_per_s"] / sum(runs)
