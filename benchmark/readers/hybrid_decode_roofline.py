"""Share of the memory roofline the decode program of a model with linear
and softmax attention layers and sparse experts reaches: the least bytes of
the traced ticks (``hybrid_decode_bytes.py``: everything outside the routed
experts once a tick, each held expert of each layer that got a row once, keys
and values of the live positions, the state of each decoding row's linear
layers read and written) over the chip's memory bandwidth, over the device
time of the traced runs of the program. Least bytes on top, so it cannot
pass 100%.

The bytes come from the program's own tick records (``experts_touched``,
``kv_positions``, ``state_rows``, ``rows``); a program that keeps no
``state_rows`` gives nothing to read. Which records are the traced ticks is
``moe_decode_roofline.py``'s guess, the harness's sleep before it opens the
trace, for the reason given there (PERF.md section 7 asks a ``benchmark`` PR
to hand the readers the capture's stamps)."""

from benchmark.readers import hybrid_decode_bytes, program_records
from benchmark.readers.moe_decode_roofline import TRACE_AFTER_S


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
              if "state_rows" in t and t["t0"] >= lo][:len(runs)]
    if not traced:
        return None
    model = ctx["cell"].config["model"]
    per_tick = sum(hybrid_decode_bytes.tick_bytes(
        model, t.get("experts_touched", 0), t["kv_positions"],
        t["state_rows"], t["rows"]) for t in traced) / len(traced)
    return 100.0 * len(runs) * per_tick / table["hbm_bytes_per_s"] / sum(runs)
