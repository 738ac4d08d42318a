"""Share of the memory roofline the decode program of a dense model with
state-space and softmax attention layers reaches: the least bytes of the
traced ticks (``ssm_decode_bytes.py``: every weight outside the embedding
once a tick, the tied head once, keys and values of the live positions, the
state and the tail of each decoding row's state-space layers read and
written) over the chip's memory bandwidth, over the device time of the traced
runs of the program. Least bytes on top, so it cannot pass 100%.

The bytes come from the program's own tick records (``kv_positions``,
``state_rows``, ``rows``); a program that keeps no ``state_rows`` gives
nothing to read. Which records are the traced ticks is
``moe_decode_roofline.py``'s guess, the harness's sleep before it opens the
trace, for the reason given there (PERF.md section 7 asks a ``benchmark`` PR
to hand the readers the capture's stamps)."""

from benchmark.readers import program_records, ssm_decode_bytes
from benchmark.readers.moe_decode_roofline import TRACE_AFTER_S


def traced_ticks(n_runs: int, field: str):
    """The window's tick records that hold ``field``, from the moment the
    harness opens the trace on, as many as the trace holds runs."""
    requests = program_records.requests_of_window(
        program_records.recent("span"))
    ticks = program_records.ticks_of_window(program_records.recent("tick"),
                                            requests)
    if not ticks:
        return []
    lo = requests[0]["t_submit"] + TRACE_AFTER_S
    return [t for t in ticks if field in t and t["t0"] >= lo][:n_runs]


def read(metric, ctx):
    trace, table = ctx["trace"], ctx["peaks"]
    runs = trace["programs"].get(metric["program"]) if trace else None
    if not runs or table is None:
        return None
    traced = traced_ticks(len(runs), "state_rows")
    if not traced:
        return None
    model = ctx["cell"].config["model"]
    per_tick = sum(ssm_decode_bytes.tick_bytes(
        model, t["kv_positions"], t["state_rows"], t["rows"])
        for t in traced) / len(traced)
    return 100.0 * len(runs) * per_tick / table["hbm_bytes_per_s"] / sum(runs)
