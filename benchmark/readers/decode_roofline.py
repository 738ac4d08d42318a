"""Share of the memory roofline the decode program reaches: the least bytes
the traced ticks had to move (the weights once a tick, plus keys and values of
the live positions of the requests that got a token) over the chip's memory
bandwidth, over the device time of those ticks. Decode is bound by bytes, not
operations. Least bytes on top, so it cannot pass 100%."""

from benchmark import peaks


def read(metric, ctx):
    trace, table, w = ctx["trace"], ctx["peaks"], ctx["window"]
    runs = trace["programs"].get(metric["program"]) if trace else None
    if not runs or table is None or not w.get("traced_decode_tokens"):
        return None
    model = ctx["cell"].config["model"]
    el = 2 if model["dtype"] in ("bf16", "fp16") else 4
    least_bytes = (len(runs) * peaks.decode_weight_bytes(model, el)
                   + w["traced_kv_positions"]
                   * peaks.kv_bytes_per_position(model, el))
    return 100.0 * least_bytes / table["hbm_bytes_per_s"] / sum(runs)
