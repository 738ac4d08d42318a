"""Model FLOP/s utilisation of the train step on the device: the operations
forward and backward require for one step (benchmark/peaks.py; recomputation
not counted) over the chip's bf16 peak, over the step program's median device
time. A share of a peak: with the least operations on top it cannot pass
100%."""

import statistics

from benchmark import peaks


def read(metric, ctx):
    trace, table = ctx["trace"], ctx["peaks"]
    runs = trace["programs"].get(metric["program"]) if trace else None
    if not runs or table is None:
        return None
    w = ctx["window"]
    flops = w["tokens_per_step"] * peaks.train_flops_per_token(
        ctx["cell"].config["model"], w["seq_len"])
    return 100.0 * flops / table["flops_bf16"] / statistics.median(runs)
