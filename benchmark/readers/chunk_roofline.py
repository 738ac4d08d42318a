"""Share of its roofline the prefill-chunk program of a dense model reaches:
for each traced chunk the larger of its real tokens' matrix-product
operations over the chip's peak and its weights' bytes over the memory
bandwidth, summed, over the device time of the traced runs of the program.
A chunk multiplies each of its real tokens against every weight outside the
embedding (2 operations a parameter and token; attention's scores, the one
row of logits and the selective scan's element-steps are left out, which
keeps the share an under-reading) and reads every weight once, the embedding
as the tied head among them. Least work on top, so it cannot pass 100%.

The real tokens come from the program's own tick records (``chunk_tokens``:
the tokens of the chunk a tick ran, its padding left out); a program that
keeps none gives nothing to read. Which records are the traced chunks is
``moe_decode_roofline.py``'s guess (``ssm_decode_roofline.traced_ticks``)."""

from benchmark.readers import ssm_decode_bytes
from benchmark.readers.ssm_decode_roofline import traced_ticks


def chunk_seconds_at_peak(model, tokens: float, table) -> float:
    """The least time one chunk of ``tokens`` real tokens can take."""
    weights = ssm_decode_bytes.block_params(model)
    return max(2.0 * weights * tokens / table["flops_bf16"],
               ssm_decode_bytes.dense_bytes_per_tick(model)
               / table["hbm_bytes_per_s"])


def read(metric, ctx):
    trace, table = ctx["trace"], ctx["peaks"]
    runs = trace["programs"].get(metric["program"]) if trace else None
    if not runs or table is None:
        return None
    traced = traced_ticks(len(runs), "chunk_tokens")
    if not traced:
        return None
    model = ctx["cell"].config["model"]
    per_chunk = sum(chunk_seconds_at_peak(model, t["chunk_tokens"], table)
                    for t in traced) / len(traced)
    return 100.0 * len(runs) * per_chunk / sum(runs)
