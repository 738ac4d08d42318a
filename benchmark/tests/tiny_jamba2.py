"""Test-only override for the dense Jamba family: a cell at a size the CPU
can hold (``tiny.py``'s override holds GPT-2's keys). Every mechanism stays:
one whole period of fourteen layers, thirteen selective-state-space layers
(a float32 state and a convolution's tail a slot) around one multi-query
attention layer, chunked prefill (chunk 16, prompts to 90, so the last chunk
carries pads and slots are used again), a dense SwiGLU, a tied head. The chip
path never takes this.
"""

import dataclasses

from benchmark import spec

TINY_MODEL = {"emb_dim": 64, "n_heads": 4, "attn_head_dim": 16,
              "n_kv_groups": 1, "n_layers": 14, "hidden_dim": 64,
              "vocab_size": 512, "context_length": 128,
              "ssm_inner": 128, "ssm_state": 8, "ssm_dt_rank": 4}


def tiny_cell(name: str, **traffic_overrides) -> spec.Cell:
    cell = spec.load_cell(name)
    # the file's limit is the chip's, for 28 bfloat16 layers at the published
    # widths; the tests run this size in float32, where a sound run reads
    # rounding (under 1e-5) and an altered token some tenths
    config = dict(cell.config, model=dict(cell.config["model"], **TINY_MODEL),
                  limits={"serve": {"served_logit_widest_gap": 1e-3}})
    traffic = dict(cell.traffic)
    traffic.update(
        prompt={"median": 36, "sigma": 0.5, "min": 4, "max": 90},
        output={"median": 8, "sigma": 0.5, "min": 3, "max": 24},
        engine=dict(traffic["engine"], n_slots=4,
                    kv_policy={"prefill_chunk": 16}),
        arrivals=dict(traffic["arrivals"], rate_per_s=8.0),
        trace_seconds=0.3, check={"n": 16, "min_tokens": 100})
    traffic.update(traffic_overrides)
    return dataclasses.replace(cell, config=config, traffic=traffic)
