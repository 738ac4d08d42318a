"""Test-only override for the Solar-Open2 family: a cell at a size the CPU
can hold (``tiny.py``'s override holds GPT-2's keys). Every mechanism stays:
a period of one gated NoPE attention layer and three gated-delta-rule linear
layers (a float32 state and a convolution's tail a slot), chunked prefill
(chunk 16, prompts to 90, so the last chunk carries pads and slots are used
again), 8 routed experts top-2 of which 3 are held, 1 shared, an untied head.
The chip path never takes this.
"""

import dataclasses

from benchmark import spec

TINY_MODEL = {"emb_dim": 64, "n_heads": 4, "attn_head_dim": 16,
              "n_kv_groups": 2, "n_layers": 4, "hidden_dim": 64,
              "vocab_size": 512, "context_length": 128,
              "linear_heads": 4, "linear_head_dim": 16,
              "linear_gate_rank": 16,
              "n_routed_experts": 8, "n_experts_per_tok": 2,
              "n_shared_experts": 1, "experts_held": [1, 5, 6]}


def tiny_cell(name: str, **traffic_overrides) -> spec.Cell:
    cell = spec.load_cell(name)
    config = dict(cell.config, model=dict(cell.config["model"], **TINY_MODEL))
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
