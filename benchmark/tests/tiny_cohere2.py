"""Test-only override for the Cohere2 MoE family: a cell at a size the CPU
can hold (``tiny.py``'s override holds GPT-2's keys). Every mechanism stays:
a period of three window layers and a full one, rings that wrap (window 16,
chunk 8, prompts to 60), 8 routed experts top-2 of which 3 are held, 2
shared, a tied head. The chip path never takes this.
"""

import dataclasses

from benchmark import spec

TINY_MODEL = {"emb_dim": 64, "n_heads": 4, "attn_head_dim": 16,
              "n_kv_groups": 2, "n_layers": 4, "hidden_dim": 64,
              "vocab_size": 512, "context_length": 128, "sliding_window": 16,
              "n_routed_experts": 8, "n_experts_per_tok": 2,
              "n_shared_experts": 2, "experts_held": [1, 5, 6]}


def tiny_cell(name: str, **traffic_overrides) -> spec.Cell:
    cell = spec.load_cell(name)
    config = dict(cell.config, model=dict(cell.config["model"], **TINY_MODEL))
    traffic = dict(cell.traffic)
    traffic.update(
        prompt={"median": 24, "sigma": 0.5, "min": 4, "max": 60},
        output={"median": 8, "sigma": 0.5, "min": 3, "max": 24},
        engine=dict(traffic["engine"], n_slots=4,
                    kv_policy={"prefill_chunk": 8}),
        arrivals=dict(traffic["arrivals"], rate_per_s=8.0),
        trace_seconds=0.3, check={"n": 16, "min_tokens": 100})
    traffic.update(traffic_overrides)
    return dataclasses.replace(cell, config=config, traffic=traffic)
