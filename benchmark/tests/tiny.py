"""Test-only override: a cell at a size the CPU can hold.

The chip path (``benchmark.run.main``) never takes this: it refuses to run
without a TPU. Tests swap a cell's configuration and traffic for these tiny
ones and drive ``run_cell``, the rest of a run.
"""

import dataclasses

from benchmark import spec

TINY_MODEL = {"emb_dim": 64, "n_heads": 4, "n_kv_groups": 4, "n_layers": 2,
              "hidden_dim": 256, "vocab_size": 512, "context_length": 128}


def tiny_cell(name: str, **traffic_overrides) -> spec.Cell:
    cell = spec.load_cell(name)
    config = dict(cell.config, model=dict(cell.config["model"], **TINY_MODEL))
    traffic = dict(cell.traffic)
    if cell.mode == "train":
        traffic.update(batch=4, seq_len=64, warm_steps=2, trace_seconds=0.3,
                       trainer=dict(traffic["trainer"], log_every=4))
    else:
        traffic.update(
            prompt={"median": 24, "sigma": 0.5, "min": 4, "max": 60},
            output={"median": 8, "sigma": 0.5, "min": 3, "max": 24},
            engine=dict(traffic["engine"], n_slots=4), pool=4000,
            trace_seconds=0.3, check={"n": 16, "min_tokens": 100})
        if traffic["arrivals"]["kind"] == "poisson":
            traffic["arrivals"] = dict(traffic["arrivals"], rate_per_s=8.0)
        else:
            traffic["arrivals"] = dict(traffic["arrivals"], clients=8)
            traffic["counted"] = 48
    traffic.update(traffic_overrides)
    return dataclasses.replace(cell, config=config, traffic=traffic)
