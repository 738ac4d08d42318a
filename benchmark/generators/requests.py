"""Serving requests from the seed: one general generator, driven by data.

Every seed gets the same set of lengths and the same set of gaps between
arrivals, in another order: lengths are the evenly spaced quantiles of a
clipped lognormal, gaps the evenly spaced quantiles of the exponential, and
the seed only permutes them and draws the token ids. So two seeds offer the
same work and a difference between their runs is the system's, not the
draw's.

Traffic file parameters: ``arrivals`` (``{"kind": "poisson", "rate_per_s"}``
or ``{"kind": "closed", "clients": n}`` with ``pool`` requests to draw from),
``prompt`` / ``output`` (``median``, ``sigma``, ``min``, ``max``),
``sampling``, ``greedy_every``.
"""

from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Any, Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Planned:
    index: int
    due_s: Optional[float]       # None in a closed loop
    prompt_ids: np.ndarray
    max_new_tokens: int
    temperature: float
    top_k: Optional[int]
    seed: int


def lognormal_quantiles(spec: Dict[str, float], n: int) -> np.ndarray:
    nd = statistics.NormalDist()
    vals = [math.exp(math.log(spec["median"])
                     + spec["sigma"] * nd.inv_cdf((i + 0.5) / n))
            for i in range(n)]
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


def exponential_gaps(n: int, rate_per_s: float, span_s: float) -> np.ndarray:
    """n gaps whose running sum stays inside [0, span_s)."""
    gaps = np.array([-math.log(1.0 - (i + 0.5) / n) / rate_per_s
                     for i in range(n)])
    return gaps * (span_s * (1.0 - 0.5 / n) / gaps.sum())


def plan(traffic: Dict[str, Any], model: Dict[str, Any], seed: int,
         seconds: float) -> List[Planned]:
    arrivals = traffic["arrivals"]
    rng = np.random.default_rng(seed)
    if arrivals["kind"] == "poisson":
        n = max(1, round(arrivals["rate_per_s"] * seconds))
        # the same gaps in another order
        gaps = rng.permutation(
            exponential_gaps(n, arrivals["rate_per_s"], seconds))
        due = [float(d) for d in np.cumsum(gaps) - gaps[0]]
    elif arrivals["kind"] == "closed":
        n = int(traffic["pool"])
        due = [None] * n
    else:
        raise SystemExit(f"unknown arrivals kind {arrivals['kind']}")
    prompts = rng.permutation(lognormal_quantiles(traffic["prompt"], n))
    outputs = rng.permutation(lognormal_quantiles(traffic["output"], n))
    if int(prompts.max() + outputs.max()) > model["context_length"]:
        raise SystemExit("traffic clips allow prompt + output beyond the "
                         "model's context")
    sampling = traffic["sampling"]
    every = int(traffic.get("greedy_every", 0))
    planned = []
    for i in range(n):
        greedy = every > 0 and i % every == 0
        planned.append(Planned(
            index=i, due_s=due[i],
            prompt_ids=rng.integers(0, model["vocab_size"], int(prompts[i]),
                                    dtype=np.int32),
            max_new_tokens=int(outputs[i]),
            temperature=0.0 if greedy else float(sampling["temperature"]),
            top_k=None if greedy else sampling.get("top_k"),
            seed=(seed * 1000003 + i) % (2 ** 31 - 1)))
    return planned
