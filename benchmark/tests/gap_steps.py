"""Where the 95th percentile of an open-loop cell's token gaps sits, once, on
the chip: the sweep of ``sweep.py`` (one process, one set-up, one window a
rate, the same rule for "sustained"), and beside each rate the share of the
window's token gaps at each ``k``, the number of prefill chunks the engine
ran in the tick before that tick's decode (the tick record's ``chunks``): a
gap is one decode tick plus ``k`` chunks, and the percentile sits on a step
of ``k``. Since PR 28 ``_chunk_tick`` runs one chunk a tick, for the slot
admitted first, so ``k`` is 0 or 1; with one for every slot in mid-prefill,
as it was, ``k`` ran up to the slots prefilling. A tick's ``rows`` decoding
rows each get one gap from it.

    python3 -m benchmark.tests.gap_steps --workload <cell> \\
        --rates 1.5,2,2.5,3 --seconds 25 --seed 1

Not part of a benchmark run; the rate it places goes into the traffic file.
"""

import argparse
import json
import statistics
import sys
import threading
import time

from benchmark import spec, stats
from benchmark.modes import serve


def gaps_by_chunks(ticks):
    """{k: [gaps (decoding rows), median wall of such a tick in ms]} over
    the ticks that decoded."""
    out = {}
    for t in ticks:
        if "decode_dispatch" in t["phases"]:
            out.setdefault(t.get("chunks", 0), []).append(t)
    return {k: [sum(t["rows"] for t in ts),
                round(1e3 * statistics.median(t["t1"] - t["t0"]
                                              for t in ts), 2)]
            for k, ts in sorted(out.items())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    from building_llm_from_scratch_tpu.obs.compile import (
        configure_compile_cache,
    )
    from building_llm_from_scratch_tpu.obs.metrics import get_metrics

    configure_compile_cache()
    cell = spec.load_cell(args.workload)
    traffic, model = cell.traffic, cell.config["model"]
    generator = spec.load_module("generators", traffic["generator"])
    rates = [float(r) for r in args.rates.split(",")]
    t0 = time.perf_counter()
    engine = serve.build_engine(cell, args.seed,
                                int(max(rates) * args.seconds) + 1,
                                traffic["prompt"]["max"])
    engine.warmup()
    engine.start()
    print(json.dumps({"setup_s": time.perf_counter() - t0}), flush=True)
    for i, rate in enumerate(rates):
        plan = generator.plan(
            dict(traffic, arrivals={"kind": "poisson", "rate_per_s": rate}),
            model, args.seed + i, args.seconds)
        flights = [serve.Flight(p) for p in plan]
        t_open = time.perf_counter()
        serve.open_loop(engine, flights, t_open, threading.Event())
        t_close = time.perf_counter()
        in_engine = sum(1 for f in flights if not f.done)
        serve.wait_for(flights, 900.0)
        t_end = max(f.stamps[-1] for f in flights)
        gaps = stats.token_gaps(f.stamps for f in flights)
        ttft = [f.stamps[0] - (t_open + f.planned.due_s) for f in flights]
        ticks = [t for t in get_metrics().recent("tick")
                 if t_open <= t["t0"] <= t_open + args.seconds]
        by_k = gaps_by_chunks(ticks)
        total = sum(n for n, _ in by_k.values()) or 1
        cumulative, run = {}, 0
        for k, (n, _) in by_k.items():
            run += n
            cumulative[k] = round(run / total, 4)
        print(json.dumps({
            "rate_per_s": rate, "requests": len(plan),
            "failed": sum(f.failed for f in flights),
            "in_engine_at_close": in_engine,
            "drain_after_window_s": t_end - t_close,
            "ttft_p50_ms": 1e3 * stats.percentile(ttft, 50),
            "ttft_p95_ms": 1e3 * stats.percentile(ttft, 95),
            "tpot_p50_ms": 1e3 * stats.percentile(gaps, 50),
            "tpot_p90_ms": 1e3 * stats.percentile(gaps, 90),
            "tpot_p95_ms": 1e3 * stats.percentile(gaps, 95),
            "tpot_p98_ms": 1e3 * stats.percentile(gaps, 98),
            "token_gaps": len(gaps),
            "gaps_and_tick_ms_by_chunks": by_k,
            "cumulative_share_by_chunks": cumulative,
            "recompiles": engine.n_recompiles}), flush=True)
    # what the engine says of itself, as far as this model's has it (a dense
    # model's has no "experts")
    said = engine.stats()
    print(json.dumps({"stats": {k: said[k] for k in (
        "kv_append", "kv_positions", "experts", "kv_policy")
        if k in said}}), flush=True)
    engine.shutdown(drain=False)
    return 0


if __name__ == "__main__":
    sys.exit(main())
