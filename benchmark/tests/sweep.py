"""Finds the knee of an open-loop cell, once, on the chip: one process, one
set-up, one window per rate. A rate is sustained when the requests still in
the engine at the end of its window are no more than the slots hold and the
wait for the last of them is what one request needs, not a backlog's.

    python3 -m benchmark.tests.sweep --workload serve_gpt2_1p5b_chat \\
        --rates 3,4,5,6,7 --seconds 25 --seed 1

Not part of a benchmark run; the rate it finds goes into the traffic file.
"""

import argparse
import json
import sys
import threading
import time

from benchmark import spec, stats
from benchmark.modes import serve


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
        serve.wait_for(flights, 600.0)
        t_end = max(f.stamps[-1] for f in flights)
        gaps = stats.token_gaps(f.stamps for f in flights)
        ttft = [f.stamps[0] - (t_open + f.planned.due_s) for f in flights]
        tokens = sum(len(f.stamps) for f in flights)
        print(json.dumps({
            "rate_per_s": rate, "requests": len(plan),
            "failed": sum(f.failed for f in flights),
            "tok_s_to_last_token": tokens / (t_end - t_open),
            "tok_s_in_window": sum(1 for f in flights for t in f.stamps
                                   if t < t_open + args.seconds)
            / args.seconds,
            "in_engine_at_close": in_engine,
            "drain_after_window_s": t_end - t_close,
            "ttft_p50_ms": 1e3 * stats.percentile(ttft, 50),
            "ttft_p95_ms": 1e3 * stats.percentile(ttft, 95),
            "tpot_p50_ms": 1e3 * stats.percentile(gaps, 50),
            "tpot_p95_ms": 1e3 * stats.percentile(gaps, 95),
            "recompiles": engine.n_recompiles}), flush=True)
    engine.shutdown(drain=False)
    return 0


if __name__ == "__main__":
    sys.exit(main())
