"""Serve mode: drives ``DecodeEngine`` as the frontends do (``warmup()``,
``start()``, ``submit(..., on_token=)``), open or closed loop.

No count depends on a clock. The engine gets no deadline (so nothing expires
or is shed), no tick timeout (no supervisor restarts), and a queue as long as
the whole plan (nothing is rejected). The harness never calls ``drain()`` and
never waits on a live request with a short timeout:

* open loop (``arrivals.kind == "poisson"``): every planned request is due
  inside the window and is attempted; when the window closes the generator
  has sent them all and the engine runs on until each has finished. Latencies
  are over all of them, timed from when each was *due*.
* closed loop: ``clients`` callers, each taking the plan's next request when
  its last is done. The plan's first ``counted`` requests (a number in the
  traffic file, few enough to finish well inside the window) are attempted;
  the callers go on until each of them has finished, so the count is the
  same whatever the tick. Tokens a second still counts every token that
  arrived inside the window.

``failed`` is an attempted request that raised at submit or ended without its
tokens. The only limit on the wait is a hang guard of several times what the
plan's longest request needs; tripping it fails the run as a whole.
"""

from __future__ import annotations

import gc
import os
import shutil
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmark import memory, spec, stats, weights
from benchmark.trace import Capture


class Flight:
    """One planned request in the air: its handle and its token stamps."""

    def __init__(self, planned):
        self.planned = planned
        self.request = None
        self.error: Optional[str] = None
        self.t_sent: Optional[float] = None
        self.stamps: List[float] = []

    def on_token(self, req, tok, piece) -> None:
        self.stamps.append(time.perf_counter())

    @property
    def done(self) -> bool:
        return self.error is not None or (
            self.request is not None and self.request.done)

    @property
    def failed(self) -> bool:
        r = self.request
        return self.error is not None or r is None or (
            r.finish_reason != "length"
            or len(r.output_ids) != self.planned.max_new_tokens)


def submit(engine, flight: Flight) -> None:
    from building_llm_from_scratch_tpu.serving.request import SamplingParams

    p = flight.planned
    flight.t_sent = time.perf_counter()
    try:
        flight.request = engine.submit(
            p.prompt_ids, SamplingParams(
                max_new_tokens=p.max_new_tokens, temperature=p.temperature,
                top_k=p.top_k, seed=p.seed, ignore_eos=True,
                deadline_s=None),
            on_token=flight.on_token)
    except Exception as e:  # noqa: BLE001 - any refusal is a failed request
        flight.error = repr(e)


def open_loop(engine, flights: List[Flight], t_open: float,
              stop: threading.Event) -> None:
    """Sends each request when it is due, whatever the engine is doing."""
    for f in sorted(flights, key=lambda f: f.planned.due_s):
        wait = t_open + f.planned.due_s - time.perf_counter()
        if wait > 0 and stop.wait(wait):
            return
        submit(engine, f)


def closed_loop(engine, flights: List[Flight], clients: int, counted: int,
                t_open: float, seconds: float, stop: threading.Event) -> None:
    """``clients`` callers; each sends the plan's next request when its last
    is done, until the window has closed and the plan's first ``counted``
    requests have all finished."""
    pool = iter(flights)
    live = []
    for _ in range(clients):
        f = next(pool)
        submit(engine, f)
        live.append(f)
    guard_s = None
    while not stop.is_set():
        since = time.perf_counter() - t_open
        if since >= seconds:
            if all(f.done for f in flights[:counted]):
                return
            if guard_s is None:
                guard_s = hang_guard_s(flights, counted)
            if since > seconds + guard_s:
                raise SystemExit(
                    f"hang guard: the first {counted} requests were not "
                    f"finished {guard_s:.0f} s after the window closed")
        for i, f in enumerate(live):
            if f.done:
                nxt = next(pool, None)
                if nxt is None:
                    raise SystemExit("the closed loop used up its pool: "
                                     "raise 'pool' in the traffic file")
                submit(engine, nxt)
                live[i] = nxt
        stop.wait(0.005)


def build_engine(cell: spec.Cell, seed: int, n_planned: int, max_prompt: int):
    import jax

    from building_llm_from_scratch_tpu.configs import ModelConfig
    from building_llm_from_scratch_tpu.serving.engine import DecodeEngine
    from building_llm_from_scratch_tpu.serving.kvcache import KVCachePolicy

    cfg = ModelConfig(**cell.config["model"])
    params = weights.make_params(cell.config, seed, cfg.jax_dtype)
    jax.block_until_ready(params)
    opts = dict(cell.traffic["engine"])
    serve_tp = int(opts.pop("serve_tp", 1))
    mesh_plan = None
    if serve_tp > 1:
        from building_llm_from_scratch_tpu.parallel.sharding import (
            serve_mesh_plan,
        )

        mesh_plan = serve_mesh_plan(serve_tp)
    return DecodeEngine(
        cfg, params, None,
        kv_policy=KVCachePolicy(**opts.pop("kv_policy", {})),
        mesh_plan=mesh_plan,
        # no request can be turned away or cut off by a clock
        max_queue=n_planned + 8, default_deadline_s=None, tick_timeout_s=0.0,
        # every bucket the plan can reach is compiled in warm-up
        warmup_prompt_cap=max_prompt, **opts)


def warm_requests(engine, plan) -> None:
    """One greedy and one sampled request through the engine's own admission
    before the window opens, so that the small programs of that path (a
    request's key, the host's conversions) are compiled in set-up like the
    prefill and decode programs that ``warmup()`` compiles."""
    import dataclasses

    sampled = next((p for p in plan if p.temperature > 0), plan[0])
    greedy = next((p for p in plan if p.temperature == 0), plan[0])
    flights = [Flight(dataclasses.replace(
        p, prompt_ids=p.prompt_ids[:8], max_new_tokens=3))
        for p in (greedy, sampled)]
    for f in flights:
        submit(engine, f)
    wait_for(flights, 120.0)
    if any(f.failed for f in flights):
        raise SystemExit("a warm-up request failed: "
                         f"{[f.error for f in flights]}")


def hang_guard_s(flights: List[Flight], n_waited: int) -> float:
    """Several times what the longest request, behind ``n_waited`` others,
    needs at the tick this run has measured."""
    gaps = stats.token_gaps(f.stamps for f in flights)
    tick = stats.percentile(gaps, 50) if gaps else 0.2
    return 60.0 + 5.0 * tick * (
        max(f.planned.max_new_tokens for f in flights) + n_waited)


def engine_counters(engine) -> Dict[str, float]:
    """The engine's own tick accounting (``DecodeEngine.step``): ticks so
    far, their wall seconds, and the seconds of each phase inside them."""
    return {"ticks": engine.n_ticks, "tick_s": engine.tick_seconds_total,
            **engine.tick_phase_totals}


PREFILL_PHASES = ("prefill", "prefill_shard", "prefix_copy")


def wait_for(flights: List[Flight], guard_s: float) -> None:
    t_end = time.perf_counter() + guard_s
    for f in flights:
        while not f.done:
            if time.perf_counter() > t_end:
                raise SystemExit(f"hang guard: a request was not finished "
                                 f"{guard_s:.0f} s after the window closed")
            time.sleep(0.005)


def pick_checked(flights: List[Flight], seed: int, n: int, min_tokens: int
                 ) -> List[Flight]:
    """Greedy requests the window finished: the longest, and others drawn
    from the seed until ``n`` requests and ``min_tokens`` served tokens."""
    greedy = [f for f in flights if f.planned.temperature == 0.0
              and f.request is not None and not f.failed]
    if not greedy:
        return []
    size = lambda f: len(f.planned.prompt_ids) + f.planned.max_new_tokens
    longest = max(greedy, key=size)
    rest = [f for f in greedy if f is not longest]
    np.random.default_rng(seed).shuffle(rest)
    picked = [longest]
    for f in rest:
        if len(picked) >= n and sum(
                p.planned.max_new_tokens for p in picked) >= min_tokens:
            break
        picked.append(f)
    return picked


def compare(cell: spec.Cell, seed: int, sequences, control: str = ""
            ) -> Dict[str, Any]:
    reference = spec.load_module("reference", cell.config["reference"])
    model = cell.config["model"]
    params = weights.make_params(cell.config, seed, np.float32)
    return reference.served_token_gaps(
        params, model, sequences, pad_to=model["context_length"],
        control=control)


def run(cell: spec.Cell, *, seed: int, seconds: float, trace: bool, peaks,
        clock, say, compiles, control: str = "") -> Dict[str, Any]:
    import jax

    traffic, model = cell.traffic, cell.config["model"]
    generator = spec.load_module("generators", traffic["generator"])
    plan = generator.plan(traffic, model, seed, seconds)
    flights = [Flight(p) for p in plan]
    work = os.path.join(spec.ROOT, ".benchmark_work", cell.name)
    shutil.rmtree(work, ignore_errors=True)
    capture = Capture(os.path.join(work, "trace")) if trace else None
    engine = build_engine(cell, seed, len(plan),
                          max(len(p.prompt_ids) for p in plan))
    clock.mark("weights_and_cache")
    engine.warmup()
    clock.mark("warmup")
    engine.start()
    warm_requests(engine, plan)
    clock.mark("first_requests")
    closed = traffic["arrivals"]["kind"] == "closed"
    stop = threading.Event()
    compiles_at_open = compiles.n
    counters_at_open = engine_counters(engine)
    t_open = time.perf_counter()
    setup_s = t_open - clock.t0
    errors: List[BaseException] = []

    def load() -> None:
        try:
            if closed:
                closed_loop(engine, flights, traffic["arrivals"]["clients"],
                            traffic["counted"], t_open, seconds, stop)
            else:
                open_loop(engine, flights, t_open, stop)
        except BaseException as e:  # noqa: BLE001 - re-raised by the run
            errors.append(e)

    driver = threading.Thread(target=load, name="load")
    driver.start()
    try:
        if capture is not None:
            time.sleep(min(2.0, seconds / 4))
            capture.start()
            time.sleep(min(float(traffic["trace_seconds"]), seconds / 2))
            capture.stop()
        time.sleep(max(0.0, t_open + seconds - time.perf_counter()))
        t_close = t_open + seconds
        ticked = {k: v - counters_at_open[k]
                  for k, v in engine_counters(engine).items()}
        driver.join()
        attempted = flights[:traffic["counted"]] if closed else flights
        if not errors:
            wait_for(attempted, hang_guard_s(flights, len(attempted)))
    finally:
        stop.set()
        driver.join()
    if errors:
        raise errors[0]
    n_compiled = max(compiles.n - compiles_at_open, engine.n_recompiles)
    peak = memory.read_peak(
        say, (engine.params, engine.cache),
        (exe for w in engine._watchers() for exe in w.executables))
    n_ticks = engine.n_ticks
    engine.shutdown(drain=False)
    result_trace = capture.result() if capture is not None else None

    failed = [f for f in attempted if f.failed]
    failures = [f.error or f.request.finish_reason for f in failed][:5]
    ok = [f for f in attempted if not f.failed]
    checked = pick_checked(attempted, seed, **traffic["check"])
    sequences = [(f.planned.prompt_ids, np.asarray(f.request.output_ids,
                                                   np.int32))
                 for f in checked]
    # free the engine (weights, cache) before the reference takes the chip
    for f in flights:
        f.request = None if f not in checked else f.request
    del engine
    gc.collect()
    t_ref = time.perf_counter()
    gaps_ref = (compare(cell, seed, sequences, control) if sequences
                else {"widest_gap": float("inf"), "tokens": 0})
    say(reference_s=round(time.perf_counter() - t_ref, 3),
        checked_requests=len(sequences), checked_tokens=gaps_ref["tokens"],
        control=gaps_ref.get("control_widest_gap"))
    shutil.rmtree(work, ignore_errors=True)

    gaps = stats.token_gaps(f.stamps for f in ok)
    in_window = sum(1 for f in flights for t in f.stamps
                    if t_open <= t < t_close)
    limits = cell.config["limits"]["serve"]
    numbers = {"served_logit_widest_gap": gaps_ref["widest_gap"]}
    end_to_end = {"setup_s": setup_s,
                  "serve_out_tok_s": in_window / seconds}
    summary = {"attempted": len(attempted), "failed": len(failed),
               "served_logit_widest_gap": gaps_ref["widest_gap"],
               "planned": len(plan), "tokens_in_window": in_window,
               "out_tok_s": in_window / seconds, "engine_ticks": n_ticks,
               "token_gaps": len(gaps),
               "longest_tick_gaps_s_ms": stats.longest_tick_gaps(
                   ([t for t in f.stamps if t_open <= t < t_close]
                    for f in flights), t_open),
               "failures": failures}
    if gaps:
        summary["tpot_p50_ms"] = 1e3 * stats.percentile(gaps, 50)
        end_to_end["tpot_p95_ms"] = 1e3 * stats.percentile(gaps, 95)
    if not closed:
        ttft = [f.stamps[0] - (t_open + f.planned.due_s) for f in ok]
        late = [f.t_sent - (t_open + f.planned.due_s) for f in flights]
        # per layer, not end to end: 5% of a window's requests are the
        # arrivals of 2.5 s, so one stall of the host that long moves the
        # 95th percentile from one tick to seconds (PERF.md section 6)
        first_token = {"ttft_p50_ms": 1e3 * stats.percentile(ttft, 50),
                       "ttft_p95_ms": 1e3 * stats.percentile(ttft, 95)}
        summary.update(
            first_token,
            ttft_longest_s_ms=[[round(f.planned.due_s, 3), round(1e3 * t, 1)]
                               for t, f in sorted(zip(ttft, ok),
                                                  key=lambda p: -p[0])[:5]],
            generator_late_p95_ms=1e3 * stats.percentile(late, 95),
            offered_per_s=len(plan) / seconds,
            drain_after_window_s=max(f.stamps[-1] for f in ok) - t_close)
    if ticked["ticks"]:
        # the engine's own account of a tick, the prefills inside it apart
        per_tick = {k: 1e3 * v / ticked["ticks"] for k, v in ticked.items()
                    if k != "ticks" and v}
        summary["tick_phases_ms"] = per_tick
        window = {"engine_tick_ms": per_tick["tick_s"] - sum(
            per_tick.get(ph, 0.0) for ph in PREFILL_PHASES)}
    else:
        window = {}
    if not closed:
        window.update(first_token)
    if capture is not None and capture.t_stop is not None:
        window.update(_traced_decode_work(flights, capture))
    return {
        "setup_s": setup_s, "attempted": len(attempted),
        "failed": len(failed),
        "sound": n_compiled == 0 and bool(sequences),
        "compiles_in_window": n_compiled,
        "compared": [(k, numbers[k], limits[k]) for k in limits],
        "end_to_end": end_to_end, "window": window, "summary": summary,
        "memory_peak_bytes": peak, "trace": result_trace,
    }


def _traced_decode_work(flights: List[Flight], capture: Capture
                        ) -> Dict[str, int]:
    """Over the traced part of the window: the tokens that decode ticks
    produced and the cache positions those ticks had to read (a request's
    j-th token, j >= 1, attends to its prompt and the j tokens before)."""
    tokens = positions = 0
    for f in flights:
        n_prompt = len(f.planned.prompt_ids)
        for j, t in enumerate(f.stamps):
            if j >= 1 and capture.t_start <= t < capture.t_stop:
                tokens += 1
                positions += n_prompt + j
    return {"traced_decode_tokens": tokens,
            "traced_kv_positions": positions}
