"""Serving frontends: the ``serve`` CLI mode (JSONL batch + minimal HTTP).

Two dependency-free ways to put load on the engine:

  - JSONL batch (``--serve_prompts requests.jsonl``): one request per
    line — ``{"prompt": "...", "max_new_tokens": 32, "temperature": 0.7,
    "top_k": 40, "seed": 1, "deadline_s": 30}`` (or ``"prompt_ids":
    [..]``). Results stream to ``--serve_out`` (default stdout) as JSONL,
    one line per request in submission order — each line is flushed the
    moment its in-order handle completes, so a crash or drain never loses
    finished work. Submission uses blocking backpressure: a full queue
    stalls the reader instead of rejecting.
  - HTTP (``--serve_port``): a stdlib ``http.server`` endpoint —
    ``POST /generate`` with the same JSON fields returns the generated
    text + telemetry; ``GET /healthz`` reports a structured stats
    snapshot (state, uptime, ticks, occupancy, queue, restarts, request
    counters); ``GET /metrics`` is Prometheus text exposition (latency
    histograms, occupancy/queue gauges, SLO burn rate) for scraping.
    Status mapping: 429 + Retry-After for queue-full AND SLO shed, 503 +
    Retry-After while draining, 504 for queue-expired deadlines and
    handler timeouts (the timed-out request is CANCELLED, freeing its
    slot), 413 for oversized bodies, 400 for malformed JSON, 500 only
    for engine-side faults.

Run-mode resilience (``run_serve``): SIGTERM/SIGINT arm
``training/resilience.GracefulStopper``; a watcher thread then drains the
engine (admission closed, in-flight finishes within ``--drain_timeout``,
the remainder fails with reason ``preempted``) and stops the HTTP server,
so a preempted replica exits 0 with every completed result already
written.
"""

from __future__ import annotations

import json
import sys
import threading
from typing import List, Optional

from building_llm_from_scratch_tpu.serving.engine import DecodeEngine
from building_llm_from_scratch_tpu.serving.queue import (
    EngineDrainingError,
    PromptTooLongError,
    QueueFullError,
    SLOShedError,
)
from building_llm_from_scratch_tpu.serving.request import (
    Request,
    RequestExpiredError,
    SamplingParams,
)
from building_llm_from_scratch_tpu.utils.logging import setup_logger

logger = setup_logger(__name__)


def parse_adapter_specs(spec: str, flag: str = "--serve_adapters") -> dict:
    """``name=path[,name=path...]`` -> {name: path}. Names must be
    unique. Shared by ``--serve_adapters`` (adapter artifacts) and the
    fused-finetune fleet's ``--fleet_jobs`` (per-tenant record files) —
    ``flag`` only labels the error messages."""
    out: dict = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise ValueError(f"{flag} entry '{part}' is not name=path")
        name, path = part.split("=", 1)
        name, path = name.strip(), path.strip()
        if not name or not path:
            raise ValueError(f"{flag} entry '{part}' is not name=path")
        if name in out:
            raise ValueError(f"{flag} names '{name}' twice")
        out[name] = path
    if not out:
        raise ValueError(f"{flag} is empty")
    return out


def params_from_record(rec: dict, default_max_new: int) -> SamplingParams:
    return SamplingParams(
        max_new_tokens=int(rec.get("max_new_tokens", default_max_new)),
        temperature=float(rec.get("temperature", 0.0)),
        top_k=(int(rec["top_k"]) if rec.get("top_k") else None),
        seed=int(rec.get("seed", 0)),
        eos_id=(int(rec["eos_id"]) if "eos_id" in rec
                and rec["eos_id"] is not None else None),
        ignore_eos=bool(rec.get("ignore_eos", False)),
        # `is not None`, not truthiness: deadline_s=0 must flow through to
        # engine.submit's `deadline_s must be > 0` ValueError (HTTP 400),
        # not be silently promoted to "no deadline"
        deadline_s=(float(rec["deadline_s"])
                    if rec.get("deadline_s") is not None else None),
        # LoRA adapter by registry name; unknown names reject at submit
        # (ValueError -> HTTP 400)
        adapter=(str(rec["adapter"])
                 if rec.get("adapter") is not None else None),
        # per-request speculative opt-out ("spec": false) — tokens are
        # bit-identical either way; this only trades draft compute
        spec=bool(rec.get("spec", True)),
    )


def result_record(req: Request, text: Optional[str] = None) -> dict:
    rec = req.summary()
    rec["token_ids"] = [int(t) for t in req.output_ids]
    rec["text"] = req.text if text is None else text
    return rec


def error_record(req: Request) -> dict:
    """The JSONL line for a request the engine failed/shed/preempted:
    still one line in submission order, with the failure surfaced instead
    of silently missing output."""
    rec = req.summary()
    rec["error"] = req.error
    return rec


def serve_jsonl(engine: DecodeEngine, prompts_path: str,
                out_path: Optional[str], default_max_new: int) -> List[dict]:
    """Pump a JSONL request file through the engine (blocking
    backpressure), write one result line per request in submission order.

    Fault/drain-tolerant: a failed, expired or preempted request becomes
    an ``error`` line instead of crashing the pump, and admission closing
    mid-file (drain) records the unsubmitted remainder as shed — every
    COMPLETED request's line is on disk either way."""
    handles: List[Request] = []
    shed: List[dict] = []
    with open(prompts_path) as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            rec = json.loads(line)
            prompt = rec.get("prompt_ids", rec.get("prompt"))
            if prompt is None:
                raise ValueError(
                    f"{prompts_path}:{lineno}: needs 'prompt' or "
                    "'prompt_ids'")
            try:
                handles.append(engine.submit(
                    prompt, params_from_record(rec, default_max_new),
                    block=True))
            except (EngineDrainingError, SLOShedError,
                    QueueFullError) as e:
                shed.append({"line": lineno, "error": str(e),
                             "finish_reason": "shed"})
    # write each result as its in-order handle completes (flushed per
    # line) so finished work is durable even if a later request crashes
    # the process
    results: List[dict] = []
    out = open(out_path, "w") if out_path else sys.stdout
    try:
        for h in handles:
            try:
                rec = result_record(h.result())
            except (RuntimeError, RequestExpiredError):
                rec = error_record(h)
            results.append(rec)
            out.write(json.dumps(rec) + "\n")
            out.flush()
        for rec in shed:
            results.append(rec)
            out.write(json.dumps(rec) + "\n")
            out.flush()
    finally:
        if out_path:
            out.close()
    n_ok = sum(1 for r in results if "error" not in r)
    logger.info("Served %d/%d JSONL requests (%d tokens; %d failed/shed).",
                n_ok, len(results),
                sum(r.get("n_tokens", 0) for r in results),
                len(results) - n_ok)
    return results


# ---------------------------------------------------------------------------
# HTTP endpoint (stdlib only)
# ---------------------------------------------------------------------------

def make_http_server(engine: DecodeEngine, port: int,
                     host: str = "127.0.0.1",
                     request_timeout_s: float = 300.0,
                     max_body_bytes: int = 1 << 20):
    """Build (not start) a ThreadingHTTPServer bound to ``port`` (0 = any
    free port; read the actual one off ``server.server_address``).
    Loopback-only by default — the endpoint is unauthenticated, so
    exposing it (``host="0.0.0.0"`` / ``--serve_host``) is opt-in.

    Input hardening: bodies over ``max_body_bytes`` get 413 without being
    read, malformed/mistyped JSON gets 400 (never a handler traceback),
    and a handler timeout CANCELS the underlying request so its slot
    stops decoding for a client that already hung up."""
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    class Handler(BaseHTTPRequestHandler):
        # socket read timeout (BaseRequestHandler.setup applies it): a
        # client that sends Content-Length: N but stalls mid-body would
        # otherwise block rfile.read(n) — and its handler thread — forever
        # (slow-loris); on timeout http.server drops the connection
        timeout = 60

        def log_message(self, fmt, *args):          # route through our logger
            logger.debug("http: " + fmt, *args)

        def _json(self, code: int, payload: dict,
                  retry_after: Optional[float] = None) -> None:
            body = json.dumps(payload).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            if retry_after is not None:
                # RFC 7231 delay-seconds (integer, >= 1)
                self.send_header("Retry-After",
                                 str(max(1, int(round(retry_after)))))
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            if self.path == "/metrics":
                # Prometheus text exposition: counters (requests by
                # outcome, restarts, per-phase tick seconds), gauges
                # (occupancy, queue depth, draining, SLO burn rate) and
                # the TTFT/TPOT/e2e/queue-wait histograms — what the
                # replica router / alerting scrape
                body = engine.prometheus_text().encode()
                self.send_response(200)
                self.send_header("Content-Type",
                                 "text/plain; version=0.0.4")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
                return
            if self.path != "/healthz":
                return self._json(404, {"error": "unknown path"})
            # one method for both binds: a DecodeEngine answers its
            # historical structured snapshot, an EngineRouter answers
            # the fleet view (per-replica status + routing counters)
            self._json(200, engine.healthz_payload())

        def do_POST(self):
            if self.path != "/generate":
                return self._json(404, {"error": "unknown path"})
            try:
                n = int(self.headers.get("Content-Length", 0))
            except (TypeError, ValueError):
                return self._json(400, {"error": "bad Content-Length"})
            if n < 0:
                return self._json(400, {"error": "bad Content-Length"})
            if n > max_body_bytes:
                # refuse WITHOUT reading: an oversized body must cost the
                # server a header parse, not max_body_bytes of RAM
                return self._json(413, {
                    "error": f"body {n} bytes exceeds the "
                             f"{max_body_bytes}-byte limit"})
            try:
                rec = json.loads(self.rfile.read(n) or b"{}")
                if not isinstance(rec, dict):
                    return self._json(
                        400, {"error": "body must be a JSON object"})
                prompt = rec.get("prompt_ids", rec.get("prompt"))
                if prompt is None:
                    return self._json(
                        400, {"error": "missing 'prompt'/'prompt_ids'"})
                params = params_from_record(
                    rec, engine.default_max_new_tokens)
            except (ValueError, TypeError, json.JSONDecodeError) as e:
                # TypeError: wrong-typed JSON fields (int({}) etc.) —
                # still the client's malformed input, still a 400
                return self._json(400, {"error": str(e)})
            try:
                handle = engine.submit(prompt, params, block=False)
            except EngineDrainingError as e:     # drain: try a peer
                return self._json(503, {"error": str(e)},
                                  retry_after=e.retry_after_s or 1.0)
            except SLOShedError as e:            # deadline unmeetable now
                return self._json(429, {
                    "error": str(e), "shed": True},
                    retry_after=e.retry_after_s or 1.0)
            except QueueFullError:
                return self._json(429, {
                    "error": "request queue full — retry later",
                    "queue_capacity": engine.queue_capacity()},
                    retry_after=engine.estimate_queue_clear_s() or 1.0)
            except PromptTooLongError as e:
                # 413: the client must shorten the payload, not retry
                # it. `max_prompt` is the seq-sharded ceiling on
                # --serve_sp engines (pane x sp).
                return self._json(413, {
                    "error": str(e), "max_prompt": e.limit,
                    "prompt_tokens": e.prompt_tokens})
            except ValueError as e:
                return self._json(400, {"error": str(e)})
            except RuntimeError as e:           # engine is dead
                return self._json(500, {"error": str(e)})
            try:
                handle.result(timeout=request_timeout_s)
            except RequestExpiredError as e:    # deadline shed in queue
                return self._json(504, {"error": str(e), "expired": True},
                                  retry_after=engine.estimate_queue_clear_s())
            except TimeoutError as e:
                # cancel so the slot stops decoding for a client whose
                # handler already gave up (it would otherwise burn the
                # slot to max_new_tokens)
                engine.cancel(handle)
                return self._json(504, {"error": str(e)})
            except RuntimeError as e:           # engine failed the request
                return self._json(500, {"error": str(e)})
            self._json(200, result_record(handle))

    return ThreadingHTTPServer((host, port), Handler)


def serve_http(engine: DecodeEngine, port: int,
               host: str = "127.0.0.1",
               server=None) -> None:
    server = server or make_http_server(engine, port, host=host)
    host, real_port = server.server_address[:2]
    logger.info("Serving on http://%s:%d (POST /generate, GET /healthz); "
                "Ctrl-C to stop.", host, real_port)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        logger.info("Shutting down HTTP server.")
    finally:
        server.server_close()


# ---------------------------------------------------------------------------
# the `serve` run mode (main.py dispatches here)
# ---------------------------------------------------------------------------

def run_serve(args, comps, metric_logger) -> DecodeEngine:
    """Warm the engine and serve --serve_prompts and/or --serve_port.
    ``comps``/``metric_logger`` come from main.py's shared bootstrap
    (metrics sink + compile cache + build_components + run-metadata
    header) so serve telemetry can't diverge from training telemetry.
    Returns the (shut-down) engine for callers/tests — an
    ``EngineRouter`` when ``--serve_replicas > 1``.

    Resilience wiring: SIGTERM/SIGINT trigger a graceful drain
    (``--drain_timeout``; rolling per replica in router mode, with
    queued work re-dispatched); ``--serve_tick_timeout`` arms the fault
    supervisor (hung-tick flight record + bounded-backoff restart);
    ``--stall_timeout`` alone arms just the flight recorder."""
    from building_llm_from_scratch_tpu.serving.kvcache import KVCachePolicy

    prefix_on = getattr(args, "serve_prefix_cache", "off") == "on"
    paged_on = getattr(args, "serve_kv_paged", "off") == "on"
    serve_sp = getattr(args, "serve_sp", 1)
    chunk = getattr(args, "serve_prefill_chunk", 0)
    if (prefix_on or paged_on or serve_sp > 1) and chunk <= 0:
        chunk = 64          # these paths all imply chunked prefill
        logger.info("--serve_%s on: defaulting --serve_prefill_chunk "
                    "to 64.",
                    "prefix_cache" if prefix_on
                    else ("kv_paged" if paged_on else "sp"))
    kv_policy = KVCachePolicy(
        kv_quant=getattr(args, "serve_kv_quant", "model"),
        prefix_cache=prefix_on,
        prefill_chunk=chunk,
        prefix_budget_bytes=int(
            getattr(args, "serve_prefix_budget_mb", 256.0) * 1024 ** 2),
        paged=paged_on,
        page_tokens=getattr(args, "serve_kv_page_tokens", 16),
    )
    n_replicas = getattr(args, "serve_replicas", 1)
    serve_tp = getattr(args, "serve_tp", 1)
    max_prompt = getattr(args, "serve_max_prompt", 0) or None
    n_workers = getattr(args, "serve_workers", 0)
    if n_workers > 0:
        import jax

        if jax.default_backend() != "cpu":
            # this process built the components, so it holds the chip, and
            # a chip belongs to one process: the workers could only die on
            # it or — worse — serve from the CPU under the same metrics
            raise RuntimeError(
                f"--serve_workers starts worker processes that each open "
                f"the accelerator, but this process already holds the "
                f"{jax.default_backend()} device (one process at a time). "
                f"On an accelerator host use --serve_replicas: in-process "
                f"replicas, one per chip.")
        # cross-process fleet (serving/fleet.py): N supervised worker
        # PROCESSES behind one engine-shaped facade. Workers rebuild
        # cfg + params from the spec (init_params is seed-deterministic;
        # --init_params_from loads the same artifact in every process),
        # so the parent's params never cross the process boundary — and
        # a worker crash can only ever take down its own replica.
        from building_llm_from_scratch_tpu.serving.fleet import (
            ProcessFleet,
        )
        from building_llm_from_scratch_tpu.serving.worker import (
            EngineSpec,
        )

        adapter_paths = (parse_adapter_specs(args.serve_adapters)
                         if getattr(args, "serve_adapters", None)
                         else None)
        spec = EngineSpec(
            model=args.model, size=args.num_params,
            dtype=args.data_type, debug=args.debug, seed=args.seed,
            init_params_from=getattr(args, "init_params_from", None),
            tokenizer=("byte" if args.byte_tokenizer else "none"),
            tp=serve_tp,
            engine=dict(
                n_slots=args.serve_slots,
                max_len=(args.serve_max_len or None),
                max_queue=args.serve_max_queue,
                max_top_k=args.serve_max_top_k,
                default_max_new_tokens=args.serve_max_new_tokens,
                default_deadline_s=(args.serve_deadline_s or None),
                tick_timeout_s=args.serve_tick_timeout,
                max_restarts=args.serve_max_restarts,
                metrics_every=args.serve_metrics_every,
                max_prompt=max_prompt),
            kv_policy=dict(
                kv_quant=kv_policy.kv_quant,
                prefix_cache=kv_policy.prefix_cache,
                prefill_chunk=kv_policy.prefill_chunk,
                prefix_budget_bytes=kv_policy.prefix_budget_bytes,
                paged=kv_policy.paged,
                page_tokens=kv_policy.page_tokens),
            adapters=adapter_paths,
            spec_k=getattr(args, "serve_spec_k", 0),
        )
        fleet = ProcessFleet(
            spec, n_workers, tokenizer=comps.tokenizer,
            max_restarts=args.serve_max_restarts,
            drain_timeout_s=args.drain_timeout,
            default_max_new_tokens=args.serve_max_new_tokens,
            metrics_base=metric_logger.jsonl_path)
        fleet.start()
        return _serve_frontends(args, fleet, [], metric_logger)
    if n_replicas > 1:
        # fleet tier (serving/router.py): N engine replicas — each on
        # its own mesh plan (tp devices apiece, disjoint when the pool
        # allows) with its own adapter registry — behind one router
        # surface. The frontends below bind the router exactly like an
        # engine. The 1-replica branch stays the historical path: no
        # router object exists there at all.
        from building_llm_from_scratch_tpu.serving.router import (
            EngineRouter,
        )

        specs = (parse_adapter_specs(args.serve_adapters)
                 if getattr(args, "serve_adapters", None) else None)
        engine = EngineRouter.build(
            comps.cfg, comps.params, comps.tokenizer,
            n_replicas=n_replicas, tp=serve_tp, sp=serve_sp,
            max_prompt=max_prompt,
            adapter_specs=specs,
            adapter_capacity=args.serve_adapter_slots,
            kv_policy=kv_policy,
            n_slots=args.serve_slots,
            max_len=(args.serve_max_len or None),
            max_queue=args.serve_max_queue,
            max_top_k=args.serve_max_top_k,
            default_max_new_tokens=args.serve_max_new_tokens,
            default_deadline_s=(args.serve_deadline_s or None),
            tick_timeout_s=args.serve_tick_timeout,
            max_restarts=args.serve_max_restarts,
            metrics_every=args.serve_metrics_every,
            spec_k=getattr(args, "serve_spec_k", 0),
        )
        stalls = []
        if args.stall_timeout > 0:
            # same semantics as the single-engine path: without the full
            # supervisor, each replica gets its OWN flight recorder (a
            # shared one would stay silent while healthy replicas tick
            # past a wedged one)
            from building_llm_from_scratch_tpu.serving.supervisor import (
                make_serve_stall_detector,
            )

            for rep in engine.engines:
                if rep.supervisor is None:
                    det = make_serve_stall_detector(args.stall_timeout)
                    rep.set_heartbeat(det.notify_step)
                    stalls.append(det)
        engine.warmup()
        engine.start()
        for det in stalls:
            det.start()
        return _serve_frontends(args, engine, stalls, metric_logger)

    adapters = None
    if getattr(args, "serve_adapters", None):
        # --serve_adapters name=path[,name=path...]: build the multi-
        # tenant LoRA registry before the engine compiles (the pool's
        # static capacity/rank are baked into the decode program)
        from building_llm_from_scratch_tpu.serving.adapters import (
            AdapterRegistry,
        )

        specs = parse_adapter_specs(args.serve_adapters)
        adapters = AdapterRegistry.from_artifacts(
            comps.cfg, comps.params, specs,
            capacity=args.serve_adapter_slots)
        logger.info("Adapter registry: %d adapter(s) loaded (%s), "
                    "capacity %d.", adapters.n_loaded,
                    ", ".join(adapters.names()), adapters.capacity)

    mesh_plan = None
    if serve_tp > 1 or serve_sp > 1:
        # single sharded replica: tp shards the whole compiled program
        # family (NamedSharding'd weights + heads-sharded slot KV over
        # the `model` mesh axis); sp sequence-shards chunk prefill over
        # the `seq` axis so long prompts admit beyond one device's pane
        # (parallel/sharding.serve_mesh_plan — the two compose)
        from building_llm_from_scratch_tpu.parallel.sharding import (
            serve_mesh_plan,
        )

        mesh_plan = serve_mesh_plan(serve_tp, sp=serve_sp)
    engine = DecodeEngine(
        comps.cfg, comps.params, comps.tokenizer,
        n_slots=args.serve_slots,
        max_len=(args.serve_max_len or None),
        max_queue=args.serve_max_queue,
        max_top_k=args.serve_max_top_k,
        default_max_new_tokens=args.serve_max_new_tokens,
        default_deadline_s=(args.serve_deadline_s or None),
        tick_timeout_s=args.serve_tick_timeout,
        max_restarts=args.serve_max_restarts,
        metrics_every=args.serve_metrics_every,
        adapters=adapters,
        kv_policy=kv_policy,
        spec_k=getattr(args, "serve_spec_k", 0),
        mesh_plan=mesh_plan,
        max_prompt=max_prompt,
    )
    stall = None
    if args.stall_timeout > 0 and engine.supervisor is None:
        # flight recorder without the supervisor: a hung tick still dumps
        # every thread's stack + device memory (obs/stall.py), it just
        # isn't auto-restarted
        from building_llm_from_scratch_tpu.serving.supervisor import (
            make_serve_stall_detector,
        )

        stall = make_serve_stall_detector(args.stall_timeout)
        engine.set_heartbeat(stall.notify_step)
    engine.warmup()
    engine.start()
    if stall is not None:
        stall.start()
    return _serve_frontends(args, engine,
                            [stall] if stall is not None else [],
                            metric_logger)


def _serve_frontends(args, engine, stalls, metric_logger):
    """Drive the frontends (JSONL pump and/or HTTP) + signal-drain wiring
    over one warmed, started ``engine`` — a ``DecodeEngine`` or an
    ``EngineRouter``; both expose the surface this loop needs (submit/
    drain/shutdown/draining/healthz/metrics). ``stalls``: already-started
    flight recorders to stop on exit (one per replica in router mode)."""
    from building_llm_from_scratch_tpu.training.resilience import (
        GracefulStopper,
    )

    server = (make_http_server(engine, args.serve_port,
                               host=args.serve_host)
              if args.serve_port else None)
    stopper = GracefulStopper()
    drained = threading.Event()

    def _drain_on_signal():
        # poll the stopper flag (the handler itself must stay tiny and
        # async-signal-safe); on preemption: close admission, finish
        # in-flight within --drain_timeout, then unblock the frontends
        while not drained.wait(0.1):
            if stopper.requested:
                engine.drain(timeout=args.drain_timeout)
                if server is not None:
                    server.shutdown()
                return

    watcher = threading.Thread(target=_drain_on_signal,
                               name="serve-drain-watch", daemon=True)
    try:
        with stopper:
            watcher.start()
            http_thread = None
            if server is not None and args.serve_prompts:
                # both workloads: HTTP serves CONCURRENTLY with the JSONL
                # pump — a /metrics scrape or /generate call must not
                # queue behind the batch (the engine is thread-safe; the
                # drain path shuts the server down via server.shutdown())
                http_thread = threading.Thread(
                    target=serve_http, name="serve-http", daemon=True,
                    args=(engine, args.serve_port),
                    kwargs=dict(host=args.serve_host, server=server))
                http_thread.start()
            if args.serve_prompts:
                serve_jsonl(engine, args.serve_prompts, args.serve_out,
                            args.serve_max_new_tokens)
            if http_thread is not None:
                http_thread.join()      # until SIGTERM/SIGINT stops it
            elif server is not None:
                serve_http(engine, args.serve_port, host=args.serve_host,
                           server=server)
    finally:
        drained.set()
        watcher.join(timeout=5)
        if stopper.requested and not engine.draining:
            engine.drain(timeout=args.drain_timeout)
        died = engine.healthz_payload()["status"] == "dead"
        engine.shutdown()
        for det in stalls:
            det.stop()
        metric_logger.close()
    if died:
        # every in-flight request already carries its error line; the
        # PROCESS must not report success for an engine whose loop died
        # (a step that failed to compile or run)
        raise RuntimeError("the serving engine died while serving; see the "
                           "'decode-engine loop died' traceback above")
    return engine
