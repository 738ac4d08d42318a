"""The telemetry schema registry: every event kind, span shape, and
phase/segment table the JSONL sink may emit — declared ONCE, here.

Before this module existed the schema lived in three places at once: the
emitting call sites (``MetricLogger.event(...)`` kwargs scattered over a
dozen modules), ``obs/trace.py``'s rendering tables, and a pinned fallback
copy inside ``scripts/summarize_metrics.py``. PR 7's review caught exactly
the failure mode that layout invites — a drift-prone private copy of
``TICK_PHASES`` — so consumers now import from here and the GL04x
telemetry lint (``analysis/telemetry.py``) checks every ``.event(...)``
call site against this registry: adding a field or an event kind without
declaring it is a lint failure, not a review catch.

Stdlib-only and import-free (no jax, no numpy): the static analyzer, the
renderer script and the trace exporter all load it without touching the
accelerator stack.

To register a new event kind:

  1. add an ``EventSpec`` to ``EVENTS`` below (required fields are the
     ones every emission must carry; ``open_fields=True`` admits dynamic
     payloads like ``watchdog_halt``'s health context);
  2. emit it with ``get_metrics().event("kind", ...)`` /
     ``obs.metrics.emit_event`` — ``scripts/lint_graft.py`` verifies the
     call site against the spec;
  3. if the trace exporter should render it, add it to
     ``INCIDENT_EVENTS`` / ``REQUEST_EVENTS`` (subsets of the registry —
     test-asserted).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, FrozenSet, List

#: Bump when a row type or a load-bearing field changes meaning. The
#: ``header`` row carries it; consumers key parsing decisions on it.
SCHEMA_VERSION = 18         # v18: serve_warmup gains state_step; the
                            # tick record's state_rows_touched counts
                            # what the step did touch (the decoding
                            # rows in a layer that walks them)
                            # (v17: the books of set-up: the memory-only
                            # `program` and `setup` records, a `setup`
                            # span root, compile gains trace_seconds
                            # and cache, serve_warmup gains programs)
                            # (v16: serve_warmup gains selective_scan)
                            # (v15: serve_warmup gains expert_dispatch)
                            # (v14: recurrent state beside keys and
                            # values — serve_warmup gains
                            # linear_attention, the tick record
                            # state_rows / state_rows_touched)
                            # (v13: long-context tier — prefill_shard
                            # tick phase (seq-sharded chunk prefill,
                            # --serve_sp), serve_warmup gains
                            # sp / prompt_pane_tokens / max_prompt,
                            # request_done gains long_prompt)
                            # (v12: paged KV cache — page_admit /
                            # page_share / page_release /
                            # page_pool_exhausted events (serving page
                            # pool: refcounted shared pages + page-table
                            # attention), serve_warmup gains
                            # kv_paged / page_tokens / pool_pages
                            # (v11: memory observatory — memory_snapshot /
                            # memory_pressure / memory_drift events
                            # (obs/memory.py MemoryLedger: byte-exact
                            # component ledger + drift/pressure
                            # detection), request_done gains
                            # kv_bytes_peak + prefix_bytes_saved
                            # (v10: fleet observatory — clock_sync /
                            # incident_snapshot events, worker_request +
                            # rpc span roots (worker-side trees stamped
                            # with pid/incarnation), worker_* events
                            # rendered on the incidents trace track)
                            # (v9: cross-process fleet — worker_spawn /
                            # worker_heartbeat_missed / worker_dead /
                            # worker_restart / pane_handoff events
                            # serving/fleet.py supervision + prefix-
                            # pane handoff over the RPC transport)
                            # (v8: scale-out serving — serve_fleet /
                            # replica_drain / replica_restart /
                            # router_redispatch events, `replica` label
                            # on engine-scoped events + span rows,
                            # `router` request-span child)

#: JSONL row discriminators (the ``type`` field).
ROW_TYPES = ("header", "metrics", "health", "event", "span")

#: Engine tick phases, in within-tick order (serving/engine.py accumulates
#: wall-clock per phase and logs the sums at its metrics cadence as
#: ``tick_<phase>_s`` fields; /metrics exports ``tick_<phase>_seconds``).
#: ``prefix_copy`` is the KV memory engine's pane traffic (prefix-hit
#: copies + post-prefill pane extraction, serving/kvcache.py).
#: ``draft`` is the speculative drafter's host-side proposal time
#: (serving/spec.py; identically 0 on spec-off engines).
#: ``prefill_shard`` is chunk prefill on a sequence-sharded mesh
#: (``--serve_sp``): the same chunk pump, booked under its own phase so
#: the long-context share of tick wall is visible (identically 0 on
#: non-sp engines, like ``draft``).
TICK_PHASES = ("admit", "prefix_copy", "prefill", "prefill_shard", "draft",
               "decode_dispatch", "host_fetch", "sample_commit",
               "callback_detok")

#: Each tick phase's name in a profiler trace (``jax.profiler``
#: ``TraceAnnotation``, opened by the engine's ``StepTimeline``): prefixed,
#: so none can be mistaken for a span of the runtime, and short, because a
#: device-idle gap is labelled ``<host span>___after_<program>_before_
#: <program>`` and cut at 64 characters (benchmark/trace.py). Every tick is
#: one ``StepTraceAnnotation`` named ``TICK_STEP``; the engine thread's time
#: outside ``step()`` lies in ``TICK_BETWEEN`` (heartbeats, the cadence
#: row) or ``TICK_IDLE_WAIT`` (nothing to do).
TICK_SPANS = {"admit": "tick.admit", "prefix_copy": "tick.pfx_copy",
              "prefill": "tick.prefill", "prefill_shard": "tick.pf_shard",
              "draft": "tick.draft", "decode_dispatch": "tick.dispatch",
              "host_fetch": "tick.fetch", "sample_commit": "tick.commit",
              "callback_detok": "tick.callback"}
TICK_STEP = "tick"
TICK_BETWEEN = "tick.between"
TICK_IDLE_WAIT = "tick.idle_wait"

#: One memory-only record per tick (``get_metrics().recent("tick")``;
#: serving/engine.py ``_book_tick``), numbers only. ``t0``/``t1`` bound the
#: timed part of ``step()``; ``t_dispatch``/``t_fetch`` are the ends of
#: ``decode_dispatch`` and ``host_fetch`` (absent where the tick ran no
#: decode program): from one tick's ``t_fetch`` to the next's
#: ``t_dispatch`` the device has no decode queued. All four are
#: ``time.perf_counter`` readings, which on Linux is the clock of
#: ``time.monotonic`` (CLOCK_MONOTONIC) that stamps requests, so a span
#: row's ``t_submit`` lies on the same axis; ``wall_submit`` on the span
#: row is the one anchor to unix time. ``phases`` holds the self seconds
#: of each phase that ran; ``tick`` is ``n_ticks`` after the tick; ``rows``
#: of the program's ``n_slots`` rows decoded a token, after ``chunks`` prefill
#: chunks of slots in mid-prefill (chunked prefill only, absent at 0) that
#: held ``chunk_tokens`` real tokens (their padding left out) and whose
#: attention read ``chunk_kv_touched`` key positions (summed over the layers:
#: the live key blocks in a layer on the chunk kernel's path, whole buffers in
#: any other), reading
#: ``kv_positions`` cache positions (live positions of those rows, summed
#: over the layers, a window layer counting at most its window) and touching
#: ``kv_touched`` (the positions the program's attention did read, every row
#: of it: block-rounded lengths in a layer on the live-block kernel's path,
#: whole buffers in any other; ``kv_positions / kv_touched`` is the live share
#: of what was read). A sparse model's
#: decode tick adds ``expert_rows`` (rows each held expert computed, summed
#: over layers) and ``experts_touched`` (held experts, counted a layer,
#: that got a row: each read its weights once). A model with 'linear' or
#: 'ssm' layers adds ``state_rows`` (decoding rows x those layers: the recurrent
#: states the tick had to read and write) and ``state_rows_touched`` (those
#: its steps did read and write: the decoding rows' in a layer whose step
#: walks them, ``state_step_path``, every row's in any other).
TICK_RECORD_FIELDS = ("tick", "t0", "t1", "t_dispatch", "t_fetch", "phases",
                      "rows", "n_slots", "admitted", "queue_depth",
                      "replica", "chunks", "chunk_tokens", "chunk_kv_touched",
                      "kv_positions", "kv_touched",
                      "expert_rows", "experts_touched",
                      "state_rows", "state_rows_touched")

#: One memory-only record per program the process builds
#: (``get_metrics().recent("program")``; obs/compile.py): ``label`` is the
#: ``CompileWatcher``'s, or JAX's ``fun_name`` for a program no watcher wraps
#: (``watched`` false: an eager ``convert_element_type``, a request's
#: ``_threefry_seed``). ``t_end`` is a ``time.perf_counter`` reading (the
#: clock of the tick records and of a span row's ``t_submit``), ``time`` unix
#: seconds (the clock of every row of the hub). ``trace_s`` (the Python
#: function run into a jaxpr: JAX's own report of the outermost trace, every
#: nested ``jit``'s lying inside it, so the longest and never the sum; None
#: for a program no watcher wraps), ``lower_s`` (the jaxpr to StableHLO,
#: each pallas kernel's body to Mosaic in it) and ``load_or_compile_s``
#: (``.compile()``: a load from the persistent cache or the compiler) are
#: wall seconds; ``cache`` is ``hit`` / ``miss`` by JAX's own
#: ``/jax/compilation_cache`` events, ``off`` where the build asked no
#: cache; a hit adds ``retrieval_s``, the part of the load that read the
#: entry. ``thread`` names the thread that built it (an engine's loop, a
#: trainer's, a caller's of its own).
PROGRAM_RECORD_FIELDS = ("label", "t_end", "time", "trace_s", "lower_s",
                         "load_or_compile_s", "cache", "watched",
                         "retrieval_s", "thread")

#: One memory-only record per engine or trainer
#: (``get_metrics().recent("setup")``, and the ``setup`` span row's twin):
#: the set-up timeline (obs/timeline.py ``SetupTimeline``) drained once,
#: when the engine has started or the trainer has made its first blocking
#: fetch. ``t0`` / ``t_end`` are ``time.perf_counter`` readings, ``time``
#: is ``t0`` in unix seconds, ``wall_s`` their distance and ``self_s`` the
#: part of it no phase covers (what the caller did between the phases).
#: ``spans`` lists every span in the order it opened: ``name``, ``t0``,
#: ``dur_s``, ``self_s`` (its duration less the spans inside it) and
#: ``depth`` (0: a phase of ``SETUP_PHASES``); self seconds, the root's
#: with them, sum to ``wall_s``. ``source`` is ``serve`` or ``train``.
SETUP_RECORD_FIELDS = ("source", "t0", "t_end", "time", "wall_s", "self_s",
                       "spans", "replica")

#: The phases of set-up (depth 0 of a ``setup`` record). Serving: ``init``
#: is ``DecodeEngine.__init__`` (inside it ``weights_layout``: the
#: per-layer copy of a dense model's stacked weights, and ``cache_alloc``),
#: ``warmup`` is ``warmup()`` (inside it one ``build:<label>`` a program
#: call, from the call to its return, and ``first_runs``: from the last
#: call's return to the fetch that ends warm-up), ``start`` is
#: ``start()``. Training: ``init`` is the trainer's construction of its
#: state, optimiser and steps, ``build:train_step`` the first step's trace,
#: lowering and compile, ``first_runs`` from there to the end of the
#: first blocking fetch.
SETUP_PHASES = {"serve": ("init", "warmup", "start"),
                "train": ("init", "build:train_step", "first_runs")}
SETUP_BUILD_PREFIX = "build:"

#: Trainer StepTimeline segments (``<segment>_s`` fields of training
#: cadence metrics rows; obs/timeline.py owns the measurement).
TRAIN_SEGMENTS = ("data_wait", "dispatch", "host_fetch", "eval", "sample",
                  "checkpoint")

#: Event kinds rendered as instants on the trace's incidents track.
#: The worker-process lifecycle kinds joined in v10 so the fleet
#: exporter (obs/fleetview.py) and the single-file exporter render the
#: same death/restart instants without a second table.
#: ``memory_pressure``/``memory_drift`` joined in v11: a near-OOM
#: crossing or a ledger leak is an incident the timeline must show next
#: to the tick phases (``memory_snapshot`` is NOT here — it renders as a
#: counter track, not an instant).
INCIDENT_EVENTS = ("engine_restart", "drain", "serve_error", "stall",
                   "watchdog_halt", "preemption_signal", "preemption_stop",
                   "checkpoint_fallback", "serve_warmup",
                   "worker_spawn", "worker_heartbeat_missed", "worker_dead",
                   "worker_restart", "pane_handoff", "incident_snapshot",
                   "memory_pressure", "memory_drift")

#: Request-lifecycle event kinds pinned to the request's own trace track.
REQUEST_EVENTS = ("request_done", "request_rejected", "request_shed",
                  "request_expired", "request_failed")

#: Lifecycle event kinds that open the serving section of the renderer
#: even when zero requests completed (incident runs). Worker-process
#: births/deaths qualify: a fleet run where a worker died before any
#: request finished is exactly an incident file the section must explain.
SERVING_LIFECYCLE_EVENTS = ("engine_restart", "drain", "serve_error",
                            "worker_spawn", "worker_dead", "worker_restart")

#: Root span names the ``span`` row type may carry (one tree per row).
#: ``request`` is the router-side tree (one per request, emitted at the
#: terminal outcome whatever it was — worker_dead included).
#: ``worker_request`` is the worker-process-side view of the same
#: request (same ``request_id``, stamped with pid/incarnation).
#: ``rpc`` is one server-side RPC handle (method + request_id), so the
#: merged timeline can show client wait vs server handle per hop.
#: ``setup`` is the set-up timeline of one engine or trainer, its spans
#: flattened into the children (``SETUP_RECORD_FIELDS``).
SPAN_NAMES = ("request", "worker_request", "rpc", "setup")

#: Child span names under a ``request`` root, in lifecycle order.
#: ``router`` (fleet dispatch hop, serving/router.py) only appears on
#: routed requests — single-engine span trees are unchanged.
REQUEST_SPAN_PHASES = ("router", "queued", "prefill", "decode")


@dataclass(frozen=True)
class EventSpec:
    """Declared shape of one ``event`` row kind.

    ``required``: every emission must carry these fields. ``optional``:
    fields an emission may carry. ``open_fields``: the payload includes
    dynamic keys (health context, stats dicts) — unknown fields are then
    legal, but the declared ones still document the stable core.
    """

    name: str
    required: FrozenSet[str] = frozenset()
    optional: FrozenSet[str] = frozenset()
    open_fields: bool = False
    doc: str = ""

    def known_fields(self) -> FrozenSet[str]:
        return self.required | self.optional | ALWAYS_ALLOWED_FIELDS


#: Fields every event row may carry regardless of kind (``event()`` adds
#: ``step`` itself; ``type``/``time``/``event`` are the row envelope).
ALWAYS_ALLOWED_FIELDS = frozenset({"step", "type", "time", "event"})


def _spec(name: str, required=(), optional=(), open_fields=False,
          doc: str = "") -> EventSpec:
    return EventSpec(name, frozenset(required), frozenset(optional),
                     open_fields, doc)


_EVENT_LIST: List[EventSpec] = [
    # -- run lifecycle ----------------------------------------------------
    _spec("components_built",
          optional=("model", "n_params", "est_train_mem_gb",
                    "flops_per_token_analytic", "shard_mode",
                    "load_weights", "prefetch", "async_ckpt",
                    "tokenizer_cache"),
          doc="model/optimizer/loader built; records the run's shape"),
    _spec("run_complete", optional=("tokens_seen", "final_train_loss"),
          doc="training main() reached its normal end"),
    # -- fetch / retry ----------------------------------------------------
    _spec("hf_fetch", required=("repo",),
          optional=("files", "bytes", "cached", "seconds"),
          doc="HF hub download (downloaded vs cached bytes split)"),
    _spec("retry", required=("describe",),
          optional=("error", "attempt", "attempts", "delay_s"),
          doc="bounded-retry attempt (utils/retry.py)"),
    _spec("tokenize_cache", required=("file", "source"),
          optional=("tokens", "seconds"),
          doc="TokenCache hit/encode (source: memory|disk|encoded)"),
    # -- compile telemetry ------------------------------------------------
    _spec("compile", required=("label",),
          optional=("compile_seconds", "trace_seconds", "lower_seconds",
                    "backend_compile_seconds", "cache",
                    "cache_retrieval_seconds", "executable_device_count",
                    "flops", "flops_per_device", "transcendentals",
                    "bytes_accessed", "memory", "n_compiles",
                    "tokens_per_step", "hbm_capacity_bytes",
                    "hbm_budget_frac", "cache_dir", "cache_entries",
                    "cache_hit"),
          doc="one AOT compile capture (obs/compile.py); lower_seconds "
              "holds trace_seconds; cache is hit|miss|off by JAX's own "
              "events, cache_hit the guess from the directory's count"),
    _spec("recompile", required=("label",),
          optional=("n_recompiles", "n_changed_leaves", "diff"),
          doc="argument-signature change after the legitimate set closed"),
    _spec("compile_fallback", required=("label",), optional=("error",),
          doc="AOT capture failed; telemetry fell back to plain jit"),
    # -- checkpoints ------------------------------------------------------
    _spec("checkpoint_save", required=("path",),
          optional=("seconds", "bytes", "leaves", "writer"),
          doc="one durable checkpoint commit (sync or async writer)"),
    _spec("checkpoint_restore", required=("path",),
          optional=("seconds", "leaves"),
          doc="checkpoint loaded into the train state"),
    _spec("checkpoint_fallback", required=("path", "reason"),
          doc="--resume auto skipped an invalid checkpoint"),
    _spec("checkpoint_gc", optional=("removed", "keep"),
          doc="--keep_ckpts retention GC removed old checkpoints"),
    _spec("ckpt_async_save", required=("path",),
          optional=("snapshot_s", "write_s", "overlap_s"),
          doc="async checkpoint: snapshot/write/overlap seconds"),
    # -- resilience -------------------------------------------------------
    _spec("preemption_signal", required=("signal",),
          doc="SIGTERM/SIGINT observed; stop at next step boundary"),
    _spec("preemption_stop", optional=("tokens_seen",),
          doc="graceful stop checkpoint written at the step boundary"),
    _spec("watchdog_halt", required=("reason",),
          optional=("loss", "recent", "median", "spike_factor"),
          open_fields=True,
          doc="loss watchdog halt (+ dynamic per-layer health context)"),
    _spec("stall", optional=("elapsed_s", "threshold_s", "memory"),
          doc="flight recorder fired: stacks + device memory dumped"),
    # -- serving: request lifecycle ---------------------------------------
    _spec("request_done", required=("request_id",),
          optional=("n_prompt_tokens", "n_tokens", "finish_reason", "slot",
                    "deadline_s", "queue_wait_s", "ttft_s", "tpot_s",
                    "e2e_s", "adapter", "spec_drafted", "spec_accepted",
                    "kv_bytes_peak", "prefix_bytes_saved", "long_prompt",
                    "replica"),
          doc="one request completed normally (latency summary; "
              "spec_drafted/spec_accepted = this request's speculative "
              "acceptance ledger on --serve_spec_k engines; "
              "kv_bytes_peak = the slot KV bytes the request occupied at "
              "its longest; prefix_bytes_saved = KV bytes prefix-cache "
              "hits spared it from recomputing; long_prompt = the prompt "
              "exceeded one device's pane on a --serve_sp engine, so "
              "prefill ran sequence-sharded)"),
    _spec("request_rejected", required=("request_id", "reason"),
          optional=("queue_depth", "replica"),
          doc="bounded queue at capacity at submit (HTTP 429)"),
    _spec("request_shed", required=("request_id", "reason"),
          optional=("queue_depth", "deadline_s", "estimated_e2e_s",
                    "retry_after_s", "replica"),
          doc="SLO-predicted deadline miss rejected at submit"),
    _spec("request_expired", required=("request_id", "reason"),
          optional=("deadline_s", "queue_wait_s", "queue_depth", "replica"),
          doc="deadline passed while queued (TTL shed, HTTP 504)"),
    _spec("request_failed", required=("request_id", "reason"),
          optional=("error", "slot", "n_tokens", "adapter", "replica"),
          doc="one request failed in isolation (or engine death/restart)"),
    # -- serving: multi-tenant LoRA adapters ------------------------------
    _spec("adapter_save", required=("path",),
          optional=("rank", "alpha", "n_params", "fingerprint", "job_id"),
          doc="finetuning exported a LoRA adapter artifact "
              "(--save_adapter, or a fused-fleet job finishing — then "
              "job_id names the tenant whose deployment just unblocked)"),
    # -- fused multi-LoRA training (training/lora_fusion.py) ---------------
    _spec("finetune_job_start", required=("job_id",),
          optional=("slot", "total_steps", "n_records", "n_epochs",
                    "rows_per_step"),
          doc="a fleet job hot-joined a free slot (identity is data: "
              "joining never recompiles the fused step)"),
    _spec("finetune_job_done", required=("job_id",),
          optional=("steps", "final_loss", "artifact", "deployed",
                    "seconds"),
          doc="a fleet job completed: its adapter exported at JOB "
              "finish (slow co-tenants don't block it) and optionally "
              "hot-loaded into the deploy registry"),
    _spec("finetune_job_failed", required=("job_id", "reason"),
          optional=("slot", "steps", "loss", "grad_norm"),
          doc="a fleet job retired in isolation (non-finite training "
              "signal; its in-graph updates were already skipped, "
              "co-trained jobs bit-identical)"),
    _spec("finetune_fleet", required=("phase",),
          optional=("n_jobs", "capacity", "rank", "alpha", "rows_per_job",
                    "jobs_done", "jobs_failed", "seconds",
                    "flops_per_token_base", "flops_per_token_adapter"),
          doc="fleet run bracketing (phase: start|end) + the analytic "
              "base-vs-adapter FLOPs split the renderer reports"),
    _spec("adapter_load", required=("name",),
          optional=("path", "row", "rank", "alpha", "seconds",
                    "n_loaded", "capacity"),
          doc="registry hot-loaded an adapter into a pool row "
              "(zero recompiles — same pool shapes)"),
    _spec("adapter_evict", required=("name",),
          optional=("row", "n_loaded"),
          doc="registry unloaded an adapter (row reused only once no "
              "active slot references it)"),
    # -- serving: KV-cache memory engine ----------------------------------
    _spec("prefix_hit", required=("request_id",),
          optional=("span_tokens", "prompt_tokens", "key",
                    "n_suffix_chunks", "adapter", "late", "replica"),
          doc="a stored prefix matched: its panes were copied into the "
              "slot (zero forward FLOPs for the cached span). late=True "
              "is the mid-prefill catch-up hit — a co-admitted sharer "
              "jumping ahead on a pane stored after its admission"),
    _spec("prefix_miss", required=("request_id",),
          optional=("prompt_tokens", "adapter", "replica"),
          doc="no stored prefix matched; the prompt prefills in full "
              "(and its chunk-aligned prefix is stored for successors)"),
    _spec("prefix_evict", required=("key",),
          optional=("bytes", "span_tokens", "hits", "age_s",
                    "entries_left", "bytes_left"),
          doc="LRU eviction under the prefix store's byte budget "
              "(pinned entries are never evicted)"),
    _spec("prefix_insert", required=("request_id",),
          optional=("span_tokens", "bytes", "entries", "adapter", "replica"),
          doc="a completed prefill's chunk-aligned prefix pane entered "
              "the store"),
    # -- serving: paged KV (page pool + page-table attention) --------------
    _spec("page_admit", required=("request_id",),
          optional=("slot", "pages_reserved", "pool_free", "replica"),
          doc="paged admission reserved the request's worst-case page "
              "need from the pool (admission gates on free pages, not "
              "free slots)"),
    _spec("page_share", required=("request_id",),
          optional=("slot", "n_pages", "span_tokens", "late", "pool_free",
                    "replica"),
          doc="a paged prefix hit: the slot's table now references the "
              "stored entry's shared refcounted pages — zero pane-copy "
              "bytes, zero forward FLOPs for the span"),
    _spec("page_release", required=("slot",),
          optional=("n_pages", "pages_freed", "pages_unreserved",
                    "pool_free", "replica"),
          doc="slot retirement decrefed its table columns (shared pages "
              "survive under the store/co-sharers) and returned the "
              "unused reservation to the pool"),
    _spec("page_pool_exhausted", required=("request_id",),
          optional=("pages_needed", "pages_available", "replica"),
          doc="paged admission refused the queue head: the pool cannot "
              "cover its worst-case need — the request re-queues at the "
              "front and retries after the next release (one event per "
              "exhaustion episode)"),
    # -- perf observatory -------------------------------------------------
    _spec("bench_result", required=("name",),
          optional=("metric", "value", "unit", "n_repeats", "quick",
                    "fingerprint_sha"),
          doc="one BenchResult landed (obs/perf.py): a bench arm's "
              "metrics JSONL records what it measured, so the perf "
              "gate's differential diagnosis can join telemetry to "
              "the bench row it belongs to"),
    # -- serving: engine lifecycle ----------------------------------------
    _spec("serve_warmup",
          optional=("n_prefill_buckets", "buckets", "seconds", "n_slots",
                    "max_len", "kv_quant", "prefix_cache", "prefill_chunk",
                    "kv_bytes_per_slot", "prefix_pane_tokens", "spec_k",
                    "drafter", "replica", "kv_paged", "page_tokens",
                    "pool_pages", "sp", "prompt_pane_tokens", "max_prompt",
                    "kv_append", "decode_attention", "chunk_attention",
                    "linear_attention", "selective_scan", "state_step",
                    "expert_dispatch", "programs"),
          doc="prefill programs + decode (or spec verify) program "
              "compiled; watchers frozen; records the KVCachePolicy "
              "(quant/chunk/prefix), which append and which attention the "
              "tick program was built with (kv_append, decode_attention) "
              "and which attention the chunk program (chunk_attention), "
              "how each program's rows reach the held experts "
              "(expert_dispatch), "
              "what each program the process built so far cost (programs: "
              "label, trace_s, lower_s, load_or_compile_s, cache), "
              "the speculative config "
              "(spec_k/drafter) when on, and the seq-sharded prefill "
              "geometry (sp/prompt_pane_tokens/max_prompt) on "
              "--serve_sp engines"),
    _spec("serve_summary", open_fields=True,
          doc="shutdown stats snapshot (histogram percentiles, counters)"),
    _spec("serve_error", required=("error",),
          optional=("n_failed", "failed_request_ids", "replica"),
          doc="engine died; every in-flight/queued request failed"),
    _spec("engine_restart", required=("reason",),
          optional=("detail", "n_restart", "max_restarts", "backoff_s",
                    "n_inflight_failed", "failed_request_ids",
                    "queue_depth", "replica"),
          doc="supervisor abandoned a wedged loop and restarted it"),
    # -- serving: fleet tier (serving/router.py) ---------------------------
    _spec("serve_fleet", required=("phase",),
          optional=("n_replicas", "tp", "sp", "disjoint_devices",
                    "n_adapters", "seconds"),
          doc="router lifecycle bracketing (phase: build|end): replica "
              "count, tensor-parallel x sequence-parallel degrees, "
              "whether replicas got disjoint device slices"),
    _spec("replica_drain", required=("replica", "phase"),
          optional=("timeout_s", "n_active", "queue_depth",
                    "n_redispatched", "n_preempted", "seconds"),
          doc="one replica drained out of the fleet (phase: start|end); "
              "its queued work re-dispatched onto live replicas"),
    _spec("replica_restart", required=("replica",),
          optional=("seconds",),
          doc="a drained/dead replica re-entered dispatch as a fresh "
              "engine (its own warmup compiles, then frozen watchers)"),
    _spec("router_redispatch", required=("request_id",),
          optional=("from_replica", "to_replica", "adapter"),
          doc="one queued request moved between replicas during a "
              "replica drain — same Request handle, zero client impact"),
    # -- serving: cross-process fleet (serving/fleet.py) -------------------
    _spec("worker_spawn", required=("replica", "pid"),
          optional=("restarts", "seconds"),
          doc="a supervised worker process came up and passed its ready "
              "handshake (restarts counts prior incarnations)"),
    _spec("worker_heartbeat_missed", required=("replica",),
          optional=("age_s", "timeout_s", "pid"),
          doc="a live worker went silent past the heartbeat timeout — "
              "the supervisor kills it (the death path follows)"),
    _spec("worker_dead", required=("replica", "reason"),
          optional=("pid", "queued_redispatched", "inflight_failed",
                    "restarts"),
          doc="a worker process died (reason: pipe_eof|exit_N|"
              "heartbeat_missed|events_lost): queued work re-dispatched "
              "onto survivors, in-flight failed typed"),
    _spec("worker_restart", required=("replica", "restarts"),
          optional=("backoff_s", "downtime_s", "pid"),
          doc="the supervisor restarted a dead worker's PROCESS after "
              "exponential backoff; it re-enters dispatch"),
    _spec("pane_handoff", required=("from_replica", "to_replica"),
          optional=("entries", "imported", "bytes", "seconds"),
          doc="a draining worker's hot PrefixStore panes shipped over "
              "the transport to an adopting replica (keys are config-"
              "fingerprinted, so they transfer verbatim)"),
    _spec("clock_sync", required=("replica", "offset_s", "uncertainty_s"),
          optional=("rtt_s", "incarnation", "pid", "source", "n_samples"),
          doc="NTP-style worker-clock offset estimate from an RPC "
              "round-trip midpoint: offset_s = worker wall clock minus "
              "supervisor wall clock, bounded by uncertainty_s = rtt/2 "
              "(source: ping|heartbeat). The fleet exporter uses the "
              "min-uncertainty sample per incarnation to shift worker "
              "rows onto the supervisor's timeline"),
    _spec("incident_snapshot", required=("reason", "path"),
          optional=("n_events", "replica"),
          doc="the fleet's bounded in-memory event ring was snapshotted "
              "to an incident file (worker death / restart-budget "
              "exhaustion) — the file holds the last N fleet events "
              "leading up to the incident"),
    _spec("drain", required=("phase",),
          optional=("timeout_s", "n_active", "queue_depth", "n_preempted",
                    "seconds", "requests_finished", "replica"),
          doc="graceful drain bracketing events (phase: start|end)"),
    # -- memory observatory (obs/memory.py) --------------------------------
    _spec("memory_snapshot", required=("source", "components"),
          optional=("total_bytes", "device_bytes", "host_bytes",
                    "capacity_bytes", "headroom_bytes", "labeled",
                    "replica"),
          doc="one MemoryLedger cadence snapshot: component -> bytes, "
              "measured from the live pytrees (nbytes sums — "
              "deterministic, so the trace's memory counter tracks are "
              "byte-identical across identical runs). labeled = the "
              "attribution series (per-tenant live KV, per-namespace "
              "prefix bytes, per-tenant adapter rows)"),
    _spec("memory_drift", required=("component", "reason"),
          optional=("expected_bytes", "measured_bytes", "delta_bytes",
                    "streak", "pinned_bytes", "pinned_entries",
                    "device_bytes", "ledger_bytes", "source", "replica"),
          doc="the leak detector fired: a component diverged from its "
              "byte-exact expectation (reason: reconcile), only ever "
              "grows (monotonic_growth), violated a probe invariant "
              "(e.g. pinned_orphan — a prefix pane still pinned at a "
              "cadence boundary), or the ledger diverged from "
              "device.memory_stats() (device_divergence)"),
    _spec("memory_pressure", required=("headroom_bytes", "capacity_bytes"),
          optional=("used_frac", "threshold_frac", "device_bytes",
                    "total_bytes", "components", "labeled", "source",
                    "replica"),
          doc="near-OOM flight recorder: device components crossed "
              "pressure_frac of capacity — the event carries the FULL "
              "component breakdown so the post-mortem has the "
              "composition at the moment headroom vanished"),
]

#: kind -> EventSpec. The single source of truth the GL04x lint, the
#: renderer and the trace exporter consume.
EVENTS: Dict[str, EventSpec] = {s.name: s for s in _EVENT_LIST}


def validate_event(kind: str, fields: Dict[str, Any]) -> List[str]:
    """Schema-check one event emission; returns a list of problems
    (empty = conforming). Used by the analyzer's runtime twin and the
    telemetry tests — emission itself stays unvalidated (a telemetry row
    must never crash the run it observes)."""
    spec = EVENTS.get(kind)
    if spec is None:
        return [f"unregistered event kind '{kind}'"]
    problems = []
    missing = spec.required - set(fields) - ALWAYS_ALLOWED_FIELDS
    if missing:
        problems.append(
            f"event '{kind}' missing required field(s) "
            f"{sorted(missing)}")
    if not spec.open_fields:
        unknown = set(fields) - spec.known_fields()
        if unknown:
            problems.append(
                f"event '{kind}' carries undeclared field(s) "
                f"{sorted(unknown)}")
    return problems


# sanity: the trace-exporter groups must be subsets of the registry —
# an entry here that no emitter can produce is schema drift in the other
# direction (also test-asserted so a failure names the stray entry)
for _group in (INCIDENT_EVENTS, REQUEST_EVENTS, SERVING_LIFECYCLE_EVENTS):
    for _name in _group:
        assert _name in EVENTS, f"{_name} not in the event registry"
