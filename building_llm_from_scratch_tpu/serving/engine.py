"""Continuous-batching decode engine (Orca-style, slot-scheduled).

One fixed ``(n_slots, Tmax)`` KV cache; requests are admitted into free
slots at step boundaries and retired the moment they finish, so XLA
compiles exactly ONE decode program (and one prefill per prompt-length
bucket) no matter how traffic arrives. The host loop per tick:

    retire finished -> admit queued into free slots (prefill, bucketed)
    -> one decode step for ALL slots (per-slot masks) -> stream

Slot independence is total: every row carries its own length, sampling
params and PRNG stream (``generate.token_rng`` fold-in on the request
seed), so a request's tokens are identical whether it runs alone, in any
slot, or next to arbitrary co-batched traffic — and identical to the
one-shot ``generate()`` path (test-pinned).

Telemetry (obs/metrics.py sink): per-request ``request_done`` events with
queue-wait/TTFT/TPOT, slot-occupancy + queue-depth gauges, periodic
``metrics`` rows with the decode token rate, and compile/recompile events
from the ``CompileWatcher``-wrapped prefill/decode programs — after
warmup, a prompt outside the warmed bucket set surfaces as a ``recompile``
event with the leaf diff instead of a silent latency cliff.

Resilience (this round — the serving counterpart of PR 1's training
fault tolerance):

  - DEADLINE-AWARE ADMISSION: requests carry ``deadline_s``; the queue
    sheds expired requests at admission boundaries (``request_expired``)
    and ``submit()`` rejects up front when queue position x the live
    TPOT-EWMA service estimate already blows the deadline
    (``request_shed`` / ``SLOShedError`` -> HTTP 429 + Retry-After).
  - FAULT ISOLATION: a poison request (raising callback, prefill fault,
    NaN-poisoned KV) fails ALONE with a ``request_failed{reason}`` event
    and frees its slot; co-resident requests' tokens are bit-identical
    to a fault-free run. An in-graph finite-logit guard retires a slot
    streaming non-finite logits instead of feeding garbage to a client.
  - SUPERVISED RESTART: a hung tick (``serving/supervisor.py`` watchdog
    on ``obs/stall.py``) dumps a flight record, fails in-flight requests,
    and restarts the decode loop with bounded backoff (``engine_restart``)
    — the compiled programs and their CompileWatchers survive, so the
    restarted engine serves with ZERO recompiles; queued requests are
    kept.
  - GRACEFUL DRAIN: ``drain()`` closes admission (``EngineDrainingError``
    -> HTTP 503 + Retry-After), finishes in-flight + queued work within
    a timeout, and fails the remainder with reason ``preempted``
    (``drain`` events bracket it).
"""

from __future__ import annotations

import collections
import functools
import threading
import time
from typing import List, Optional, Sequence, Union

import numpy as np

from building_llm_from_scratch_tpu.configs import (
    ModelConfig,
    refuse_unsupported,
)
from building_llm_from_scratch_tpu.generate import (
    _bucket,
    sample_tokens_dynamic,
    token_rng,
)
from building_llm_from_scratch_tpu.models.moe import expert_dispatch_path
from building_llm_from_scratch_tpu.models.transformer import (
    chunk_attention_path,
    decode_attention_path,
    init_slot_cache,
    kv_append_path,
    prefill_chunk_into_slot,
    prefill_into_slot,
    state_step_path,
    unstack_blocks,
    verify_slots,
)
from building_llm_from_scratch_tpu.obs.compile import (
    CompileWatcher,
    program_table,
)
from building_llm_from_scratch_tpu.obs.memory import (
    MemoryLedger,
    pytree_nbytes,
)
from building_llm_from_scratch_tpu.obs.metrics import (
    Histogram,
    RollingRatio,
    get_metrics,
    render_prometheus,
)
from building_llm_from_scratch_tpu.obs.schema import (
    SETUP_BUILD_PREFIX,
    TICK_BETWEEN,
    TICK_IDLE_WAIT,
    TICK_PHASES,
    TICK_SPANS,
    TICK_STEP,
)
from building_llm_from_scratch_tpu.obs.stall import last_ticks
from building_llm_from_scratch_tpu.obs.timeline import (
    StepTimeline,
    annotate,
    annotate_step,
    books_init,
    emit_setup_record,
)
from building_llm_from_scratch_tpu.ops.chunk_attention import (
    chunk_positions_read,
)
from building_llm_from_scratch_tpu.ops.decode_step import live_positions_read
from building_llm_from_scratch_tpu.ops.linear_attention import (
    linear_attention_path,
)
from building_llm_from_scratch_tpu.ops.selective_scan import (
    selective_scan_path,
    state_rows_walked,
)
from building_llm_from_scratch_tpu.parallel.collectives import (
    trace_under_mesh,
)
from building_llm_from_scratch_tpu.serving.adapters import BASE_ADAPTER
from building_llm_from_scratch_tpu.serving.kvcache import (
    KVCachePolicy,
    PagePool,
    PrefixStore,
    cache_nbytes,
    copy_prefix_into_slot,
    extract_prefix_panes,
)
from building_llm_from_scratch_tpu.serving.queue import (
    EngineDrainingError,
    PromptTooLongError,
    QueueFullError,
    RequestQueue,
    SLOShedError,
)
from building_llm_from_scratch_tpu.serving.request import (
    FINISH_CANCELLED,
    FINISH_EOS,
    FINISH_ERROR,
    FINISH_EXPIRED,
    FINISH_LENGTH,
    FINISH_PREEMPTED,
    FINISH_REJECTED,
    FINISH_SHED,
    FINISHED,
    QUEUED,
    REJECTED,
    RUNNING,
    Request,
    SamplingParams,
    next_request_id,
    resolve_eos,
)
from building_llm_from_scratch_tpu.serving.scheduler import Scheduler
from building_llm_from_scratch_tpu.serving.supervisor import (
    EngineSupervisor,
    FaultHooks,
)
from building_llm_from_scratch_tpu.utils.logging import setup_logger

logger = setup_logger(__name__)


class DecodeEngine:
    """The serving runtime: slot-batched KV cache + request lifecycle.

    Drive it either manually (``step()`` / ``run_until_idle()`` — what the
    deterministic tests do) or with the background thread
    (``start()`` / ``shutdown()`` — what the frontends do). ``submit()``
    is thread-safe either way.
    """

    @books_init     # self._setup_tl: the books of set-up, `init` open
    def __init__(self, cfg: ModelConfig, params, tokenizer=None, *,
                 n_slots: int = 4, max_len: Optional[int] = None,
                 max_queue: int = 64, max_top_k: int = 64,
                 default_max_new_tokens: int = 128,
                 warmup_prompt_cap: int = 256, metrics_every: int = 32,
                 watch_compiles: bool = True,
                 default_deadline_s: Optional[float] = None,
                 tick_timeout_s: float = 0.0, max_restarts: int = 3,
                 restart_backoff_s: float = 0.5,
                 hooks: Optional[FaultHooks] = None,
                 adapters=None,
                 kv_policy: Optional[KVCachePolicy] = None,
                 spec_k: int = 0, drafter=None,
                 mesh_plan=None, replica: Optional[int] = None,
                 max_prompt: Optional[int] = None):
        import jax

        self.cfg = cfg
        self._n_window_layers = len(cfg.layers_of("sliding"))
        self._n_linear_layers = len(cfg.layers_of("linear"))
        self._n_ssm_layers = len(cfg.layers_of("ssm"))
        #: layers whose memory of a row is a recurrent state, either kind
        self._n_state_layers = self._n_linear_layers + self._n_ssm_layers
        #: parallel/sharding.MeshPlan (or None = the historical
        #: single-device engine, byte-for-byte). tp>1 runs the whole
        #: prefill/decode/verify program family with NamedSharding'd
        #: weights and heads-sharded slot KV over the ``model`` mesh
        #: axis; tp=1 plans pin a replica to its own device (the
        #: router's replica-per-device layout).
        self.mesh_plan = mesh_plan
        #: fleet position (serving/router.py): labels this engine's
        #: telemetry events/metrics with ``replica=<i>``. None outside a
        #: router — single-engine telemetry is unchanged.
        self.replica = replica
        if mesh_plan is not None:
            # copy=False: the engine never donates params, so aliasing
            # the caller's buffers is safe (and skips a full weight copy
            # when build_components already placed them on this plan)
            params = mesh_plan.shard_params(params, copy=False)
        self.params = params
        self.tokenizer = tokenizer
        #: serving/kvcache.KVCachePolicy — KV layout/dtype + prefix
        #: policy. STATIC per engine: it decides which prefill tier
        #: compiles (monolithic-bucketed vs ONE chunk program) and the
        #: cache pytree's dtypes; hits/misses/spans are per-call data.
        self.kv_policy = kv_policy or KVCachePolicy()
        refuse_unsupported(
            cfg, paged=self.kv_policy.paged,
            prefix_cache=self.kv_policy.prefix_cache,
            int8_cache=self.kv_policy.quantized, speculation=spec_k > 0,
            tensor_parallel=mesh_plan is not None and mesh_plan.n_model > 1,
            sequence_parallel=mesh_plan is not None and mesh_plan.n_seq > 1,
            lora=adapters is not None)
        #: serving/adapters.AdapterRegistry (or None = base model only).
        #: The stacked pool + per-slot adapter ids become per-call data
        #: arguments of the compiled programs — multi-tenant traffic
        #: keeps the ONE-decode-program invariant.
        self.adapters = adapters
        self.n_slots = int(n_slots)
        self.max_len = min(int(max_len or cfg.context_length),
                           cfg.context_length)
        self.max_top_k = min(int(max_top_k), cfg.vocab_size)
        self.default_max_new_tokens = int(default_max_new_tokens)
        self.warmup_prompt_cap = min(int(warmup_prompt_cap), self.max_len)
        self.metrics_every = int(metrics_every)
        self.default_deadline_s = default_deadline_s
        self.hooks = hooks or FaultHooks()
        self.max_restarts = int(max_restarts)
        self.restart_backoff_s = float(restart_backoff_s)
        self.supervisor = (EngineSupervisor(self, tick_timeout_s,
                                            max_restarts=max_restarts,
                                            backoff_s=restart_backoff_s)
                           if tick_timeout_s > 0 else None)

        #: speculative decoding (serving/spec.py): k drafted tokens per
        #: slot per tick, verified by ONE Tq=k+1 compiled program. 0 =
        #: off (the engine is then byte-for-byte the historical one —
        #: same programs, same signatures, same cache shapes).
        self.spec_k = int(spec_k)
        if self.spec_k < 0:
            raise ValueError("spec_k must be >= 0")
        if self.spec_k >= self.max_len:
            raise ValueError(
                f"spec_k={self.spec_k} must be < the slot capacity "
                f"{self.max_len}")
        self.drafter = None
        if self.spec_k > 0:
            from building_llm_from_scratch_tpu.serving.spec import (
                NgramDrafter,
            )

            self.drafter = drafter or NgramDrafter()
        #: cache rows carry ``spec_k`` headroom positions past ``max_len``:
        #: the verify program appends k+1 candidate entries at the row's
        #: length, and the LAST legitimate decode position is max_len-1 —
        #: without headroom the batched DUS would clamp the write start
        #: and silently overwrite committed KV near capacity
        self._cache_len = self.max_len + self.spec_k

        #: long-context tier: sequence-sharded prefill. A plan with a
        #: live ``seq`` axis runs THE one chunk-prefill program with the
        #: chunk's token axis sharded over ``seq`` (GSPMD gathered
        #: attention: queries split across devices, the slot's cached KV
        #: replicated, the chunk's new KV gathered back into the slot
        #: row) — per-device prefill compute and activation memory drop
        #: by sp while decode keeps the existing replicated programs.
        #: The sharding is STATIC (part of the compiled signature), so
        #: long/short mixed traffic never recompiles, and the math is
        #: per-query-identical to the unsharded program, so tokens stay
        #: bit-exact vs single-device ``generate()``.
        self._sp = int(mesh_plan.n_seq) if mesh_plan is not None else 1
        self._sp_sharding = None
        if self._sp > 1:
            if self.kv_policy.prefill_chunk <= 0:
                raise ValueError(
                    "sequence-sharded prefill (mesh_plan with a seq "
                    "axis > 1) needs chunked prefill "
                    "(KVCachePolicy.prefill_chunk > 0): the seq axis "
                    "shards the chunk's token dimension")
            if self.kv_policy.prefill_chunk % self._sp != 0:
                raise ValueError(
                    f"prefill_chunk {self.kv_policy.prefill_chunk} must "
                    f"be divisible by the seq-parallel degree "
                    f"{self._sp}: every device owns an equal token "
                    "slice of the chunk")
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            from building_llm_from_scratch_tpu.parallel.mesh import (
                SEQ_AXIS,
            )

            self._sp_sharding = NamedSharding(mesh_plan.mesh,
                                              P(None, SEQ_AXIS))
        #: where chunk-prefill wall books: sp engines split it out as
        #: ``prefill_shard`` (identically 0 elsewhere, like ``draft``)
        self._prefill_phase = ("prefill_shard" if self._sp > 1
                               else "prefill")
        #: per-device prefill pane in prompt tokens, and the admission
        #: ceiling it implies. ``max_prompt`` (the --serve_max_prompt
        #: flag) declares what ONE device's pane may prefill; the
        #: engine-level ceiling is ``pane x sp`` — it LIFTS with the
        #: seq-parallel degree. Default pane = slot capacity / sp, so an
        #: unconfigured engine admits exactly what capacity allows.
        self.prompt_pane = (int(max_prompt) if max_prompt
                            else -(-self.max_len // self._sp))
        self.max_prompt = min(self.max_len - 1,
                              self.prompt_pane * self._sp)

        #: paged KV (``KVCachePolicy.paged``): slot rows map their
        #: logical positions onto fixed-size pages of ONE shared pool
        #: through a host-owned (n_slots, max_pages) int32 page table
        #: that rides every compiled program as traced DATA (the
        #: adapter-pool trick: identity is data, capacity is static) —
        #: page churn (hits, frees, eviction, oversubscription) never
        #: recompiles anything. Pool membership, refcounts and the
        #: admission reservation are pure host bookkeeping (PagePool);
        #: the device owns only the pool arrays.
        self._paged = self.kv_policy.paged
        self.page_pool: Optional[PagePool] = None
        self._page_table: Optional[np.ndarray] = None
        if self._paged:
            if mesh_plan is not None and mesh_plan.n_model > 1:
                raise ValueError(
                    "paged KV cannot ride a tensor-parallel mesh plan "
                    "yet: the pool leaves' (n_pages, ...) layout has no "
                    "heads-sharded placement — run paged engines "
                    "planless or seq-sharded only (replica-per-device "
                    "fleets are fine)")
            self._pages_per_slot = self.kv_policy.pages_per_slot(
                self._cache_len)
            self.page_pool = PagePool(
                self.kv_policy.total_pool_pages(self.n_slots,
                                                self._cache_len),
                self.kv_policy.page_bytes(cfg))
            self._page_table = np.zeros(
                (self.n_slots, self._pages_per_slot),
                np.int32)                               # guarded-by: _lock
            #: table columns each slot has allocated (col 0 upward) and
            #: the admission reservation still owed to it — invariant:
            #: reserved[slot] == worst-case need − cols referenced
            self._slot_cols = np.zeros(
                (self.n_slots,), np.int32)              # guarded-by: _lock
            self._pages_reserved = np.zeros(
                (self.n_slots,), np.int32)              # guarded-by: _lock
            # one page_pool_exhausted event per exhaustion episode (the
            # head request would re-refuse every tick until pages free)
            self._pool_exhausted_logged = False         # guarded-by: _lock
        #: pane-copy spy: counts contiguous prefix-hit pane COPIES (the
        #: duplicated-bytes path paged mode deletes) — a paged engine
        #: must hold this at zero (bench + CI assert it)
        self.pane_copies = 0                            # guarded-by: _lock

        self.queue = RequestQueue(max_queue)
        self.scheduler = Scheduler(self.n_slots)
        with self._setup_tl.span("cache_alloc"):
            self.cache = self._place_cache(init_slot_cache(
                cfg, self.n_slots, self._cache_len,
                policy=self.kv_policy))                 # guarded-by: _lock
        # pin the cache pytree's shardings for the life of the engine:
        # every compiled program constrains its cache OUTPUT to these, so
        # the donated rebind can never drift to a GSPMD-chosen layout
        # that would change the next call's arg signature (a recompile)
        self._cache_shardings = (jax.tree_util.tree_map(
            lambda x: x.sharding, self.cache)
            if mesh_plan is not None else None)
        #: which write the tick program's append was built with, chosen
        #: once, at trace time, by the rule the program itself asks
        #: (``kv_append_path``): "lane_window" | "scatter", or "paged" for
        #: the page table's writer. In ``stats()``, ``/healthz``, the warm-up
        #: event
        self.kv_append = ("paged" if self._paged else
                          kv_append_path(self.cache, self.spec_k + 1))
        #: the same for the tick program's attention
        #: (``decode_attention_path``): "live_blocks" (the key blocks a row's
        #: live positions reach) | "whole_buffer", or "paged".
        #: ``_attn_reads``: {(on the kernel's path, buffer length, head_dim,
        #: a ring's window): layers}: what a tick's ``kv_touched`` counts
        attn_layers = cfg.layers_of("full", "sliding")
        self._attn_reads = collections.Counter(
            self._attention_read(self.cache, l) for l in attn_layers)
        if self._paged:
            self.decode_attention = "paged"
        else:
            self.decode_attention = (
                "live_blocks" if any(k[0] for k in self._attn_reads)
                else "whole_buffer")
        #: the same for the chunk program's attention
        #: (``chunk_attention_path``): "live_blocks" (the key blocks that
        #: hold the row's live positions) | "materialised", or "paged"; None
        #: where prefill is not chunked and no chunk program ever runs.
        #: ``_chunk_reads``: a layer's (on the kernel's path, buffer length):
        #: what a tick's ``chunk_kv_touched`` counts
        chunked = self.kv_policy.prefill_chunk > 0
        self._chunk_reads = [self._chunk_read(self.cache, l)
                             for l in attn_layers] if chunked else []
        if not chunked:
            self.chunk_attention = None
        elif self._paged:
            self.chunk_attention = "paged"
        else:
            self.chunk_attention = (
                "live_blocks" if any(k for k, _ in self._chunk_reads)
                else "materialised")
        #: the form each program's 'linear' layers were built with
        #: (``linear_attention_path``): the tick's "step", a prefill's
        #: "chunked"; None for a model with no such layer
        self.linear_attention = ({
            "tick": linear_attention_path(self.spec_k + 1),
            "prefill": linear_attention_path(
                self.kv_policy.prefill_chunk or self.max_len)}
            if self._n_linear_layers else None)
        #: the same for its 'ssm' layers (``selective_scan_path``): the
        #: tick's "step", a prefill's "kernel" on a TPU at shapes the kernel
        #: takes, else "scan"
        self.selective_scan = ({
            "tick": selective_scan_path(self.spec_k + 1, cfg.ssm_inner,
                                        cfg.ssm_state),
            "prefill": selective_scan_path(
                self.kv_policy.prefill_chunk or self.max_len, cfg.ssm_inner,
                cfg.ssm_state)}
            if self._n_ssm_layers else None)
        #: how the tick program steps those layers' states
        #: (``state_step_path``): "live_rows" (the step walks the rows that
        #: decode, in place, and touches no other row's state) |
        #: "whole_buffer" (every row stepped, a select keeps the old state of
        #: those that do not decode); None for a model with no such layer.
        #: ``_state_walks``: the layers on the walk's path, which a tick's
        #: ``state_rows_touched`` counts by their decoding rows
        self._state_walks = sum(
            state_step_path(self.cache, cfg.layer_kind(l), self.spec_k + 1,
                            layer=l, rows_named=True) == "live_rows"
            for l in cfg.state_layers)
        self.state_step = (
            ("live_rows" if self._state_walks else "whole_buffer")
            if self._n_state_layers else None)
        #: how each program's rows reach the held experts
        #: (``expert_dispatch_path``): the tick's "per_expert" (one
        #: conditional a held expert), a chunk's "grouped" on a TPU (one
        #: kernel over the experts that got rows), "chunk" None where prefill
        #: is not chunked (a prompt bucket asks for its own rows); None for a
        #: dense model
        self.expert_dispatch = None
        if cfg.is_moe:
            dispatch = functools.partial(
                expert_dispatch_path, cfg,
                dtype=self.params["blocks"]["moe"]["experts"]["gate"].dtype)
            self.expert_dispatch = {
                "tick": dispatch(self.n_slots * (self.spec_k + 1)),
                "chunk": (dispatch(self.kv_policy.prefill_chunk)
                          if chunked else None)}
        #: the weights ride every compiled program as an ARGUMENT: closed
        #: over, jit bakes them into each program as constants (GPT2-124M
        #: bf16: 0.3 GB per program, 40 s per compile on the chip, and
        #: enough host memory over the program family to be killed at 40 GiB).
        #: A sparse model's are held ONCE: its programs take their layers'
        #: weights as static slices of the stacked leaves inside the program
        #: (its experts have to be sliced inside their conditionals anyway).
        #: A dense model keeps the per-layer copy beside the stacked leaves:
        #: sliced inside, the 1.5B decode program is 4.7 ms a tick slower on
        #: the chip. One convention for both would be the per-layer leaves
        #: alone, but the caller's stacked tree lives through this
        #: constructor whatever the engine drops (the caller's frame holds
        #: it), and at the sparse cell's size 6.25 GB of it + 6.25 GB of
        #: copy + 6.74 GB of cache is 19.2 GB of 16 (PERF.md sections 6
        #: and 7, PR 28; ROADMAP S3)
        with self._setup_tl.span("weights_layout"):
            self._blocks = (None if cfg.is_moe
                            else unstack_blocks(self.params, cfg))
        self._weights = (self.params, self._blocks)
        if self.adapters is not None and mesh_plan is not None:
            # the stacked pool rides every compiled call as data — it has
            # to live on THIS engine's mesh (replicated: every model
            # shard reads all adapter columns it needs), or jit would see
            # arguments spanning two device sets
            self.adapters.place_pool(mesh_plan.put_replicated)
        #: chunked-prefill progress per slot (slot -> host dict); a slot
        #: present here is ADMITTED but not yet decoding — the decode
        #: tick computes (and ignores) its row, and its next-write
        #: position doubles as the row's length so the decode step's
        #: garbage append lands exactly where the next chunk overwrites
        self._prefill_state: dict = {}                  # guarded-by: _lock
        #: static pane width for prefix panes (copy/extract programs):
        #: one width -> ONE copy + ONE extract program, hit spans are
        #: data against it
        self._prefix_pane_len = self._bucket_len(
            max(self.warmup_prompt_cap, 1))
        self.prefix_store: Optional[PrefixStore] = None
        if self.kv_policy.prefix_cache:
            from building_llm_from_scratch_tpu.models.lora import (
                adapter_fingerprint,
            )

            self.prefix_store = PrefixStore(
                adapter_fingerprint(cfg),
                chunk_tokens=self.kv_policy.prefill_chunk,
                budget_bytes=self.kv_policy.prefix_budget_bytes,
                pane_tokens=self._prefix_pane_len,
                page_pool=self.page_pool)

        S = self.n_slots
        # host-owned per-slot state; the device owns only the big k/v.
        # PRNG key width depends on the configured impl (threefry (2,),
        # rbg (4,)) — probe it instead of assuming
        probe_key = np.asarray(_prng_key(0))
        self._lengths = np.zeros((S,), np.int32)        # guarded-by: _lock
        self._last_tokens = np.zeros((S,), np.int32)    # guarded-by: _lock
        self._n_gen = np.zeros((S,), np.int32)          # guarded-by: _lock
        self._base_keys = np.zeros(
            (S,) + probe_key.shape, probe_key.dtype)    # guarded-by: _lock
        self._temps = np.zeros((S,), np.float32)        # guarded-by: _lock
        self._topks = np.zeros((S,), np.int32)          # guarded-by: _lock
        # per-slot adapter pool row; −1 = base model (exact zero delta)
        self._adapter_ids = np.full((S,), -1, np.int32)  # guarded-by: _lock
        # per-slot committed-token history (prompt + generated), the
        # n-gram drafter's haystack; host-only, maintained iff spec is on
        self._hist = (np.zeros((S, self.max_len), np.int32)
                      if self.spec_k else None)          # guarded-by: _lock
        self._hist_len = np.zeros((S,), np.int32)        # guarded-by: _lock
        # per-adapter request accounting ("base" for un-adapted traffic):
        # name -> {finished, failed, tokens} — feeds the labeled /metrics
        # series and serve_summary
        self._adapter_counts = {}                        # guarded-by: _lock
        if self.adapters is not None:
            # the registry's load() must not reuse a pool row an active
            # slot still decodes against (hot-evict-then-load safety)
            self.adapters.set_in_use_probe(self._adapter_rows_in_use)

        # donate the cache pytree: the caller always rebinds self.cache
        # to the outputs, so XLA may alias input->output (and the tick's
        # pallas append is in place: no per-tick full-cache copy). The
        # prefix-EXTRACT program deliberately does NOT donate
        # — it only reads the cache (the next donating call reuses the
        # same arrays).
        def jit(fn, **kw):
            # the tp/sp engine's kernels shard_map over the plan's mesh
            return jax.jit(trace_under_mesh(
                fn, mesh_plan.mesh if mesh_plan is not None else None), **kw)

        prefill_jit = jit(self._prefill_impl, donate_argnums=(0,))
        # paged: the chunk/step programs take the page table as one more
        # traced argument (``_paged_tail``) and write/read through it; the
        # monolithic prefill and the prefix copy/extract pair are never
        # CALLED (paged implies chunked prefill, and a paged hit is a host
        # table write) — they stay built so the watcher set is stable
        chunk_jit = jit(self._chunk_impl, donate_argnums=(0,))
        copy_jit = jit(self._copy_impl, donate_argnums=(0,))
        extract_jit = jit(functools.partial(
            extract_prefix_panes, pane_len=self._prefix_pane_len))
        # spec on: the Tq=k+1 verify program IS the tick program — the
        # plain decode step is never built (every slot, spec-opted-out
        # rows included, rides verify; their commit count is clamped to 1
        # on the host). spec off: the historical decode step, untouched.
        step_jit = jit(self._decode_impl, donate_argnums=(0,))
        step_label = "serve_verify" if self.spec_k else "serve_decode"
        if watch_compiles:
            self._prefill = CompileWatcher(prefill_jit,
                                           label="serve_prefill",
                                           multi_program=True)
            self._prefill_chunk = CompileWatcher(
                chunk_jit, label="serve_prefill_chunk", multi_program=True)
            self._prefix_copy = CompileWatcher(
                copy_jit, label="serve_prefix_copy", multi_program=True)
            self._prefix_extract = CompileWatcher(
                extract_jit, label="serve_prefix_extract",
                multi_program=True)
            step_watched = CompileWatcher(step_jit, label=step_label,
                                          multi_program=True)
        else:
            self._prefill = prefill_jit
            self._prefill_chunk = chunk_jit
            self._prefix_copy = copy_jit
            self._prefix_extract = extract_jit
            step_watched = step_jit
        if self.spec_k:
            self._verify = step_watched
            self._decode = None
        else:
            self._decode = step_watched
            self._verify = None

        #: memory observatory (obs/memory.py): the per-token KV cost the
        #: live-attribution math scales host lengths by, and the ledger
        #: itself — built AFTER the cache/store/pool exist so every
        #: provider closes over live engine state
        self._kv_bytes_per_token = self.kv_policy.bytes_per_slot(
            self.cfg, self._cache_len)["bytes_per_token"]
        self.memory_ledger = self._build_memory_ledger()

        self._lock = threading.RLock()
        self._work = threading.Condition()
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        # _dead/_draining: written by _fail_all/drain; racy READS are the
        # design (submit's fast-path check repeats its decision under a
        # real barrier), so only writes are lock-checked
        self._dead: Optional[str] = None    # guarded-by: _lock [writes]
        self._draining = False              # guarded-by: _lock [writes]
        # bumped on every supervisor restart; a stale loop thread (one
        # that eventually un-wedges after being abandoned) sees the bump
        # and exits WITHOUT committing any state (see step()). Reads are
        # deliberately lock-free generation checks — a stale read only
        # delays the abandonment by one commit point.
        self._restart_lock = threading.Lock()
        self._generation = 0        # guarded-by: _restart_lock [writes]
        self.n_restarts = 0         # guarded-by: _restart_lock [writes]
        self.warmed_up = False
        #: the set-up record went to the metrics hub (once, in `start`)
        self._setup_emitted = False
        # live service-time estimate for SLO-aware admission: EWMAs of
        # per-token decode time and tokens-per-request over finished
        # requests (alpha 0.2 — a few requests of history dominate)
        self._tpot_ewma: Optional[float] = None     # guarded-by: _lock
        self._tokens_ewma: Optional[float] = None   # guarded-by: _lock

        # rolling serve accounting: fixed-bucket histograms (obs/metrics
        # Histogram — Prometheus semantics, O(buckets) memory forever;
        # replaces the 8192-deque reservoirs whose percentiles silently
        # covered only the most recent window of a long-running server)
        # plus a rolling deadline-miss ratio for SLO burn-rate alerting
        self.n_ticks = 0                    # guarded-by: _lock
        self.tokens_generated = 0           # guarded-by: _lock
        self.requests_finished = 0          # guarded-by: _lock
        self.requests_rejected = 0          # guarded-by: _lock
        self.requests_failed = 0            # guarded-by: _lock
        self.requests_shed = 0              # guarded-by: _lock
        self.requests_expired = 0           # guarded-by: _lock
        self.ttft_hist = Histogram()
        self.tpot_hist = Histogram()
        self.queue_wait_hist = Histogram()
        self.e2e_hist = Histogram()
        #: per-tick prefill+prefix-copy wall (ticks that did prefill
        #: work): the chunked-prefill scoreboard — its p95 is the
        #: head-of-line bound chunking exists to shrink. Finer buckets
        #: than the request-latency default: chunked-vs-monolithic A/Bs
        #: differ by small factors the 2.5x latency ladder can't resolve
        self.tick_prefill_hist = Histogram(bounds=(
            0.0002, 0.0005, 0.001, 0.002, 0.004, 0.008, 0.015, 0.03,
            0.06, 0.12, 0.2, 0.3, 0.45, 0.7, 1.0, 1.5, 2.2, 3.3, 5.0,
            7.5, 11.0, 17.0, 26.0, 40.0, 60.0))
        self.slo_window = RollingRatio(window_s=300.0)
        self._t_start_mono = time.monotonic()
        # per-tick phase breakdown (obs/schema.TICK_PHASES): every phase
        # is a span of the tick's timeline (obs/timeline.StepTimeline,
        # the trainer's primitive): perf_counter ONLY — the
        # instrumentation adds zero device fetches (guard-tested) — and a
        # TraceAnnotation, so a device trace names the host's part of a
        # gap. `_book_tick` drains it once a tick into one record
        # (get_metrics().recent("tick")) and into the cumulative totals
        # the /metrics counters export.
        # (one tick at a time uses it; `_restart` replaces it)
        self._tl = StepTimeline(
            TICK_SPANS)                 # guarded-by: _restart_lock [writes]
        self._tick_rec: dict = {}                        # guarded-by: _lock
        self.tick_phase_totals = {ph: 0.0
                                  for ph in TICK_PHASES}  # guarded-by: _lock
        self.tick_seconds_total = 0.0                    # guarded-by: _lock
        # chunked-prefill chunks (prefix hits and misses: the store keeps
        # count) and the speculative-decoding accounting: drafted = k per
        # spec-enabled decoding row per tick; accepted = the in-graph
        # n_acc (draft tokens the verify committed). All cumulative:
        # /metrics exports the spec pair, and the cadence metrics row
        # reports each as the difference since the row before
        # (`_win_base`)
        self.prefill_chunks = 0                          # guarded-by: _lock
        self.spec_tokens_drafted = 0                     # guarded-by: _lock
        self.spec_tokens_accepted = 0                    # guarded-by: _lock
        self._win_base = self._cumulative()              # guarded-by: _lock

    # -- mesh placement (tp-sharded engine) --------------------------------

    def _place_cache(self, cache):
        """Place a fresh slot cache on the engine's mesh (identity for
        planless engines — the historical allocation untouched)."""
        if self.mesh_plan is None:
            return cache
        return self.mesh_plan.shard_cache(cache)

    def _pin_cache(self, cache):
        """In-graph sharding constraint pinning a program's cache OUTPUT
        to the engine's fixed cache layout (no-op when planless). Keeps
        the donate->rebind->call cycle signature-stable under GSPMD."""
        if self._cache_shardings is None:
            return cache
        import jax

        return jax.tree_util.tree_map(jax.lax.with_sharding_constraint,
                                      cache, self._cache_shardings)

    # -- telemetry ---------------------------------------------------------

    def _ev(self, kind: str, **fields) -> None:
        """Engine-scoped event: labels with this engine's fleet position
        (``replica=<i>``) when it has one, so a router's merged JSONL
        stays attributable per replica. Single engines emit the exact
        historical rows (no replica field at all)."""
        if self.replica is not None:
            fields["replica"] = self.replica
        get_metrics().event(kind, **fields)

    # -- memory observatory (obs/memory.py) --------------------------------

    def _build_memory_ledger(self) -> MemoryLedger:
        """Register every device-memory consumer this engine owns as a
        ledger component, measured from the LIVE arrays (providers close
        over ``self`` — a donated-cache rebind or a restart's fresh
        allocation is picked up on the next snapshot automatically).
        Expectations are the byte-exact analytic sizes, so any
        measured-vs-expected gap is a ``memory_drift``."""
        ledger = MemoryLedger(emit=self._ev, source="engine")
        ledger.register("model_params",
                        lambda: pytree_nbytes(self.params))
        bps = self.kv_policy.bytes_per_slot(self.cfg, self.max_len)
        n = self.n_slots
        if self._paged:
            # the pool IS the KV allocation: one component, byte-exact
            # by construction (every leaf is n_pages x one page's slice,
            # so measured == total_pool_pages x page_bytes, always —
            # any gap means the pool arrays were rebuilt wrong).
            # Providers read self.page_pool dynamically: a restart swaps
            # in a fresh pool and the next snapshot follows it.
            ledger.register(
                "page_pool",
                lambda: cache_nbytes(self.cache),  # graft-ok: GL031 nbytes metadata, runs at ledger cadence under the engine lock
                expected=lambda: (self.page_pool.n_pages
                                  * self.page_pool.page_bytes))
        else:
            ledger.register("slot_kv",
                            lambda: self._cache_component_bytes()[0],
                            expected=lambda: bps["kv_bytes"] * n)
            if bps["scale_bytes"]:
                ledger.register("kv_scales",
                                lambda: self._cache_component_bytes()[1],
                                expected=lambda: bps["scale_bytes"] * n)
            if "state_bytes" in bps:
                ledger.register(
                    "slot_state",
                    lambda: sum(a.nbytes for key in ("conv", "state")  # graft-ok: GL031 nbytes metadata, runs at ledger cadence under the engine lock
                                for a in self.cache[key] if a is not None),
                    expected=lambda: bps["state_bytes"] * n)
            if self.spec_k:
                bps_full = self.kv_policy.bytes_per_slot(self.cfg,
                                                         self._cache_len)
                ledger.register(
                    "spec_headroom",
                    lambda: self._cache_component_bytes()[2],
                    expected=lambda: (bps_full["total_bytes"]
                                      - bps["total_bytes"]) * n)
        if self.prefix_store is not None:
            store = self.prefix_store
            # paged: stored entries hold REFERENCES to pool pages — the
            # bytes already live inside the page_pool component, so the
            # store series is attribution only (device=False keeps it
            # out of the pressure/headroom device sum: no double count)
            ledger.register("prefix_store", lambda: store.bytes_total,
                            device=not self._paged)
            ledger.register_labeled("prefix_store_bytes", "namespace",
                                    store.bytes_by_tag)
            ledger.register_probe("prefix_store",
                                  self._prefix_pinned_probe)
        if self.adapters is not None:
            ledger.register("adapter_pool", self.adapters.pool_nbytes)
            ledger.register_labeled("adapter_pool_bytes", "tenant",
                                    self.adapters.bytes_by_adapter)
        ledger.register("compile_temps", self._compile_temp_bytes)
        ledger.register_labeled("kv_live_bytes", "tenant",
                                self._kv_live_by_tenant)
        ledger.track_host_rss()
        return ledger

    # called under _lock from the cadence observe and the scrape's timed
    # acquire; a failed timed acquire reads stale-but-safe metadata,
    # like the rest of metrics_snapshot
    # graft: hot-path
    def _cache_component_bytes(self) -> tuple:  # holds: _lock
        """(slot_kv, kv_scales, spec_headroom) bytes of the live slot
        cache, measured from the actual arrays' ``nbytes`` (metadata —
        never a sync). The spec headroom tail (``spec_k`` positions past
        ``max_len``) is carved out along the time axis; the three parts
        sum to ``cache_nbytes(self.cache)`` byte-exactly because every
        array's byte count is divisible by its time extent."""
        kv_nb = sum(a.nbytes for key in ("k", "v")
                    for a in self.cache.get(key, ()) if a is not None)
        scale_nb = sum(a.nbytes for key in ("k_scale", "v_scale")
                       for a in self.cache.get(key, ()) if a is not None)
        slot_kv = kv_nb * self.max_len // self._cache_len
        kv_scales = scale_nb * self.max_len // self._cache_len
        return slot_kv, kv_scales, kv_nb + scale_nb - slot_kv - kv_scales

    def _compile_temp_bytes(self) -> int:
        """Peak compile-time scratch across the engine's programs (HLO
        memory analysis via CompileWatcher): programs execute one at a
        time, so the RESIDENT scratch is the max, not the sum."""
        peak = 0
        for w in self._watchers():
            mem = getattr(w, "memory", None) or {}
            peak = max(peak, mem.get("temp_bytes", 0))
        return peak

    # graft: hot-path
    def _kv_live_by_tenant(self) -> dict:  # holds: _lock
        """Live KV attribution: each occupied slot's committed length x
        bytes/token, rolled up by tenant (adapter name; "base" for
        un-adapted traffic). Host numpy state only."""
        out: dict = {}
        for slot, req in self.scheduler.active():
            nm = req.params.adapter or BASE_ADAPTER
            if self._paged:
                # page-exact: mapped columns x page bytes. A shared page
                # is charged to EVERY sharer (attribution answers "who
                # depends on this memory", not "who allocated it"), so
                # the tenant sum can exceed pool-used — by design
                cols = int(self._slot_cols[slot])  # graft-ok: GL011 host numpy
                out[nm] = (out.get(nm, 0)
                           + cols * self.page_pool.page_bytes)
                continue
            live = int(self._lengths[slot])  # graft-ok: GL011 host numpy
            out[nm] = out.get(nm, 0) + live * self._kv_bytes_per_token
        return out

    def _prefix_pinned_probe(self) -> Optional[dict]:
        """Pins are held only across one in-flight pane copy under the
        engine lock — an entry still pinned when the cadence observes is
        leaked (its bytes can never be evicted). The ledger turns a
        non-None return into ``memory_drift(component="prefix_store")``."""
        pinned, keys = self.prefix_store.pinned_bytes()
        if not pinned:
            return None
        return {"reason": "pinned_orphan", "pinned_bytes": pinned,
                "pinned_entries": keys[:8], "measured_bytes": pinned}

    # -- jitted programs (close over params/cfg/blocks so per-tick call
    # signatures carry only the small mutable state + caches) -------------

    def _adapter_arg(self, pool, pool_scale, ids):
        """The model passes' ``adapter=``: the registry's stacked pool and
        the batch's rows of it (one request's row, or a row a slot), or
        None for a registry-less engine."""
        import jax.numpy as jnp

        if pool is None:
            return None
        return {"pool": pool, "scaling": pool_scale,
                "ids": jnp.reshape(ids, (-1,))}

    def _paged_kw(self, page_table) -> dict:
        """The model passes' keywords that reach a row through the page
        table: the KV cache is the shared page pool and the per-slot int32
        table rides each call as TRACED DATA (one (S, max_pages) signature
        — page churn never recompiles, mirroring the adapter-pool trick)."""
        if page_table is None:
            return {}
        return {"page_table": page_table, "cache_len": self._cache_len}

    def _first_token(self, logits, cache, base_key, temp, topk):
        """A prefill program's tail: sample the request's first token
        (fold-in index 0) from its last position's logits."""
        import jax.numpy as jnp

        key0 = token_rng(base_key, 0)
        tok = sample_tokens_dynamic(
            logits[None], key0[None], jnp.reshape(temp, (1,)),
            jnp.reshape(topk, (1,)), self.max_top_k)[0]
        # in-graph finite guard: non-finite logits mean the slot would
        # stream garbage — the host retires the request with an error
        # status instead (scalar flag; adds one all-reduce over V)
        ok = jnp.all(jnp.isfinite(logits))
        return tok, ok, self._pin_cache(cache)

    def _prefill_impl(self, cache, weights, tokens, prompt_len, slot,
                      base_key, temp, topk, pool=None, pool_scale=None,
                      adapter_id=None):
        logits, cache = prefill_into_slot(
            weights[0], self.cfg, tokens, prompt_len, slot,
            cache, weights[1],
            adapter=self._adapter_arg(pool, pool_scale, adapter_id))
        return self._first_token(logits, cache, base_key, temp, topk)

    def _chunk_impl(self, cache, weights, tokens, chunk_start, prompt_len,
                    slot, base_key, temp, topk, pool=None, pool_scale=None,
                    adapter_id=None, page_table=None):
        """One C-token prefill chunk (the chunked tier's ONE compiled
        prefill program). Samples the would-be first token every call —
        the host only reads it (and the finite flag) on the FINAL chunk,
        so non-final chunks cost zero device->host syncs.

        Seq-sharded engines (``--serve_sp``): the chunk's token axis is
        constrained onto the ``seq`` mesh axis and GSPMD propagates the
        split through the whole chunk forward — each device embeds,
        normalizes and attends its C/sp queries against the replicated
        slot KV or page pool (per-query math identical to unsharded, so
        tokens stay bit-exact), then the chunk's new KV is gathered back
        into the replicated slot row (its page scatters) by the output's
        pinned sharding."""
        import jax

        if self._sp_sharding is not None:
            tokens = jax.lax.with_sharding_constraint(tokens,
                                                      self._sp_sharding)
        logits, cache = prefill_chunk_into_slot(
            weights[0], self.cfg, tokens, chunk_start, prompt_len, slot,
            cache, weights[1],
            adapter=self._adapter_arg(pool, pool_scale, adapter_id),
            **self._paged_kw(page_table))
        return self._first_token(logits, cache, base_key, temp, topk)

    def _copy_impl(self, cache, panes, slot):
        """Prefix HIT: one batched DUS per layer writes the stored panes
        into row ``slot`` — the whole cached-span compute (no forward)."""
        return self._pin_cache(copy_prefix_into_slot(cache, panes, slot))

    def _decode_impl(self, cache, weights, tokens, lengths, base_keys,
                     n_gen, temps, topks, pool=None, pool_scale=None,
                     adapter_ids=None, live=None, page_table=None):
        """THE tick program: what the engine IS (``spec_k``, fixed at
        construction) says whether it is the plain decode step (``tokens``
        (S,): each slot's last token; returns (next tokens (S,), ok (S,),
        cache)) or the speculative one (``_accept``). ``live`` (S,) bool
        rides a sparse model's tick only (``_step_tail``): the rows that
        decode."""
        import jax
        import jax.numpy as jnp

        # a sparse model's tick hands back, with the two arrays the host
        # fetches anyway, the rows each held expert computed, a layer a row
        expert_rows = [] if self.cfg.is_moe else None
        logits, cache = verify_slots(
            weights[0], self.cfg,
            tokens if self.spec_k else tokens[:, None], lengths,
            cache, weights[1],
            adapter=self._adapter_arg(pool, pool_scale, adapter_ids),
            live=live, expert_rows=expert_rows,
            **self._paged_kw(page_table))
        if self.spec_k:
            return (*self._accept(logits, tokens, base_keys, n_gen, temps,
                                  topks), self._pin_cache(cache))
        logits = logits[:, 0]              # decode is verify at Tq = 1
        keys = jax.vmap(token_rng)(base_keys, n_gen)
        nxt = sample_tokens_dynamic(logits, keys, temps, topks,
                                    self.max_top_k)
        # per-row finite guard: slot independence means a numerically
        # poisoned row (bad KV state) goes non-finite ALONE — the host
        # retires just that slot (reason non_finite_logits)
        ok = jnp.all(jnp.isfinite(logits), axis=-1)
        if expert_rows is not None:
            ok = (ok, jnp.stack(expert_rows))
        return nxt, ok, self._pin_cache(cache)

    def _accept(self, logits, tokens, base_keys, n_gen, temps, topks):
        """The speculative tick's tail: ONE Tq=k+1 forward scored every
        slot's [last_token, d_1..d_k] and the in-graph accept rule commits
        the longest valid prefix. Position j of row s samples with the
        fold-in key for token index n_gen[s]+j — the exact key the
        non-speculative path would use for that token — so committed
        tokens are bit-identical to spec-off at any acceptance rate.
        Returns (tokens (S, k+1), n_accepted (S,), ok (S,))."""
        import jax
        import jax.numpy as jnp

        from building_llm_from_scratch_tpu.generate import (
            accept_draft_tokens,
        )

        Tq = tokens.shape[1]
        offsets = n_gen[:, None] + jnp.arange(Tq)[None, :]     # (S, Tq)
        keys = jax.vmap(jax.vmap(token_rng, in_axes=(None, 0)))(
            base_keys, offsets)
        return accept_draft_tokens(
            logits, tokens[:, 1:], keys, temps, topks, self.max_top_k)

    def _pool_args(self) -> tuple:
        """Positional tail for the compiled programs: the registry's
        CURRENT stacked pool + scaling (lock-free snapshot — hot-loads
        swap these device arrays between ticks, same shapes, zero
        recompiles). Empty when no registry is attached, keeping the
        registry-less engine's historical call signature."""
        if self.adapters is None:
            return ()
        pool, scale = self.adapters.pool_args()
        return (pool, scale)

    def _paged_tail(self, tail: tuple, width: int) -> tuple:  # holds: _lock
        """A paged engine's programs take the page table LAST, behind
        their ``width`` optional arguments (the other engines' signatures
        stay as they were)."""
        if not self._paged:
            return tail
        return tail + (None,) * (width - len(tail)) + (self._page_table,)

    def _step_tail(self) -> tuple:  # holds: _lock
        """The tick program's positional tail: the adapter pool and the
        slots' rows of it; for a sparse model or one whose layers hold a
        state (neither takes an adapter) the rows that decode this tick: the
        others reach no expert and move no state."""
        if self.cfg.is_moe or self._n_state_layers:
            live = np.zeros((self.n_slots,), np.bool_)
            live[[s for s, _ in self.scheduler.active()
                  if s not in self._prefill_state]] = True
            tail = (None, None, None, live)
        elif self.adapters is None:
            tail = ()
        else:
            tail = self._pool_args() + (self._adapter_ids,)
        return self._paged_tail(tail, 4)

    def _pool_args_for(self, adapter_row) -> tuple:
        """Prefill's positional tail: pool + scaling + THIS request's row."""
        base = self._pool_args()
        return base + (adapter_row,) if base else ()

    def _adapter_rows_in_use(self):
        """Registry in-use probe: pool rows active slots reference. TIMED
        lock acquire — a wedged (or just slow) tick must not hang registry
        admin. On timeout the answer must be CONSERVATIVE: an in-flight
        ``_admit`` may have resolved a row but not yet committed it to
        ``_adapter_ids``, so a lock-free read could green-light reusing a
        row a just-admitted request is about to decode against (silent
        cross-tenant weight corruption). Report every row in use instead —
        a hot-load during a wedge waits or fails loudly, never corrupts."""
        lock = self._lock
        locked = lock.acquire(timeout=1.0)
        try:
            if not locked:
                return set(range(self.adapters.capacity))
            return {int(r) for r in self._adapter_ids if r >= 0}
        finally:
            if locked:
                lock.release()

    # -- admission --------------------------------------------------------

    def _bucket_len(self, n: int) -> int:
        return min(_bucket(n), self.max_len)

    def prompt_buckets(self) -> List[int]:
        """The prompt-length buckets warmup compiles (one prefill program
        each): every bucket value up to ``warmup_prompt_cap``. Prompts
        longer than the cap still work — their first arrival pays a
        compile, which the frozen watcher reports as a ``recompile``
        (bucket miss)."""
        vals = {self._bucket_len(1)}
        b = 64
        while b <= self.warmup_prompt_cap:
            vals.add(self._bucket_len(b))
            b += 64
        # the clamped terminal bucket: when max_len is not a multiple of
        # 64 the loop above never reaches it, yet in-capacity prompts
        # bucket there (e.g. max_len=48 -> bucket 48)
        vals.add(self._bucket_len(self.warmup_prompt_cap))
        return sorted(vals)

    def encode_prompt(self, prompt: Union[str, Sequence[int], np.ndarray]
                      ) -> np.ndarray:
        if isinstance(prompt, str):
            if self.tokenizer is None:
                raise ValueError("text prompt needs a tokenizer")
            ids = self.tokenizer.encode(prompt)
        else:
            ids = prompt
        ids = np.asarray(ids, np.int32).reshape(-1)
        if ids.size < 1:
            raise ValueError("empty prompt")
        if int(ids.min()) < 0 or int(ids.max()) >= self.cfg.vocab_size:
            # out-of-vocab ids make the embedding gather fill NaN and the
            # slot would stream garbage until the finite guard retires it
            # — reject the poison at submit instead of burning a slot
            raise ValueError(
                f"prompt token ids must be in [0, {self.cfg.vocab_size}); "
                f"got range [{int(ids.min())}, {int(ids.max())}]")
        return ids

    def submit(self, prompt, params: Optional[SamplingParams] = None,
               block: bool = False, timeout: Optional[float] = None,
               on_token=None, route: Optional[dict] = None) -> Request:
        """Enqueue one request (thread-safe). ``block=False`` rejects with
        ``QueueFullError`` when the bounded queue is at capacity;
        ``block=True`` waits for space (backpressure). Raises
        ``EngineDrainingError`` once ``drain()`` has closed admission and
        ``SLOShedError`` when the request's deadline is predicted
        unmeetable from the current backlog."""
        if self._dead is not None:
            raise RuntimeError(f"engine is dead: {self._dead}")
        if self._draining:
            # the backlog estimate reads the service EWMAs, which mutate
            # under the engine lock (GL031). TIMED acquire: drain() sets
            # _draining at entry but only replaces a wedged lock after
            # its timeout wait, so an unbounded acquire here could park
            # the client's thread forever on the abandoned lock — on
            # timeout, skip the estimate (Retry-After is best-effort)
            # rather than delay the 503
            lock = self._lock
            retry = None
            locked = lock.acquire(timeout=0.5)
            try:
                if locked:
                    retry = self.estimate_queue_clear_s()
            finally:
                if locked:
                    lock.release()
            raise EngineDrainingError(
                "engine is draining: admission closed",
                retry_after_s=retry)
        params = params or SamplingParams()
        if params.deadline_s is None and self.default_deadline_s:
            import dataclasses

            params = dataclasses.replace(
                params, deadline_s=self.default_deadline_s)
        if params.deadline_s is not None and params.deadline_s <= 0:
            raise ValueError("deadline_s must be > 0")
        ids = self.encode_prompt(prompt)
        if params.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if params.top_k is not None and not (
                1 <= params.top_k <= self.max_top_k):
            raise ValueError(
                f"top_k={params.top_k} outside this engine's compiled "
                f"capacity 1..{self.max_top_k} (raise max_top_k)")
        if params.adapter is not None:
            # unknown adapters are poison at admission (the slot would
            # decode base-model garbage under the tenant's name) — reject
            # at submit (HTTP 400). Re-resolved at admit: a concurrent
            # evict between here and admission fails just that request.
            if self.adapters is None:
                raise ValueError(
                    f"request names adapter '{params.adapter}' but this "
                    "engine has no adapter registry (--serve_adapters)")
            try:
                self.adapters.resolve(params.adapter)
            except KeyError as e:
                # e.args[0], not str(e): KeyError.__str__ reprs its
                # message, which would wrap the 400 body in quotes
                raise ValueError(e.args[0]) from None
        if int(ids.size) > self.max_prompt:
            sharded = (f" ({self.prompt_pane} tokens/device pane x "
                       f"sp={self._sp}, seq-sharded)" if self._sp > 1
                       else "")
            raise PromptTooLongError(
                f"prompt ({ids.size} tokens) exceeds the engine's "
                f"prompt ceiling {self.max_prompt}{sharded}",
                prompt_tokens=int(ids.size), limit=self.max_prompt,
                pane_tokens=self.prompt_pane, sp=self._sp)
        total = int(ids.size) + params.max_new_tokens
        if total > self.max_len:
            # plain ValueError (HTTP 400), NOT PromptTooLongError: the
            # prompt itself fits under the ceiling — the client asked
            # for too many NEW tokens, so shrinking max_new_tokens (not
            # the payload) is the fix
            raise ValueError(
                f"prompt ({ids.size}) + max_new_tokens "
                f"({params.max_new_tokens}) = {total} exceeds the "
                f"engine's slot capacity {self.max_len}")
        # the Request exists BEFORE any shed/reject decision: every
        # terminal outcome — even "never entered the queue" — must carry
        # a request_id on its event and close a span tree under that id,
        # or trace joins silently drop the requests that were turned away
        req = Request(next_request_id(), ids, params, on_token=on_token)
        # long-context telemetry: a prompt no single device's pane could
        # have prefilled alone (always False off the seq-sharded path)
        req.long_prompt = self._sp > 1 and int(ids.size) > self.prompt_pane
        # router hop (serving/router.py): the dispatch decision precedes
        # the Request's existence, so it arrives as data and lands on the
        # span tree as a `router` child — even for requests turned away
        # by the shed/queue-full decisions below
        req.route = route
        if params.deadline_s is not None:
            # SLO-aware rejection: estimated completion = (queue position
            # / n_slots) x EWMA per-request service time + the request's
            # own decode budget x TPOT. Predictably blowing the deadline
            # gets a useful 429 NOW instead of a useless 504 later.
            # The whole decision runs under the engine lock: the EWMAs
            # and the shed counter mutate under it, and the pre-fix
            # lock-free reads were exactly the unguarded-EWMA access
            # class graft-lint GL031 now flags. TIMED acquire: a wedged
            # tick may hold this lock forever (and drain/restart later
            # abandon it, not release it) — a submit racing the wedge
            # window must stay bounded, so on timeout the shed check is
            # skipped and the request admitted optimistically (the queue
            # TTL expiry still protects its deadline downstream).
            lock = self._lock
            shed = False
            locked = lock.acquire(timeout=1.0)
            try:
                if locked:
                    est = self.estimate_completion_s(
                        len(self.queue), params.max_new_tokens)
                    shed = est is not None and est > params.deadline_s
                    if shed:
                        self.requests_shed += 1
                        retry = round(max(self.estimate_queue_clear_s()
                                          or 0.0, 0.001), 3)
            finally:
                if locked:
                    lock.release()
            if shed:
                self.slo_window.observe(miss=True)
                req.error = (f"shed at submit: estimated completion "
                             f"{est:.2f}s > deadline {params.deadline_s}s")
                req.finish_reason = FINISH_SHED
                req.state = REJECTED
                req.t_finish = time.monotonic()
                self._ev(
                    "request_shed", request_id=req.id,
                    reason="slo_predicted_miss",
                    queue_depth=len(self.queue),
                    deadline_s=params.deadline_s,
                    estimated_e2e_s=round(est, 4), retry_after_s=retry)
                self._emit_span(req)
                req._mark_done()
                raise SLOShedError(
                    f"deadline {params.deadline_s}s unmeetable: estimated "
                    f"completion {est:.2f}s at queue depth "
                    f"{len(self.queue)}", retry_after_s=retry)
        try:
            self.queue.put(req, block=block, timeout=timeout)
        except QueueFullError:
            req.state = REJECTED
            req.finish_reason = FINISH_REJECTED
            req.t_finish = time.monotonic()
            with self._lock:                   # submit() is thread-safe
                self.requests_rejected += 1
            self._ev("request_rejected", request_id=req.id,
                                reason="queue_full",
                                queue_depth=len(self.queue))
            self._emit_span(req)
            req._mark_done()
            raise
        if self._dead is not None or self._draining:
            # raced _fail_all/drain: a blocked put() can be woken by the
            # death/drain queue sweep and append into an engine that will
            # never process it — fail it here instead of hanging result()
            msg = self._dead or "engine is draining"
            if self.queue.remove(req):
                # still queued: we own it — retire it here
                req.error = msg
                req.finish_reason = (FINISH_ERROR if self._dead
                                     else FINISH_PREEMPTED)
                req.state = FINISHED
                req._mark_done()
            elif self._draining and self._dead is None:
                # the decode loop popped it first: admission beat the
                # drain, the request IS being served and drain will let
                # it finish — force-finishing here would double-finish a
                # live request. Hand the caller its (valid) handle.
                with self._work:
                    self._work.notify()
                return req
            # remove failed + dead: the _fail_all sweep already retired it
            if self._dead is not None:
                raise RuntimeError(f"engine is dead: {self._dead}")
            raise EngineDrainingError("engine is draining: admission closed")
        with self._work:
            self._work.notify()
        return req

    def adopt(self, req: Request, timeout: float = 5.0) -> None:
        """Enqueue an EXISTING queued ``Request`` (the router's drain
        re-dispatch: work stolen from a draining replica's queue moves to
        a live one without the client's handle changing). BOUNDED
        blocking backpressure: past ``timeout`` a full (or wedged-loop)
        target raises ``QueueFullError`` so the re-dispatcher can fall
        through to another replica — an unbounded wait here would hang
        the whole rolling drain behind one stuck engine."""
        if self._dead is not None:
            raise RuntimeError(f"engine is dead: {self._dead}")
        if self._draining:
            raise EngineDrainingError(
                "engine is draining: admission closed")
        self.queue.put(req, block=True, timeout=timeout)
        with self._work:
            self._work.notify()

    def service_snapshot(self) -> dict:
        """Router-facing load/liveness snapshot (one per dispatch
        decision). TIMED lock acquire: a wedged replica must never hang
        fleet dispatch — on timeout the lock-free attr reads are stale
        but safe (worst case one misrouted request, which the target's
        own admission stack still protects)."""
        lock = self._lock
        locked = lock.acquire(timeout=0.2)
        try:
            return {
                "queue_depth": len(self.queue),
                "queue_capacity": self.queue.max_size,
                "n_active": self.scheduler.n_active,
                "n_slots": self.n_slots,
                "tpot_ewma": self._tpot_ewma,
                "tokens_ewma": self._tokens_ewma,
                "draining": self._draining,
                "dead": self._dead is not None,
            }
        finally:
            if locked:
                lock.release()

    # -- SLO service estimate ---------------------------------------------

    # holds: _lock
    def estimate_completion_s(self, queue_depth: int,
                              max_new_tokens: int) -> Optional[float]:
        """Predicted submit->finish seconds for a request entering the
        queue at ``queue_depth``: (queue position + the already-RUNNING
        requests, counted half-done on average) x the EWMA per-request
        service time (spread over ``n_slots`` concurrent rows) + its own
        decode budget at the EWMA TPOT. Without the in-flight term a
        full-slots/empty-queue engine would predict zero wait and admit
        requests straight into a TTL expiry. None until at least one
        request has finished (no history — admission stays optimistic).
        The math itself lives in module-level ``service_estimate`` — the
        router's fleet dispatch computes the SAME estimate from replica
        snapshots, and the two deciding differently about "predicted
        miss" would route requests into immediate sheds."""
        return service_estimate(queue_depth, self.scheduler.n_active,
                                self.n_slots, self._tpot_ewma,
                                self._tokens_ewma, max_new_tokens)

    # holds: _lock
    def estimate_queue_clear_s(self) -> Optional[float]:
        """Rough seconds until the current backlog drains (Retry-After
        material for 429/503 responses)."""
        return queue_clear_estimate(len(self.queue),
                                    self.scheduler.n_active, self.n_slots,
                                    self._tpot_ewma, self._tokens_ewma)

    # holds: _lock
    def _observe_service_time(self, req: Request) -> None:
        """Fold one finished request into the TPOT/length EWMAs (only
        normal completions: failed/expired requests have no useful
        service signature)."""
        tpot = req.tpot_s()
        n_tok = len(req.output_ids)
        if tpot is None or n_tok < 1:
            return
        alpha = 0.2
        self._tpot_ewma = (tpot if self._tpot_ewma is None
                           else (1 - alpha) * self._tpot_ewma
                           + alpha * tpot)
        self._tokens_ewma = (float(n_tok) if self._tokens_ewma is None
                             else (1 - alpha) * self._tokens_ewma
                             + alpha * n_tok)

    # -- admission-boundary shed ------------------------------------------

    # holds: _lock
    def _admission_skip(self, req: Request) -> bool:
        """Scheduler skip hook: shed expired/cancelled requests the moment
        they reach the queue head, without consuming a slot."""
        if req._cancelled:
            self._fail_request(None, req, "cancelled while queued",
                               reason="cancelled", finish=FINISH_CANCELLED)
            return True
        if req.expired():
            self.requests_expired += 1
            self.slo_window.observe(miss=True)
            waited = time.monotonic() - req.t_submit
            req.error = (f"deadline {req.params.deadline_s}s passed after "
                         f"{waited:.2f}s in queue")
            req.finish_reason = FINISH_EXPIRED
            req.state = FINISHED
            req.t_finish = time.monotonic()
            self._ev("request_expired", request_id=req.id,
                                reason="deadline_expired",
                                deadline_s=req.params.deadline_s,
                                queue_wait_s=round(waited, 4),
                                queue_depth=len(self.queue))
            self._emit_span(req)
            req._mark_done()
            return True
        return False

    # holds: _lock
    def _admit(self, slot: int, req: Request, gen: int) -> None:
        """Prefill one admitted request into ``slot``. Fault-isolated: a
        host-side fault on THIS request's path (injected prefill fault,
        raising client callback, detok error) fails it alone and frees the
        slot — co-resident requests never see it. (Device-side faults that
        poison the whole batch escape to the loop and go through the
        supervisor restart instead.)

        ``gen`` is the caller's generation stamp: the prefill device call
        is a wedge point the supervisor may abandon, so a thread that
        un-wedges here must re-check before committing the new cache —
        otherwise it would overwrite the restarted engine's fresh KV."""
        import jax

        Tp = int(req.prompt_ids.size)   # graft-ok: GL011 host numpy size
        # explicit device_get: the ONLY sanctioned d->h idiom in the tick
        # path — the transfer-guard sentry (analysis/runtime.py) lets it
        # through while failing any implicit fetch that sneaks in
        base_key = jax.device_get(_prng_key(req.params.seed))
        temp = np.float32(req.params.temperature)
        topk = np.int32(req.params.top_k or 0)
        adapter_row = np.int32(-1)
        if req.params.adapter is not None:
            # re-resolve by NAME at admission: submit's check only gates
            # entry — a hot evict (or evict+reload into another row)
            # while the request sat queued must bind the CURRENT row, or
            # fail this one request in isolation, never serve stale rows
            row = (self.adapters.lookup(req.params.adapter)
                   if self.adapters is not None else None)
            if row is None:
                self._fail_request(
                    slot, req,
                    f"adapter '{req.params.adapter}' evicted while queued",
                    reason="adapter_not_loaded")
                return
            adapter_row = np.int32(row)
        try:
            self.hooks.before_prefill(req)
        except Exception as e:  # noqa: BLE001 — poison request, isolate
            if self._generation != gen:
                return      # restart already failed this request
            self._fail_request(slot, req, f"prefill failed: {e!r}",
                               reason="prefill_error")
            return
        if self.kv_policy.prefill_chunk > 0:
            self._admit_chunked(slot, req, gen, base_key, temp, topk,
                                adapter_row)
            return
        # monolithic tier only: bucket-pad the whole prompt (the chunked
        # tier builds its C-token chunk arrays per tick instead)
        Tpb = self._bucket_len(Tp)
        padded = np.zeros((1, Tpb), np.int32)
        padded[0, :Tp] = req.prompt_ids
        # the `prefill` phase spans dispatch THROUGH the ok-scalar sync:
        # the jitted call returns before the device finishes (async
        # dispatch), so timing the call alone would book the execution
        # wait into whatever host line happens to touch a result first
        with self._tl.span("prefill"):
            tok, ok, cache = self._prefill(
                self.cache, self._weights, padded, np.int32(Tp),
                np.int32(slot), base_key, temp, topk,
                *self._pool_args_for(adapter_row))
            if self._generation != gen:
                return          # abandoned mid-prefill: commit nothing
            self.cache = cache
            req.state = RUNNING
            req.slot = slot
            req.t_admit = time.monotonic()
            self._lengths[slot] = Tp
            self._n_gen[slot] = 0
            self._base_keys[slot] = base_key
            self._temps[slot] = temp
            self._topks[slot] = topk
            self._adapter_ids[slot] = adapter_row
            if self._hist is not None:
                self._hist[slot, :Tp] = req.prompt_ids
                self._hist_len[slot] = Tp
            if self.hooks.poison_nan(req):
                self._poison_slot_cache(slot)      # fault injection (tests)
            # explicit fetch; blocks until prefill ran
            ok_host = bool(jax.device_get(ok))
        if not ok_host:
            self._fail_request(slot, req,
                               "non-finite logits in prefill",
                               reason="non_finite_logits")
            return
        self._accept_token(slot, req, int(jax.device_get(tok)), gen)

    # -- chunked prefill + prefix cache ------------------------------------

    def _adapter_tag(self, req: Request) -> Optional[str]:
        """Prefix-store namespace for one request: the registry's LOAD
        tag (name + per-install sequence), so an adapter evicted and
        reloaded — possibly with different weights — can never hit the
        old install's panes. Base traffic shares one namespace. None —
        the adapter vanished between admission's row resolution and
        here (hot evict race) — means NO namespace: the request must
        neither hit another tenant's panes nor store its own under one,
        so the caller skips the prefix store entirely."""
        if req.params.adapter is None or self.adapters is None:
            return BASE_ADAPTER
        return self.adapters.load_tag(req.params.adapter)

    # holds: _lock
    def _admit_chunked(self, slot: int, req: Request, gen: int,
                       base_key, temp, topk, adapter_row) -> None:
        """Chunked admission: probe the prefix store, copy a hit's panes
        into the slot (one batched DUS program — zero forward FLOPs for
        the cached span), and queue the suffix for the per-tick chunk
        pump (``_chunk_tick``). The first sampled token arrives when the
        final chunk lands, so slot state is primed here but the request
        only joins the decode batch then."""
        Tp = int(req.prompt_ids.size)   # graft-ok: GL011 host numpy size
        pos = 0
        tag = (self._adapter_tag(req) if self.prefix_store is not None
               else None)
        if tag is not None:
            span, entry = self.prefix_store.match(req.prompt_ids, tag)
            if entry is not None:
                if not self._apply_prefix_hit(slot, req, gen, span, entry,
                                              late=False):
                    return      # abandoned mid-copy: commit nothing
                pos = span
            else:
                self._ev("prefix_miss", request_id=req.id,
                                    prompt_tokens=Tp,
                                    adapter=req.params.adapter)
        req.state = RUNNING
        req.slot = slot
        req.t_admit = time.monotonic()
        # slot state primed now; `_lengths` tracks the NEXT write
        # position while prefilling, so the decode step's garbage append
        # for this row lands exactly where the next chunk overwrites
        self._lengths[slot] = pos
        self._n_gen[slot] = 0
        self._base_keys[slot] = base_key
        self._temps[slot] = temp
        self._topks[slot] = topk
        self._adapter_ids[slot] = adapter_row
        if self._hist is not None:
            self._hist[slot, :Tp] = req.prompt_ids
            self._hist_len[slot] = Tp
        self._prefill_state[slot] = {
            "req": req, "pos": pos, "Tp": Tp, "base_key": base_key,
            "temp": temp, "topk": topk, "adapter_row": adapter_row,
            "stored": False,
        }

    # holds: _lock
    def _apply_prefix_hit(self, slot: int, req: Request, gen: int,
                          span: int, entry, late: bool,
                          prev_pos: int = 0) -> bool:
        """Copy a matched (pinned) entry's panes into ``slot`` and emit
        the hit. Returns False on a generation abort (nothing committed).
        ``late``: the catch-up hit — a mid-prefill slot jumping ahead on
        a pane a co-resident sharer just stored (see ``_chunk_tick``);
        ``prev_pos`` is the slot's already-prefilled position then, so
        the request's ``prefix_bytes_saved`` ledger counts only the NEW
        tokens the copy spared it from recomputing."""
        if self._paged:
            return self._apply_paged_hit(slot, req, gen, span, entry,
                                         late, prev_pos)
        with self._tl.span("prefix_copy"):
            try:
                cache = self._prefix_copy(self.cache, entry.panes,
                                          np.int32(slot))
            finally:
                self.prefix_store.release(entry)
        if self._generation != gen:
            return False
        self.cache = cache
        self.pane_copies += 1   # spy: paged mode asserts this stays 0
        # the exact quantity ROADMAP item 1 (paged KV) optimizes: KV
        # bytes this hit spared the request from recomputing
        req.prefix_bytes_saved += ((span - prev_pos)
                                   * self._kv_bytes_per_token)
        Tp = int(req.prompt_ids.size)   # graft-ok: GL011 host numpy size
        self._ev(
            "prefix_hit", request_id=req.id, span_tokens=span,
            prompt_tokens=Tp, key=entry.key, late=late,
            n_suffix_chunks=-(-(Tp - span)
                              // self.kv_policy.prefill_chunk),
            adapter=req.params.adapter)
        return True

    # holds: _lock
    def _apply_paged_hit(self, slot: int, req: Request, gen: int,
                         span: int, entry, late: bool,
                         prev_pos: int = 0) -> bool:
        """Paged prefix HIT: a host page-table write. The slot's leading
        columns point at the entry's SHARED refcounted pages — no device
        program, no copy, zero FLOPs/bytes for the cached span (the
        whole point of the page table). Incref FIRST, then retire the
        slot's old columns: a late hit's entry may share physical pages
        with the columns being replaced (a sharer stored a longer pane
        over the same prefix), and incref-before-decref keeps those
        pages alive through the swap."""
        pages = entry.pages
        try:
            for p in pages:
                self.page_pool.incref(p)
        finally:
            self.prefix_store.release(entry)
        old_cols = int(self._slot_cols[slot])  # graft-ok: GL011 host numpy
        old = [int(p)                          # graft-ok: GL011 host numpy
               for p in self._page_table[slot, :old_cols]]
        n_new = len(pages)          # == span // page_tokens, by insert
        self._page_table[slot, :n_new] = pages
        self._slot_cols[slot] = n_new
        for p in old:
            self.page_pool.decref(p)
        # refund the reservation for every column the share just covered:
        # admission reserved the full worst-case need assuming NO hit;
        # shared columns will never draw a fresh page
        refund = min(n_new - old_cols,
                     int(self._pages_reserved[slot]))  # graft-ok: GL011 host numpy
        if refund > 0:
            self.page_pool.unreserve(refund)
            self._pages_reserved[slot] -= refund
        req.prefix_bytes_saved += ((span - prev_pos)
                                   * self._kv_bytes_per_token)
        Tp = int(req.prompt_ids.size)   # graft-ok: GL011 host numpy size
        self._ev(
            "prefix_hit", request_id=req.id, span_tokens=span,
            prompt_tokens=Tp, key=entry.key, late=late,
            n_suffix_chunks=-(-(Tp - span)
                              // self.kv_policy.prefill_chunk),
            adapter=req.params.adapter)
        self._ev("page_share", request_id=req.id, slot=slot,
                 n_pages=n_new, span_tokens=span, late=late,
                 pool_free=self.page_pool.n_free)
        return True

    # holds: _lock
    def _chunk_tick(self, gen: int) -> bool:
        """ONE prefill chunk a tick, for the slot in mid-prefill that was
        admitted first (the others wait their turn: first come, first to
        its first token) — the per-tick prefill work is bounded by one
        C-token program, whatever the prompt lengths and however many
        slots are prefilling, so a token gap is one decode tick plus at
        most one chunk. (A chunk for EVERY such slot made a gap one tick
        plus k chunks, k the slots prefilling: the tail of the gaps then
        sat on the steps of k. PERF.md section 6, PR 28.) Returns False on
        a generation abort (the caller books tick wall and bails)."""
        import jax

        C = self.kv_policy.prefill_chunk
        slot = min(self._prefill_state, key=lambda s: (
            self._prefill_state[s]["req"].t_admit, s))
        st = self._prefill_state[slot]
        req: Request = st["req"]
        Tp = st["Tp"]
        span_cap = (self.prefix_store.storable_span(Tp)
                    if self.prefix_store is not None else 0)
        # catch-up probe: a slot co-admitted with the FIRST sharer of
        # a prefix missed at admission (the store was empty), but the
        # sharer's pane may have landed since (early insertion below)
        # — jump ahead by pane copy instead of recomputing chunks.
        # count_miss=False: only admission misses are workload misses
        tag = (self._adapter_tag(req)
               if self.prefix_store is not None and st["pos"] < span_cap
               else None)
        if tag is not None:
            span, entry = self.prefix_store.match(
                req.prompt_ids, tag,
                min_span=st["pos"], count_miss=False)
            if entry is not None:
                if not self._apply_prefix_hit(slot, req, gen, span,
                                              entry, late=True,
                                              prev_pos=st["pos"]):
                    return False
                st["pos"] = span
                self._lengths[slot] = span
        with self._tl.span(self._prefill_phase):
            lo = st["pos"]
            hi = min(lo + C, Tp)
            chunk = np.zeros((1, C), np.int32)
            chunk[0, : hi - lo] = req.prompt_ids[lo:hi]
            if self._paged:
                # back the chunk's real columns with pages; the pad
                # tail's columns stay unmapped and scatter into trash
                self._ensure_pages(slot, hi)
            tok, ok, cache = self._prefill_chunk(
                self.cache, self._weights, chunk, np.int32(lo),
                np.int32(Tp), np.int32(slot),
                st["base_key"], st["temp"], st["topk"],
                *self._paged_tail(self._pool_args_for(st["adapter_row"]), 3))
            if self._generation != gen:
                return False    # abandoned mid-chunk: commit nothing
            self.cache = cache
            st["pos"] = lo + C
            self.prefill_chunks += 1
            self._tick_rec["chunks"] = self._tick_rec.get("chunks", 0) + 1
            self._tick_rec["chunk_tokens"] = (
                self._tick_rec.get("chunk_tokens", 0) + hi - lo)
            # inside the span: the phases of a tick add up to its wall
            self._tick_rec["chunk_kv_touched"] = (
                self._tick_rec.get("chunk_kv_touched", 0)
                + sum(chunk_positions_read(lo, hi, C, buffer) if kernel
                      else buffer for kernel, buffer in self._chunk_reads))
        # EARLY insertion: the moment the chunk covering the storable
        # span lands, the pane [0, span) is final — store it NOW so
        # co-admitted sharers (still mid-prefill behind us) catch up
        # this very tick instead of after our whole prompt
        if (self.prefix_store is not None and not st["stored"]
                and 0 < span_cap <= st["pos"]):
            st["stored"] = True
            self._maybe_store_prefix(slot, req, gen)
            if self._generation != gen:
                return False
        if st["pos"] < Tp:
            self._lengths[slot] = st["pos"]
            return True
        # final chunk: the request's first token. Explicit fetch —
        # the ONLY chunk that syncs (mirrors the legacy prefill)
        with self._tl.span(self._prefill_phase):
            ok_host = bool(jax.device_get(ok))
        del self._prefill_state[slot]
        self._lengths[slot] = Tp
        if self.hooks.poison_nan(req):
            self._poison_slot_cache(slot)  # fault injection (tests)
        if not ok_host:
            self._fail_request(slot, req,
                               "non-finite logits in prefill",
                               reason="non_finite_logits")
            return True
        self._accept_token(slot, req, int(jax.device_get(tok)), gen)
        if self._generation != gen:
            return False
        return True

    # holds: _lock
    def _maybe_store_prefix(self, slot: int, req: Request,
                            gen: int) -> None:
        """After a completed prefill, extract the slot's chunk-aligned
        prefix pane and insert it into the store (miss path only — a
        present key is just touched). Runs BEFORE the first decode
        append, so the pane is a pure function of (prefix tokens,
        params, adapter); the extract program additionally zero-clamps
        everything past the span (byte-determinism — see
        ``kvcache.extract_prefix_panes``)."""
        if self.prefix_store is None:
            return
        Tp = int(req.prompt_ids.size)   # graft-ok: GL011 host numpy size
        span = self.prefix_store.storable_span(Tp)
        if span <= 0:
            return
        tag = self._adapter_tag(req)
        if tag is None:
            return      # adapter evicted mid-flight: no namespace to own
        prefix_ids = req.prompt_ids[:span]
        if self.prefix_store.contains(prefix_ids, tag):
            return
        if self._paged:
            # paged store = publish the slot's OWN leading pages under
            # the key (the store increfs them) — no extract program, no
            # copy, no new bytes allocated. span is chunk-aligned and
            # C % P == 0, so the span covers whole pages exactly.
            n_cols = span // self.kv_policy.page_tokens
            pages = [int(p)                    # graft-ok: GL011 host numpy
                     for p in self._page_table[slot, :n_cols]]
            nbytes = self.prefix_store.insert_pages(prefix_ids, tag,
                                                    pages)
            if nbytes:
                self._ev(
                    "prefix_insert", request_id=req.id,
                    span_tokens=span, bytes=nbytes,
                    entries=self.prefix_store.n_entries,
                    adapter=req.params.adapter)
            return
        with self._tl.span("prefix_copy"):
            panes = self._prefix_extract(self.cache, np.int32(slot),
                                         np.int32(span))
        if self._generation != gen:
            return
        nbytes = self.prefix_store.insert(prefix_ids, tag, panes)
        if nbytes:
            self._ev(
                "prefix_insert", request_id=req.id, span_tokens=span,
                bytes=nbytes, entries=self.prefix_store.n_entries,
                adapter=req.params.adapter)

    # holds: _lock
    def _poison_slot_cache(self, slot: int) -> None:
        """Overwrite one slot's KV rows with NaN (fault-injection hook):
        the next decode tick's logits for that row go non-finite IN-GRAPH,
        exercising the finite guard through the real compiled program —
        same shapes, zero recompiles, co-resident rows untouched (their
        attention never reads another slot's rows). int8 caches poison
        through the FLOAT leaves (the scale sidecars): int8 codes can't
        hold NaN, but a NaN scale makes every dequantized value NaN.

        Paged: NaN only the slot's PRIVATE pages (refcount 1). Shared
        pages belong to other tenants too — poisoning them would fail
        innocent co-sharers, which the contiguous fault model (slot
        isolation) never does."""
        import jax.numpy as jnp

        if self._paged:
            self._rewrite_slot_pages(slot, np.nan)
            return

        def nan_row(layer):
            if layer is None or not jnp.issubdtype(layer.dtype,
                                                    jnp.floating):
                return layer
            host = np.asarray(layer).copy()
            host[slot] = np.nan
            if self.mesh_plan is not None:
                # keep the pinned cache sharding: a default-device
                # rebuild would change the compiled programs' arg
                # signature (a recompile) on a mesh-placed engine
                import jax

                return jax.device_put(host, layer.sharding)
            return jnp.asarray(host)

        self.cache = {name: [nan_row(buf) for buf in bufs]
                      for name, bufs in self.cache.items()}

    # -- paged page accounting (host bookkeeping; the jitted programs
    # only ever see the resulting table as traced data) -------------------

    # holds: _lock
    def _page_need(self, req: Request) -> int:
        """Worst-case page count for one request: whole prompt plus
        max_new_tokens plus spec headroom, capped at the slot window."""
        Tp = int(req.prompt_ids.size)   # graft-ok: GL011 host numpy size
        toks = min(Tp + req.params.max_new_tokens + self.spec_k,
                   self._cache_len)
        return -(-toks // self.kv_policy.page_tokens)

    # holds: _lock
    def _admit_pages(self, slot: int, req: Request) -> bool:
        """Paged admission gate: reserve the request's WORST-CASE page
        need up front — admission checks free pages, not free slots.
        Refusal is the oversubscription policy made explicit: the
        request bounces back to the queue head and waits for a
        retirement, instead of deadlocking mid-decode on a dry pool. A
        later prefix hit refunds the shared columns' reservation."""
        if not self._paged:
            return True
        need = self._page_need(req)
        pool = self.page_pool
        if need > pool.n_pages - 1:
            # can NEVER fit (worst case exceeds the whole usable pool):
            # bouncing would livelock the queue head — fail it loudly,
            # like an over-long prompt. Returns None so the admission
            # loop skips _admit (the slot was already freed here).
            self._fail_request(
                slot, req,
                f"request needs up to {need} KV pages but the pool "
                f"holds {pool.n_pages - 1}: shorten the request or "
                "size pool_pages for at least one worst-case request",
                reason="page_pool_too_small")
            return None
        if pool.available() < need:
            if not self._pool_exhausted_logged:
                # one-shot per exhaustion episode (cleared when a slot
                # next returns pages) — steady-state refusals must not
                # spam the event log
                self._pool_exhausted_logged = True
                self._ev("page_pool_exhausted", request_id=req.id,
                         pages_needed=need,
                         pages_available=pool.available())
            return False
        pool.reserve(need)
        self._pages_reserved[slot] = need
        self._ev("page_admit", request_id=req.id, slot=slot,
                 pages_reserved=need, pool_free=pool.n_free)
        return True

    # graft: hot-path
    # holds: _lock
    def _ensure_pages(self, slot: int, n_tokens: int) -> None:
        """Map enough table columns for ``n_tokens`` tokens, drawing
        from this slot's admission reservation (never from the open
        pool — reserving at admission is what makes mid-flight
        exhaustion impossible). Host numpy + integer bookkeeping only;
        unmapped columns stay 0 = the pinned trash page."""
        P = self.kv_policy.page_tokens
        cols = int(self._slot_cols[slot])   # graft-ok: GL011 host numpy
        want = -(-min(n_tokens, self._cache_len) // P)
        want = min(want, self._pages_per_slot,
                   cols + int(self._pages_reserved[slot]))  # graft-ok: GL011 host numpy
        while cols < want:
            page = self.page_pool.alloc(from_reserved=True)
            self._pages_reserved[slot] -= 1
            self._page_table[slot, cols] = page
            cols += 1
        self._slot_cols[slot] = cols

    # holds: _lock
    def _release_slot_pages(self, slot: int) -> None:
        """Retire/cancel/fail: decref every mapped column (pages shared
        with the prefix store or co-sharers survive; private ones return
        to the pool) and hand back the unused reservation — live
        capacity is bounded by tokens in flight, not n_slots x Tmax."""
        cols = int(self._slot_cols[slot])      # graft-ok: GL011 host numpy
        freed = 0
        for col in range(cols):
            if self.page_pool.decref(
                    int(self._page_table[slot, col])):  # graft-ok: GL011 host numpy
                freed += 1
        reserved = int(self._pages_reserved[slot])  # graft-ok: GL011 host numpy
        if reserved:
            self.page_pool.unreserve(reserved)
        self._page_table[slot, :] = 0
        self._slot_cols[slot] = 0
        self._pages_reserved[slot] = 0
        self._pool_exhausted_logged = False
        self._ev("page_release", slot=slot, n_pages=cols,
                 pages_freed=freed, pages_unreserved=reserved,
                 pool_free=self.page_pool.n_free)

    # holds: _lock
    def _rewrite_slot_pages(self, slot: int, value: float) -> None:
        """Host-rewrite the FLOAT leaves of the slot's PRIVATE pages
        (refcount 1; shared pages belong to co-sharers too). value=NaN
        is the fault-injection poison; value=0.0 is the recycling scrub:
        pool pages are read by every slot's gather, so a freed page
        still carrying NaN would re-enter the pool and poison whichever
        slot draws it next (masked attention weights are exactly 0.0,
        and 0.0 x NaN = NaN straight through the softmax) — a cross-slot
        blast radius the contiguous layout never had."""
        import jax.numpy as jnp

        mine = [int(p)
                for p in self._page_table[slot, :self._slot_cols[slot]]
                if int(p) != 0 and self.page_pool.refcount(int(p)) == 1]
        if not mine:
            return

        def rewrite(buf):
            if not jnp.issubdtype(buf.dtype, jnp.floating):
                return buf      # int8 codes: NaN rides the float scales
            host = np.asarray(buf).copy()
            host[mine] = value
            return jnp.asarray(host)

        self.cache = {name: [rewrite(buf) for buf in bufs]
                      for name, bufs in self.cache.items()}

    # -- tracing / tick accounting ----------------------------------------

    def _attention_read(self, cache, l: int) -> tuple:
        """(on the kernel's path, buffer length, ``head_dim``, window) of
        layer ``l``'s decode attention (``decode_attention_path``): what
        ``live_positions_read`` needs to count the kernel's reads; any other
        path reads the buffer whole. ``window``: a ring's, else None."""
        if self._paged:
            return False, self._cache_len, 0, None
        ring = self.cfg.layer_kind(l) == "sliding"
        _, _, Tmax, hd = cache["k"][l].shape
        return (decode_attention_path(
            cache, self.spec_k + 1, self.cfg.n_heads, layer=l,
            ring=ring) == "live_blocks", Tmax, hd,
            self.cfg.sliding_window if ring else None)

    def _chunk_read(self, cache, l: int) -> tuple:
        """(on the chunk kernel's path, key positions of the buffer) of
        layer ``l``'s chunk attention (``chunk_attention_path``): the kernel
        reads the live key blocks (``chunk_positions_read``), any other
        path the buffer whole, the page table's a gathered view of the
        row's logical length."""
        if self._paged:
            return False, self._cache_len
        return (chunk_attention_path(
            cache, self.kv_policy.prefill_chunk, self.cfg.n_heads,
            layer=l) == "live_blocks", cache["k"][l].shape[2])

    def _kv_positions_read(self, decoding) -> tuple:  # holds: _lock
        """Cache positions this tick's attention has to read, and those it
        does read. Has to: each decoding row's live positions (the one it
        appends included), summed over the layers, a window layer counting
        no more than its window. Does (``_attn_reads``): a layer on the
        kernel's path what ``live_positions_read`` counts for the rows the
        program is handed (every row's block-rounded length, a free slot's
        too; where the tick names the rows that decode, ``_step_tail``,
        and the kernel reads by them, theirs alone, a ring's the blocks its
        window reaches), any other every row's whole buffer."""
        lengths = self._lengths.tolist()    # plain ints: a few us a tick
        live = [lengths[s] + 1 for s, _ in decoding]
        n_window = self._n_window_layers
        total = (self.cfg.n_layers - n_window
                 - self._n_state_layers) * sum(live)
        if n_window:
            window = self.cfg.sliding_window
            total += n_window * sum(min(n, window) for n in live)
        kv_length, rows = self._lengths + 1, None
        if self.cfg.is_moe or self._n_state_layers:
            rows = np.zeros((self.n_slots,), np.bool_)
            rows[[s for s, _ in decoding]] = True
        touched = sum(
            layers * (live_positions_read(kv_length, buffer, hd, live=rows,
                                          window=window)
                      if kernel else self.n_slots * buffer)
            for (kernel, buffer, hd, window), layers
            in self._attn_reads.items())
        return total, touched

    def _emit_span(self, req: Request) -> None:
        """Write the request's one terminal ``span`` row (request tree:
        queued/prefill/decode children under a root ``request`` span).
        Every terminal transition calls this exactly once."""
        get_metrics().log_span(**req.trace_row())

    # holds: _lock
    def _book_tick(self, tl: StepTimeline, rec: dict, t0: float,
                   t1: float) -> None:
        """Close one tick's account: drain its spans into the cumulative
        totals, fold its prefill+prefix-copy seconds into
        ``tick_prefill_hist`` (the per-tick distribution the chunking A/B
        reads) and keep one record of it (obs/schema.TICK_RECORD_FIELDS).
        Called on EVERY exit from the timed part of ``step()`` —
        generation-abort returns and raising ticks included, which have
        already booked phase seconds: skipping the wall there would let
        the phases sum past ``tick_seconds_total``."""
        ends = tl.ends
        phases = tl.drain()  # graft-ok: GL032 StepTimeline's drain, not the engine's
        del phases["steps"]
        for ph, dt in phases.items():
            self.tick_phase_totals[ph] += dt
        self.tick_seconds_total += t1 - t0
        pf = sum(phases.get(ph, 0.0)
                 for ph in ("prefill", "prefill_shard", "prefix_copy"))
        if pf > 0:
            self.tick_prefill_hist.observe(pf)
        rec.update(tick=self.n_ticks, t0=t0, t1=t1, phases=phases,
                   n_slots=self.n_slots)
        if "decode_dispatch" in ends:
            rec["t_dispatch"] = ends["decode_dispatch"]
        if "host_fetch" in ends:
            rec["t_fetch"] = ends["host_fetch"]
        if self.replica is not None:
            rec["replica"] = self.replica
        get_metrics().keep_record("tick", rec)

    # -- the tick ---------------------------------------------------------

    def step(self) -> bool:
        """One engine tick: admit into free slots, then one batched decode
        step over the slot batch. Returns False when fully idle (no active
        slots and nothing queued).

        Generation-guarded: ``_restart`` bumps ``self._generation`` and
        replaces the lock, so a tick that un-wedges AFTER the supervisor
        abandoned it discovers the bump at the next checkpoint and returns
        without committing any state into the restarted engine."""
        gen = self._generation
        lock = self._lock
        tl = self._tl
        # the `tick` step is open over the wait for the lock and the
        # booking too, so that a trace finds all of step() in a span
        n_step = self.n_ticks + 1  # graft-ok: GL031 a label for the trace; only ticks write it
        with annotate_step(TICK_STEP, step_num=n_step):
            with lock:
                if self._generation != gen or self._dead is not None:
                    return False
                n0 = self.n_ticks
                rec = self._tick_rec = {"rows": 0, "admitted": 0,
                                        "queue_depth": len(self.queue)}
                t0 = time.perf_counter()
                try:
                    return self._tick(gen)
                finally:
                    t1 = time.perf_counter()
                    with annotate(TICK_BETWEEN):
                        self._book_tick(tl, rec, t0, t1)
                        if self.n_ticks != n0:      # a raising tick counts none
                            self._maybe_log_metrics()

    # holds: _lock
    def _tick(self, gen: int) -> bool:
        """The timed part of ``step()``. Every phase is a span of
        ``self._tl``; nested spans (a prefill or a client callback inside
        ``admit``, callbacks inside ``sample_commit``) book their own
        seconds and the outer phase keeps the remainder."""
        import jax

        self.hooks.before_tick(self)       # injected hang/fault point
        if self._generation != gen:
            return False
        with self._tl.span("admit"):
            # re-run admission until no progress: a request can finish
            # DURING admission (eos on its first sampled token, or
            # max_new_tokens=1), freeing its slot after admit_from already
            # returned — without the retry those queued behind it would
            # strand (step() would report idle with a non-empty queue)
            while True:
                admitted = self.scheduler.admit_from(
                    self.queue, skip=self._admission_skip)
                bounced = None
                for i, (slot, req) in enumerate(admitted):
                    # paged oversubscription: admission is gated on FREE
                    # PAGES (this request's worst-case need), not free
                    # slots — a slot with no backing memory must not run
                    ok = self._admit_pages(slot, req)
                    if ok is None:
                        continue  # failed permanently (slot already freed)
                    if not ok:
                        bounced = i
                        break
                    self._tick_rec["admitted"] += 1
                    self._admit(slot, req, gen)
                    if self._generation != gen:
                        return False
                if bounced is not None:
                    # hand the refused head — and everything admit_from
                    # popped behind it — back to the queue in reverse, so
                    # FCFS order survives the bounce; retry next tick
                    # once retirements have returned pages to the pool
                    for slot, req in reversed(admitted[bounced:]):
                        self.scheduler.retire(slot)
                        self.queue.put_front(req)
                    break
                if not admitted:
                    break
            # client cancellations retire at the tick boundary: the slot
            # frees NOW instead of decoding to max_new_tokens for nobody
            # (mid-prefill slots included: _free_slot drops their state)
            for slot, req in self.scheduler.active():
                if req._cancelled:
                    self._fail_request(slot, req, "cancelled by client",
                                       reason="cancelled",
                                       finish=FINISH_CANCELLED)
        # chunked-prefill pump: one C-token chunk per mid-prefill
        # slot, BEFORE the decode step — a slot whose final chunk
        # lands here joins this very tick's decode batch (the same
        # admit-then-decode cadence the monolithic path has)
        if self._prefill_state and not self._chunk_tick(gen):
            return False
        active = self.scheduler.active()
        if not active:
            # all slots free. Legacy: admission drained the queue
            # too. Chunked: a first-token eos inside _chunk_tick can
            # free the last slot with requests still queued — report
            # progress so the next tick admits them (an admission-
            # only tick still books its wall time so phases keep
            # summing to it)
            return len(self.queue) > 0
        # mid-prefill slots ride through the fixed-shape decode step
        # as ignored rows (their garbage append lands at the next
        # chunk's write position — see _admit_chunked); with NO row
        # actually decoding, skip the step entirely
        decoding = [(s, r) for s, r in active
                    if s not in self._prefill_state]
        if not decoding:
            self.n_ticks += 1
            return True
        self._tick_rec["rows"] = len(decoding)
        if self.spec_k:
            # speculative tick: draft k per slot, ONE verify forward,
            # multi-token commit (serving/spec.py + _verify_tick)
            return self._verify_tick(decoding, gen)
        if self._paged:
            # grow each decoding slot's table BEFORE dispatch: the
            # append lands at column lengths//P, which must point at
            # a real page (mid-prefill rows ride as ignored garbage
            # into the pinned trash page — no allocation for them)
            for slot, _req in decoding:
                self._ensure_pages(
                    slot, int(self._lengths[slot]) + 1)  # graft-ok: GL011 host numpy
        with self._tl.span("decode_dispatch"):
            # inside the span: the phases of a tick add up to its wall
            (self._tick_rec["kv_positions"],
             self._tick_rec["kv_touched"]) = self._kv_positions_read(decoding)
            if self._n_state_layers:
                # the states this tick has to read and write (a decoding
                # row's, a layer that holds one) and those its step does:
                # theirs alone in a layer on the walk's path, every row's
                # in any other
                self._tick_rec["state_rows"] = (
                    len(decoding) * self._n_state_layers)
                self._tick_rec["state_rows_touched"] = state_rows_walked(
                    len(decoding), self.n_slots, self._state_walks,
                    self._n_state_layers - self._state_walks)
            nxt, ok, cache = self._decode(
                self.cache, self._weights, self._last_tokens, self._lengths,
                self._base_keys, self._n_gen, self._temps,
                self._topks, *self._step_tail())
        if self._generation != gen:
            return False
        # `host_fetch` covers the donated-cache rebind AND the two
        # device->host fetches: dropping the old (donated-away)
        # cache arrays and the device_get both block on the in-flight
        # step, so this phase is "waiting for the device to catch up".
        # EXPLICIT device_get, never np.asarray/float(): these are
        # the tick's only two sanctioned d->h transfers, and the
        # transfer-guard sentry test proves nothing implicit remains
        with self._tl.span("host_fetch"):
            self.cache = cache
            nxt = jax.device_get(nxt)
            ok_rows = jax.device_get(ok)
            if self.cfg.is_moe:
                # rode the same transfer: (layers, held experts) rows
                ok_rows, by_layer = ok_rows
                self._tick_rec["expert_rows"] = by_layer.sum(0).tolist()
                self._tick_rec["experts_touched"] = int(
                    np.count_nonzero(by_layer))
        # the fetch is a wedge point: a tick the supervisor abandoned in it
        # must not open a span on the timeline `_restart` has put in place
        if self._generation != gen:
            return False
        with self._tl.span("sample_commit"):
            for slot, req in decoding:
                # a slow-client hook inside _accept_token is a wedge point
                # the supervisor may abandon mid-loop — stop committing
                # rows the moment the generation moves on
                if self._generation != gen:
                    return False
                # this tick wrote the slot's previous token at _lengths
                self._lengths[slot] += 1
                if not bool(ok_rows[slot]):
                    self._fail_request(
                        slot, req,
                        f"non-finite logits at token {len(req.output_ids)}",
                        reason="non_finite_logits")
                    continue
                self._accept_token(slot, req, int(nxt[slot]), gen)
        self.n_ticks += 1
        return True

    def run_until_idle(self) -> None:
        while self.step():
            pass

    # holds: _lock
    def _verify_tick(self, decoding, gen: int) -> bool:
        """One speculative tick: propose k drafts per decoding slot
        (host-side, ``drafter.propose`` against the slot's own history),
        run THE one compiled verify program over all slots, and commit
        each row's longest-accepted prefix — 1..k+1 tokens per slot per
        tick, every count through the same program signature (zero
        recompiles across acceptance churn, watcher-enforced).

        Rows whose request opted out (``SamplingParams.spec=False``)
        ride the same program with their commit clamped to one token —
        per-request semantics cost no extra programs. Mid-prefill slots
        were already filtered out of ``decoding`` by the caller and ride
        as ignored rows inside the program, exactly as in the plain
        decode tick. Returns False on a generation abort, mirroring
        ``_tick``'s decode block."""
        import jax

        k = self.spec_k
        with self._tl.span("draft"):
            drafts = np.zeros((self.n_slots, k), np.int32)
            for slot, req in decoding:
                if req.params.spec:
                    n_hist = self._hist_len[slot]
                    drafts[slot] = self.drafter.propose(
                        self._hist[slot, :n_hist], k)
            tokens_in = np.concatenate(
                [self._last_tokens[:, None], drafts], axis=1)
        if self._paged:
            # verify appends k+1 candidates at lengths..lengths+k; the
            # spec headroom (_cache_len = max_len + spec_k) guarantees
            # those columns exist for decoding rows
            for slot, _req in decoding:
                self._ensure_pages(
                    slot, int(self._lengths[slot]) + 1 + k)  # graft-ok: GL011 host numpy
        with self._tl.span("decode_dispatch"):
            toks, n_acc, ok, cache = self._verify(
                self.cache, self._weights, tokens_in, self._lengths,
                self._base_keys, self._n_gen, self._temps, self._topks,
                *self._step_tail())
        if self._generation != gen:
            return False
        # ONE explicit fetch for the tick's three results (+ the donated
        # cache rebind) — the same sanctioned d->h discipline as the
        # plain decode tick
        with self._tl.span("host_fetch"):
            self.cache = cache
            toks, n_acc, ok_rows = jax.device_get((toks, n_acc, ok))
        if self._generation != gen:
            return False
        with self._tl.span("sample_commit"):
            for slot, req in decoding:
                if self._generation != gen:
                    return False
                if not bool(ok_rows[slot]):
                    self._fail_request(
                        slot, req,
                        f"non-finite logits at token {len(req.output_ids)}",
                        reason="non_finite_logits")
                    continue
                is_spec = req.params.spec
                n_commit = 1 + (int(n_acc[slot]) if is_spec else 0)
                if is_spec:
                    # acceptance telemetry counts the IN-GRAPH decision
                    # (drafter quality), independent of host truncation
                    # at eos/budget below
                    accepted = int(n_acc[slot])
                    req.spec_drafted += k
                    req.spec_accepted += accepted
                    self.spec_tokens_drafted += k
                    self.spec_tokens_accepted += accepted
                for j in range(n_commit):
                    # each commit advances the row's valid-KV prefix by
                    # one: position j's entry was appended by THIS tick's
                    # verify (the trailing rejected entries stay past the
                    # prefix, masked everywhere and overwritten next tick)
                    self._lengths[slot] += 1
                    self._accept_token(slot, req, int(toks[slot, j]), gen)
                    if self._generation != gen:
                        return False
                    if req.done:
                        break           # eos/budget/fault: slot already freed
        self.n_ticks += 1
        return True

    # holds: _lock
    def _accept_token(self, slot: int, req: Request, tok: int,
                      gen: int) -> None:
        eos = resolve_eos(req.params, self.cfg.eos_id)
        if eos is not None and tok == eos:
            # the triggering eos is dropped (generate()'s per-row
            # semantics) and the slot frees this boundary
            self._finish(slot, req, FINISH_EOS)
            return
        now = time.monotonic()
        if req.t_first_token is None:
            req.t_first_token = now
        req.output_ids.append(tok)
        self._last_tokens[slot] = tok
        self._n_gen[slot] = len(req.output_ids)
        if self._hist is not None:
            # committed token enters the drafter's haystack (the dropped
            # eos above never does — it is not part of the sequence)
            self._hist[slot, self._hist_len[slot]] = tok
            self._hist_len[slot] += 1
        self.tokens_generated += 1
        try:
            # the request's OWN host path: detok + client callback. A
            # fault here (raising on_token, tokenizer bug on this output)
            # is this request's problem alone — fail it, free the slot,
            # co-residents decode on undisturbed
            with self._tl.span("callback_detok"):
                piece = self._detok_piece(req)
                if req.on_token is not None:
                    req.on_token(req, tok, piece)
                self.hooks.after_token(req, tok)   # injected slow-client point
        except Exception as e:  # noqa: BLE001 — poison request, isolate
            if self._generation != gen:
                return      # restart already failed this request
            self._fail_request(slot, req, f"token callback failed: {e!r}",
                               reason="callback_error")
            return
        if self._generation != gen:
            # the callback/hook above is a wedge point — un-wedging after
            # a supervisor restart must not finish/free slots that now
            # belong to the restarted engine
            return
        if piece:
            req._push_piece(piece)
        if len(req.output_ids) >= req.params.max_new_tokens:
            self._finish(slot, req, FINISH_LENGTH)

    #: max tokens a partial multi-byte char may hold back detokenization
    #: before committing anyway (bounds the re-decoded tail per token)
    _DETOK_HOLD_MAX = 16

    def _detok_piece(self, req: Request, final: bool = False) -> str:
        """Incremental detokenization: decode only the uncommitted tail
        (O(tail) per token, not O(total)). A tail ending in a replacement
        char is a partial multi-byte sequence the next token may complete
        — hold it (return "") rather than commit a mangled boundary,
        up to ``_DETOK_HOLD_MAX`` tokens; ``final`` flushes regardless."""
        if self.tokenizer is None:
            return ""
        tail_ids = req.output_ids[req._detok_start:]
        if not tail_ids:
            return ""
        try:
            tail = self.tokenizer.decode([int(t) for t in tail_ids])
        except Exception:                      # partial byte sequences etc.
            return ""
        if (not final and tail.endswith("�")
                and len(tail_ids) < self._DETOK_HOLD_MAX):
            return ""
        req.text += tail
        req._detok_start = len(req.output_ids)
        return tail

    # holds: _lock
    def _free_slot(self, slot: int) -> None:
        if self._paged:
            self._release_slot_pages(slot)
        self.scheduler.retire(slot)
        self._prefill_state.pop(slot, None)    # mid-prefill retirement
        self._lengths[slot] = 0
        self._last_tokens[slot] = 0
        self._n_gen[slot] = 0
        self._temps[slot] = 0.0
        self._topks[slot] = 0
        self._adapter_ids[slot] = -1
        self._hist_len[slot] = 0

    # holds: _lock
    def _count_adapter(self, req: Request, outcome: str) -> None:
        """Per-adapter request accounting (name "base" for un-adapted
        traffic): feeds the labeled /metrics series + serve_summary."""
        name = req.params.adapter or BASE_ADAPTER
        c = self._adapter_counts.setdefault(
            name, {"finished": 0, "failed": 0, "tokens": 0})
        c[outcome] += 1
        c["tokens"] += len(req.output_ids)

    # holds: _lock
    def _fail_request(self, slot: Optional[int], req: Request, msg: str,
                      reason: str, finish: str = FINISH_ERROR) -> None:
        """Fail ONE request (fault isolation): free its slot if it holds
        one, surface the error on the handle, emit ``request_failed`` with
        the machine-readable ``reason`` — the engine itself keeps serving.
        """
        if slot is not None and self.scheduler.slots[slot] is req:
            if self._paged and reason == "non_finite_logits":
                # scrub the failed slot's private pages to zero BEFORE
                # they return to the pool: unlike the contiguous layout,
                # freed pages are recycled into other slots, and a NaN
                # KV value reads through masked attention (0.0 x NaN)
                self._rewrite_slot_pages(slot, 0.0)
            self._free_slot(slot)
        req.error = msg
        req.finish_reason = finish
        req.state = FINISHED
        req.t_finish = time.monotonic()
        self.requests_failed += 1
        self._count_adapter(req, "failed")
        if req.params.deadline_s is not None and finish != FINISH_CANCELLED:
            # a failure is an SLO miss — except a client cancellation,
            # which is the CLIENT giving up; counting it would let
            # disconnect storms fire the burn-rate alert on a server
            # that met every deadline it was actually asked to meet
            self.slo_window.observe(miss=True)
        self._ev("request_failed", request_id=req.id,
                            reason=reason, error=msg, slot=slot,
                            n_tokens=len(req.output_ids),
                            adapter=req.params.adapter)
        self._emit_span(req)
        logger.warning("Request %d failed (%s): %s", req.id, reason, msg)
        req._mark_done()
        with self._work:
            self._work.notify_all()

    # holds: _lock
    def _finish(self, slot: int, req: Request, reason: str) -> None:
        tail = self._detok_piece(req, final=True)  # flush any held bytes
        if tail:
            req._push_piece(tail)
        req.state = FINISHED
        req.finish_reason = reason
        req.t_finish = time.monotonic()
        if self.scheduler.slots[slot] is req:  # not reassigned by restart
            # peak slot-KV attribution, read BEFORE the slot is freed:
            # lengths only grow over a request's residency, so the final
            # committed length IS the peak
            live = int(self._lengths[slot])  # graft-ok: GL011 host numpy
            req.kv_bytes_peak = live * self._kv_bytes_per_token
            self._free_slot(slot)
        self.requests_finished += 1
        self._count_adapter(req, "finished")
        self._observe_service_time(req)
        for hist, val in ((self.ttft_hist, req.ttft_s()),
                          (self.tpot_hist, req.tpot_s()),
                          (self.queue_wait_hist, req.queue_wait_s()),
                          (self.e2e_hist, req.e2e_s())):
            if val is not None:
                hist.observe(val)
        if req.params.deadline_s is not None:
            # SLO burn-rate: a completion is a miss when it beat the shed
            # machinery but still finished past its deadline
            e2e = req.e2e_s() or 0.0
            self.slo_window.observe(miss=e2e > req.params.deadline_s)
        sink = get_metrics()
        self._ev("request_done", **req.summary())
        self._emit_span(req)
        sink.gauge("slot_occupancy", self.scheduler.occupancy())
        sink.gauge("queue_depth", len(self.queue))
        req._mark_done()
        with self._work:
            self._work.notify_all()

    # holds: _lock
    def _cumulative(self) -> dict:
        """The counters a cadence row reports as differences, now."""
        return {"t": time.monotonic(), "wall": time.time(),
                "tokens": self.tokens_generated, "ticks": self.n_ticks,
                "tick_s": self.tick_seconds_total,
                "prefill_chunks": self.prefill_chunks,
                "spec_drafted": self.spec_tokens_drafted,
                "spec_accepted": self.spec_tokens_accepted,
                **self.tick_phase_totals}

    # holds: _lock
    def _maybe_log_metrics(self) -> None:
        if self.metrics_every <= 0 or self.n_ticks % self.metrics_every:
            return
        base, now = self._win_base, self._cumulative()
        self._win_base = now
        win = {k: now[k] - base[k] for k in now}
        sink = get_metrics()
        sink.gauge("slot_occupancy", self.scheduler.occupancy())
        sink.gauge("queue_depth", len(self.queue))
        sink.gauge("draining", 1.0 if self._draining else 0.0)
        slo = self.slo_window.ratio()
        if slo is not None:
            sink.gauge("slo_miss_ratio", round(slo, 6))
        # the window's tick-phase breakdown: wall-clock aggregates only
        # (perf_counter), fetched device values are NOT involved — the
        # per-tick host syncs stay exactly the two the decode loop always
        # had (next-token + ok mask; guard-tested)
        phases = {f"tick_{ph}_s": round(win[ph], 6) for ph in TICK_PHASES}
        kv = {}
        if self.kv_policy.prefill_chunk > 0:
            kv["prefill_chunks"] = win["prefill_chunks"]
        if self.spec_k:
            kv["spec_drafted"] = win["spec_drafted"]
            kv["spec_accepted"] = win["spec_accepted"]
        fleet = ({"replica": self.replica, "monotonic": False}
                 if self.replica is not None else {})
        sink.log_metrics(self.n_ticks, **fleet,
                         serve_tok_s=round(
                             win["tokens"] / max(win["t"], 1e-9), 2),
                         requests_finished=self.requests_finished,
                         tokens_generated=self.tokens_generated,
                         ticks_in_window=win["ticks"],
                         win_t0=round(base["wall"], 6),
                         win_dur_s=round(win["wall"], 6),
                         tick_total_s=round(win["tick_s"], 6),
                         **phases, **kv)
        # memory-ledger cadence: snapshot + drift/pressure detectors +
        # the memory_snapshot event the trace renders as counter tracks.
        # Pure nbytes/host math — the tick's device syncs stay the two
        # the decode loop always had (guard-tested)
        self.memory_ledger.observe(self.n_ticks)

    # -- warmup / compile discipline --------------------------------------

    def warmup(self) -> None:
        """Compile the legitimate program set up front, then freeze the
        watchers so any later signature is reported as a bucket-miss
        ``recompile``. Monolithic tier: one prefill per prompt bucket.
        Chunked tier (``kv_policy.prefill_chunk > 0``): ONE chunk
        program (+ the prefix copy/extract pair when the store is on) —
        chunk offset, prompt length, span and slot are all data, so the
        whole prefill tier warms in a constant number of compiles.
        Plus THE decode step either way. The warmup traffic runs through
        slot 0 with throwaway state; host state is reset after. Runs
        under the engine lock: warmup normally precedes ``start()``, but
        holding the lock makes a late warmup (or a concurrent early
        submit) safe instead of silently corrupting slot state."""
        import jax

        t0 = time.monotonic()
        build = lambda label: self._setup_tl.span(SETUP_BUILD_PREFIX + label)
        with self._setup_tl.span("warmup"), self._lock:
            zero_key = np.zeros_like(self._base_keys[0])
            # warm WITH the adapter-pool argument tail when a registry is
            # attached (id −1 = base): the adapter graph is part of THE
            # one decode program, so later adapter traffic — and every
            # hot-load, which swaps same-shaped pool arrays — hits the
            # frozen signature exactly
            if self.kv_policy.prefill_chunk > 0:
                buckets = [self.kv_policy.prefill_chunk]
                dummy = np.zeros((1, self.kv_policy.prefill_chunk),
                                 np.int32)
                # paged: the warmup table is ALL ZEROS — every scatter/
                # gather rides the pinned trash page, so warming compiles
                # the real programs without allocating a single page
                with build("serve_prefill_chunk"):
                    tok, _ok, cache = self._prefill_chunk(
                        self.cache, self._weights, dummy, np.int32(0),
                        np.int32(1), np.int32(0),
                        zero_key, np.float32(0.0), np.int32(0),
                        *self._paged_tail(
                            self._pool_args_for(np.int32(-1)), 3))
                self.cache = cache
                if self.prefix_store is not None and not self._paged:
                    # paged hit/store are host table writes — the copy/
                    # extract programs exist but are never dispatched
                    with build("serve_prefix_extract"):
                        panes = self._prefix_extract(
                            self.cache, np.int32(0), np.int32(1))
                    with build("serve_prefix_copy"):
                        self.cache = self._prefix_copy(self.cache, panes,
                                                       np.int32(0))
            else:
                buckets = self.prompt_buckets()
                for Tpb in buckets:
                    dummy = np.zeros((1, Tpb), np.int32)
                    with build("serve_prefill"):
                        tok, _ok, cache = self._prefill(
                            self.cache, self._weights, dummy, np.int32(1),
                            np.int32(0), zero_key, np.float32(0.0),
                            np.int32(0), *self._pool_args_for(np.int32(-1)))
                    self.cache = cache
            # the Tq=k+1 verify program IS the tick program when
            # speculation is on — warm (and freeze) it instead of a
            # plain decode step that would never run
            warm_tokens = (np.zeros((self.n_slots, self.spec_k + 1), np.int32)
                           if self.spec_k else self._last_tokens)
            with build("serve_verify" if self.spec_k else "serve_decode"):
                nxt, *_, cache = (self._verify or self._decode)(
                    self.cache, self._weights, warm_tokens, self._lengths,
                    self._base_keys, self._n_gen, self._temps, self._topks,
                    *self._step_tail())
            self.cache = cache
            with self._setup_tl.span("first_runs"):
                jax.device_get(nxt)           # block until compiled + ran
            if isinstance(self._prefill, CompileWatcher):
                for w in self._watchers():
                    w.freeze()
            self._lengths[:] = 0
            self._last_tokens[:] = 0
            self._n_gen[:] = 0
            self._adapter_ids[:] = -1
            # re-anchor the metrics window: the first cadence row should
            # describe serving, not a window stretched over compile time
            self._win_base = self._cumulative()
            self.warmed_up = True
        bps = self.kv_policy.bytes_per_slot(self.cfg, self._cache_len)
        spec_fields = ({"spec_k": self.spec_k,
                        "drafter": self.drafter.describe()}
                       if self.spec_k else {})
        kv_fields = self.kv_policy.describe()
        if self._paged:
            # the RESOLVED usable pool (policy.pool_pages=0 means "sized
            # to n_slots full rows" — report what was actually built)
            kv_fields["pool_pages"] = self.page_pool.n_pages - 1
        sp_fields = ({"sp": self._sp,
                      "prompt_pane_tokens": self.prompt_pane,
                      "max_prompt": self.max_prompt}
                     if self._sp > 1 else {})
        self._ev(
            "serve_warmup", n_prefill_buckets=len(buckets),
            buckets=buckets, seconds=round(time.monotonic() - t0, 3),
            n_slots=self.n_slots, max_len=self.max_len,
            kv_bytes_per_slot=bps["total_bytes"],
            kv_append=self.kv_append,
            decode_attention=self.decode_attention,
            chunk_attention=self.chunk_attention,
            linear_attention=self.linear_attention,
            selective_scan=self.selective_scan,
            state_step=self.state_step,
            expert_dispatch=self.expert_dispatch,
            programs=program_table(),
            prefix_pane_tokens=(self._prefix_pane_len
                                if self.prefix_store is not None
                                else None),
            **kv_fields, **spec_fields, **sp_fields)
        logger.info(
            "Serving warmup: %s + 1 %s program in %.2fs (kv %s, "
            "%.2f MiB/slot%s%s)",
            (f"1 chunk program (C={self.kv_policy.prefill_chunk})"
             if self.kv_policy.prefill_chunk > 0
             else f"{len(buckets)} prefill buckets {buckets}"),
            f"verify (k={self.spec_k})" if self.spec_k else "decode",
            time.monotonic() - t0, self.kv_policy.kv_quant,
            bps["total_bytes"] / 1024 ** 2,
            ", prefix cache on" if self.prefix_store is not None else "",
            f", spec {self.drafter.describe()}" if self.spec_k else "")

    def _watchers(self) -> list:
        return [w for w in (self._prefill, self._prefill_chunk,
                            self._prefix_copy, self._prefix_extract,
                            self._decode, self._verify)
                if isinstance(w, CompileWatcher)]

    @property
    def n_recompiles(self) -> int:
        return sum(w.n_recompiles for w in self._watchers())

    # -- background loop ---------------------------------------------------

    def start(self) -> None:
        if self._thread is not None:
            return
        with self._setup_tl.span("start"):
            self._stop.clear()
            if self.supervisor is not None:
                self.supervisor.start()
            self._spawn_loop()
        if not self._setup_emitted:
            # set-up is over: its books go to the hub, once an engine
            self._setup_emitted = True
            emit_setup_record(self._setup_record())

    def _setup_record(self) -> dict:
        record = self._setup_tl.record("serve")
        if self.replica is not None:
            record["replica"] = self.replica
        return record

    def setup_books(self) -> dict:
        """Why this engine took as long to come up as it did: the set-up
        timeline so far (obs/schema.py ``SETUP_RECORD_FIELDS``: `init`,
        `warmup`, `start` and the spans inside them) and what each program
        the process has built cost (``PROGRAM_RECORD_FIELDS``: trace, lower,
        cache load or compile; a cache that missed says so)."""
        return {"record": self._setup_record(), "programs": program_table()}

    def _spawn_loop(self) -> None:
        """Start one decode-loop thread bound to the CURRENT generation.
        A stale thread (superseded by ``_restart``) exits at its next
        checkpoint without touching engine state."""
        gen = self._generation

        def loop():
            while not self._stop.is_set() and self._generation == gen:
                # a span over each stretch of this thread's life outside
                # step(), so that a device trace finds the host in some
                # span of the engine whenever the chip waits for it
                with annotate(TICK_BETWEEN):
                    if self.supervisor is not None:
                        self.supervisor.notify_tick()
                    if self._heartbeat is not None:
                        self._heartbeat()
                try:
                    progressed = self.step()
                except Exception as e:          # noqa: BLE001 — must not
                    # die silently: callers block on result() forever and
                    # shutdown(drain=True) spins if requests just vanish
                    if self._generation != gen:
                        return                  # superseded: not ours
                    logger.exception("decode-engine loop died")
                    # batch-wide fault: with a supervisor and restart
                    # budget left, fail only the in-flight batch and come
                    # back up; otherwise the engine dies loudly
                    if self.supervisor is None or not self._restart(
                            reason="loop_error",
                            detail=f"engine loop error: {e!r}"):
                        self._fail_all(f"engine loop error: {e!r}")
                    return
                if not progressed:
                    with annotate(TICK_IDLE_WAIT), self._work:
                        self._work.wait(timeout=0.05)

        self._thread = threading.Thread(target=loop, name="decode-engine",
                                        daemon=True)
        self._thread.start()

    #: external per-tick heartbeat (``--stall_timeout`` flight recorder in
    #: serve mode rides this without the full supervisor)
    _heartbeat = None

    def set_heartbeat(self, fn) -> None:
        self._heartbeat = fn

    def _restart(self, reason: str, detail: str = "") -> bool:
        """Supervisor recovery: abandon the (possibly wedged) loop thread,
        fail the in-flight requests, keep the queue, rebuild the KV cache
        and sync primitives, and bring up a fresh loop thread after a
        bounded exponential backoff. The compiled prefill/decode programs
        (and their CompileWatchers) are untouched — the restarted engine
        reuses them, so recovery costs ZERO recompiles. Returns False when
        the restart budget is exhausted (caller escalates to _fail_all).
        """
        with self._restart_lock:
            if self._dead is not None or self._stop.is_set():
                return False
            if self.n_restarts >= self.max_restarts:
                return False
            self.n_restarts += 1
            n_restart = self.n_restarts
            # bump FIRST: the wedged thread checks the generation at every
            # commit point, and must see the bump before we touch state
            self._generation += 1
            # fresh primitives — the abandoned thread may hold the old
            # lock forever; new threads must not queue behind it
            self._lock = threading.RLock()
            self._work = threading.Condition()
            # and a fresh timeline: the abandoned tick closes its open
            # spans and books its wall on the one it started with
            self._tl = StepTimeline(TICK_SPANS)
            failed = 0
            failed_ids = []
            with self._lock:
                for slot, req in self.scheduler.active():
                    self._fail_request(
                        slot, req,
                        f"engine restarted ({reason}): {detail}",
                        reason="engine_restart")
                    failed += 1
                    failed_ids.append(req.id)
                self._lengths[:] = 0
                self._last_tokens[:] = 0
                self._n_gen[:] = 0
                self._temps[:] = 0.0
                self._topks[:] = 0
                self._adapter_ids[:] = -1
                self._hist_len[:] = 0
                self._prefill_state.clear()
                # the old cache may be donation-poisoned or numerically
                # corrupt; a fresh one has identical shapes/dtypes, so the
                # frozen compiled programs accept it without recompiling.
                # Contiguous: the prefix store survives — its panes are
                # independent device arrays a wedged tick can't have
                # corrupted. Paged: stored entries REFERENCE the pool
                # being thrown away, so the store is cleared and the pool
                # rebuilt from scratch alongside the cache (the ledger's
                # providers read self.page_pool and follow the swap).
                self.cache = self._place_cache(init_slot_cache(
                    self.cfg, self.n_slots, self._cache_len,
                    policy=self.kv_policy))
                if self._paged:
                    if self.prefix_store is not None:
                        self.prefix_store.clear()
                    self.page_pool = PagePool(
                        self.kv_policy.total_pool_pages(self.n_slots,
                                                        self._cache_len),
                        self.kv_policy.page_bytes(self.cfg))
                    if self.prefix_store is not None:
                        self.prefix_store.page_pool = self.page_pool
                    self._page_table[:] = 0
                    self._slot_cols[:] = 0
                    self._pages_reserved[:] = 0
                    self._pool_exhausted_logged = False
            backoff = self.restart_backoff_s * (2.0 ** (n_restart - 1))
            self._ev(
                "engine_restart", reason=reason, detail=detail,
                n_restart=n_restart, max_restarts=self.max_restarts,
                backoff_s=round(backoff, 3), n_inflight_failed=failed,
                failed_request_ids=failed_ids,
                queue_depth=len(self.queue))
            logger.error(
                "Engine restart %d/%d (%s): failed %d in-flight "
                "request(s), kept %d queued; backoff %.2fs.",
                n_restart, self.max_restarts, reason, failed,
                len(self.queue), backoff)
            time.sleep(backoff)
            if self._thread is not None:
                self._spawn_loop()
        return True

    def _fail_all(self, msg: str) -> None:
        """Fail every in-flight and queued request (engine loop death):
        set ``req.error`` so ``result()`` raises instead of hanging.
        Marks the engine dead — later ``submit()`` calls raise.

        Timed lock acquire for the same reason as ``drain()``: the
        supervisor's escalation path runs this WHILE the tick is wedged
        holding the lock — a plain acquire would deadlock the recovery."""
        lock = self._lock
        locked = lock.acquire(timeout=5.0)
        try:
            if not locked:
                # edge is infeasible: this branch runs only when the
                # _lock acquire FAILED (wedged tick), and _restart
                # acquires the REPLACEMENT lock, not the abandoned one
                with self._restart_lock:  # graft-ok: GL032 wedge path
                    self._generation += 1   # wedged loop may never commit
                    self._lock = threading.RLock()   # see drain(): later
                    self._work = threading.Condition()  # paths must not
                    # queue behind the lock the wedged thread holds
            self._dead = msg
            failed = 0
            failed_ids = []

            def _kill(req, slot=None):
                # engine death is still a per-request terminal outcome:
                # each request gets its own request_failed event + closed
                # span so trace joins never drop the casualties
                req.error = msg
                req.finish_reason = FINISH_ERROR
                req.state = FINISHED
                req.t_finish = time.monotonic()
                self.requests_failed += 1
                self._ev("request_failed", request_id=req.id,
                                    reason="engine_dead", error=msg,
                                    slot=slot,
                                    n_tokens=len(req.output_ids))
                self._emit_span(req)
                req._mark_done()
                failed_ids.append(req.id)

            for slot, req in self.scheduler.active():
                self.scheduler.retire(slot)
                _kill(req, slot)
                failed += 1
            while True:
                req = self.queue.get_nowait()
                if req is None:
                    break
                _kill(req)
                failed += 1
            self._ev("serve_error", error=msg, n_failed=failed,
                                failed_request_ids=failed_ids)
        finally:
            if locked:
                lock.release()
        with self._work:
            self._work.notify_all()

    # -- graceful drain ----------------------------------------------------

    @property
    def draining(self) -> bool:
        return self._draining

    def cancel(self, req: Request) -> bool:
        """Client gave up on ``req`` (HTTP timeout, disconnect): stop
        spending decode on it. Queued requests are failed immediately;
        running ones are marked and retired at the next tick boundary
        (their slot frees instead of decoding to ``max_new_tokens`` for
        nobody). Returns False when the request is already done."""
        if req.done:
            return False
        req._cancelled = True
        if req.state == QUEUED and self.queue.remove(req):
            # under the engine lock: _fail_request mutates the shared
            # failure counters and must not interleave with a tick
            # retiring the same request (pre-fix this ran lock-free from
            # client threads — a real GL031 finding). TIMED acquire: a
            # wedged tick holds the lock forever and restart ABANDONS
            # (never releases) it, so an unbounded acquire would leak
            # this client thread — on timeout fall back to the old
            # lock-free retire: we already own the request (remove()
            # returned True) and the wedged tick can never commit it
            # (generation-checked), so the race window is gone with it
            lock = self._lock
            locked = lock.acquire(timeout=2.0)
            try:
                self._fail_request(None, req, "cancelled while queued",
                                   reason="cancelled",
                                   finish=FINISH_CANCELLED)
            finally:
                if locked:
                    lock.release()
        with self._work:
            self._work.notify()
        return True

    def drain(self, timeout: float = 30.0) -> dict:
        """Graceful drain: close admission (``submit()`` raises
        ``EngineDrainingError`` -> HTTP 503), let in-flight AND queued
        work finish within ``timeout`` seconds, then fail whatever is
        left with reason ``preempted``. Idempotent; safe from any thread
        (the SIGTERM path calls it off the signal watcher). Returns a
        small summary dict (also emitted as the ``drain`` event)."""
        t0 = time.monotonic()
        already = self._draining
        # deliberately lock-free write: drain's whole reason to exist is
        # the wedged-tick case where self._lock may NEVER be released —
        # a bool store is atomic and readers re-check under real barriers
        self._draining = True                  # graft-ok: GL031 wedge-safe
        if not already:
            self._ev(
                "drain", phase="start", timeout_s=timeout,
                n_active=self.scheduler.n_active,
                queue_depth=len(self.queue))
            logger.warning(
                "Draining: admission closed; finishing %d in-flight + %d "
                "queued request(s) within %.1fs.",
                self.scheduler.n_active, len(self.queue), timeout)
        deadline = t0 + timeout
        if self._thread is not None:
            while (time.monotonic() < deadline
                   and (self.scheduler.n_active or len(self.queue))
                   and self._thread.is_alive()
                   and self._dead is None):
                time.sleep(0.01)
        else:
            # manual mode (no loop thread): we do the ticking ourselves
            while time.monotonic() < deadline and self.step():
                pass
        preempted = 0
        # a WEDGED tick holds self._lock for the whole hung device call —
        # a plain `with self._lock:` here would deadlock the drain (and
        # the SIGTERM exit path behind it) forever, exactly the hang this
        # PR exists to bound. Timed acquire: on timeout, retire the
        # wedged loop via a generation bump (it can never commit state
        # again — every commit point is generation-checked) and sweep the
        # requests without the lock so clients and serve_jsonl unblock.
        lock = self._lock
        lock_wait = min(5.0, max(0.1, timeout))
        locked = lock.acquire(timeout=lock_wait)
        try:
            if not locked:
                logger.error(
                    "Drain: decode tick wedged (lock held > %.1fs); "
                    "abandoning it and force-failing in-flight requests.",
                    lock_wait)
                # edge is infeasible: this branch runs only when the
                # _lock acquire FAILED (wedged tick), and _restart
                # acquires the REPLACEMENT lock, not the abandoned one
                with self._restart_lock:  # graft-ok: GL032 wedge path
                    self._generation += 1
                    # the wedged thread holds the OLD lock forever — give
                    # every later path (shutdown's stats(), submit's
                    # counters) a fresh one or they deadlock behind it
                    self._lock = threading.RLock()
                    self._work = threading.Condition()
            for slot, req in self.scheduler.active():
                self._fail_request(
                    slot, req,
                    f"preempted: drain timeout {timeout}s elapsed",
                    reason="preempted", finish=FINISH_PREEMPTED)
                preempted += 1
            while True:
                req = self.queue.get_nowait()
                if req is None:
                    break
                self._fail_request(
                    None, req,
                    f"preempted: drain timeout {timeout}s elapsed",
                    reason="preempted", finish=FINISH_PREEMPTED)
                preempted += 1
        finally:
            if locked:
                lock.release()
        summary = {"phase": "end", "n_preempted": preempted,
                   "seconds": round(time.monotonic() - t0, 3),
                   "requests_finished": self.requests_finished}
        self._ev("drain", **summary)
        logger.warning("Drain complete in %.2fs (%d preempted).",
                       summary["seconds"], preempted)
        return summary

    def shutdown(self, drain: bool = True) -> None:
        """Stop the engine loop; with ``drain`` (default) finish everything
        queued first. Emits the ``serve_summary`` event with the latency
        histograms' percentiles."""
        if self._thread is not None:
            if drain:
                while ((self.scheduler.n_active or len(self.queue))
                       and self._thread.is_alive()):
                    time.sleep(0.01)
            self._stop.set()
            with self._work:
                self._work.notify_all()
            self._thread.join(timeout=10)
            self._thread = None
        elif drain:
            self.run_until_idle()
        if self.supervisor is not None:
            self.supervisor.stop()
        self._ev("serve_summary", **self.stats())

    def layout(self) -> dict:
        """What the model's kinds of layer made of this engine, for
        ``stats()`` and ``/healthz``: the positions a slot holds in a
        window layer's ring and in a full layer (no window layers: the
        one length), the recurrent state a slot holds beside them, and the
        routed experts held here."""
        lengths = self.kv_policy.layer_lengths(self.cfg, self._cache_len)
        out = {"kv_positions": {"full": self._cache_len}}
        if self.cfg.has_window_layers:
            out["kv_positions"]["ring"] = min(n for n in lengths if n)
        if self._n_state_layers:
            out["state"] = {
                "layers": self._n_state_layers,
                "bytes_per_slot": self.kv_policy.bytes_per_slot(
                    self.cfg, self._cache_len)["state_bytes"]}
        if self.cfg.is_moe:
            out["experts"] = {"held": list(self.cfg.held_experts),
                              "routed": self.cfg.n_routed_experts,
                              "per_token": self.cfg.n_experts_per_tok,
                              "shared": self.cfg.n_shared_experts}
        return out

    def stats(self) -> dict:
        with self._lock:                       # vs a mid-tick _finish()
            out = {
                "requests_finished": self.requests_finished,
                "requests_rejected": self.requests_rejected,
                "requests_failed": self.requests_failed,
                "requests_shed": self.requests_shed,
                "requests_expired": self.requests_expired,
                "tokens_generated": self.tokens_generated,
                "n_ticks": self.n_ticks,
                "n_recompiles": self.n_recompiles,
                "n_restarts": self.n_restarts,
                "draining": self._draining,
            }
            if self.spec_k:
                out["spec_k"] = self.spec_k
                out["spec_tokens_drafted"] = self.spec_tokens_drafted
                out["spec_tokens_accepted"] = self.spec_tokens_accepted
                if self.spec_tokens_drafted:
                    out["spec_acceptance_ratio"] = round(
                        self.spec_tokens_accepted
                        / self.spec_tokens_drafted, 6)
            if self._adapter_counts:
                out["per_adapter"] = {
                    nm: dict(c)
                    for nm, c in sorted(self._adapter_counts.items())}
            if self.adapters is not None:
                out["adapters_loaded"] = self.adapters.n_loaded
            out["kv_policy"] = self.kv_policy.describe()
            out["kv_append"] = self.kv_append
            out["decode_attention"] = self.decode_attention
            out["chunk_attention"] = self.chunk_attention
            out["linear_attention"] = self.linear_attention
            out["selective_scan"] = self.selective_scan
            out["state_step"] = self.state_step
            out["expert_dispatch"] = self.expert_dispatch
            out.update(self.layout())
            out["setup"] = self.setup_books()
            out["memory"] = self.memory_ledger.describe()
            if self._paged:
                out["page_pool"] = self.page_pool.stats()
                out["pane_copies"] = self.pane_copies
            if self.prefix_store is not None:
                out["prefix_store"] = self.prefix_store.stats()
            slo = self.slo_window.ratio()
            if slo is not None:
                out["slo_miss_ratio"] = round(slo, 6)
            hists = [("ttft_s", self.ttft_hist),
                     ("tpot_s", self.tpot_hist),
                     ("queue_wait_s", self.queue_wait_hist),
                     ("e2e_s", self.e2e_hist)]
        for name, hist in hists:
            # percentiles are now bucket-interpolated estimates (the
            # histograms are cumulative and never forget a request)
            pct = hist.percentiles((50, 95, 99))
            if pct:
                out[name] = pct
        return out

    def uptime_s(self) -> float:
        return time.monotonic() - self._t_start_mono

    def queue_capacity(self) -> int:
        """Bounded-queue capacity (the 429 payload field) — a method so
        the HTTP frontend reads one surface for engine AND router."""
        return self.queue.max_size

    def metrics_snapshot(self) -> tuple:
        """(counters, gauges, histograms) for the ``/metrics`` exporter
        and the structured ``/healthz`` body. TIMED lock acquire: a
        wedged tick holding the engine lock must not hang the scrape —
        monitoring an incident is precisely when ``/metrics`` has to
        answer (the fields are simple attrs, so a lock-less read during
        a wedge is stale-but-safe)."""
        lock = self._lock
        locked = lock.acquire(timeout=0.5)
        try:
            counters = {
                "requests_finished": self.requests_finished,
                "requests_failed": self.requests_failed,
                "requests_rejected": self.requests_rejected,
                "requests_shed": self.requests_shed,
                "requests_expired": self.requests_expired,
                "tokens_generated": self.tokens_generated,
                "engine_restarts": self.n_restarts,
                "engine_ticks": self.n_ticks,
                "recompiles": self.n_recompiles,
                "tick_busy_seconds": round(self.tick_seconds_total, 6),
            }
            for ph in TICK_PHASES:
                counters[f"tick_{ph}_seconds"] = round(
                    self.tick_phase_totals[ph], 6)
            if self.prefix_store is not None:
                counters["prefix_hits"] = self.prefix_store.n_hits
                counters["prefix_misses"] = self.prefix_store.n_misses
                counters["prefix_evictions"] = \
                    self.prefix_store.n_evictions
                counters["prefix_inserts"] = self.prefix_store.n_inserts
            if self.spec_k:
                counters["spec_tokens_drafted"] = self.spec_tokens_drafted
                counters["spec_tokens_accepted"] = \
                    self.spec_tokens_accepted
            # per-adapter labeled series (multi-tenant accounting): one
            # requests/tokens counter triple per adapter name seen, plus
            # a live per-adapter slot-occupancy gauge
            adapter_active: dict = {}
            for _slot, _req in self.scheduler.active():
                nm = _req.params.adapter or BASE_ADAPTER
                adapter_active[nm] = adapter_active.get(nm, 0) + 1
            for nm, c in sorted(self._adapter_counts.items()):
                lbl = f'{{adapter="{nm}"}}'
                counters[f"adapter_requests_finished{lbl}"] = c["finished"]
                counters[f"adapter_requests_failed{lbl}"] = c["failed"]
                counters[f"adapter_tokens_generated{lbl}"] = c["tokens"]
            gauges = {
                "slot_occupancy": self.scheduler.occupancy(),
                "slots_active": self.scheduler.n_active,
                "slots_total": self.n_slots,
                "queue_depth": len(self.queue),
                "queue_capacity": self.queue.max_size,
                "draining": 1.0 if self._draining else 0.0,
                "engine_up": 0.0 if self._dead is not None else 1.0,
                "uptime_seconds": round(self.uptime_s(), 3),
            }
            for nm, n_act in sorted(adapter_active.items()):
                gauges[f'adapter_slots_active{{adapter="{nm}"}}'] = n_act
            if self.adapters is not None:
                gauges["adapters_loaded"] = self.adapters.n_loaded
                gauges["adapter_capacity"] = self.adapters.capacity
            # KV memory-engine gauges: bytes/slot is the HBM number that
            # sizes n_slots (the int8 policy's whole point); the
            # hit-ratio is the prefix cache's scoreboard
            gauges["kv_bytes_per_slot"] = self.kv_policy.bytes_per_slot(
                self.cfg, self._cache_len)["total_bytes"]
            if self._paged:
                ps = self.page_pool.stats()
                gauges["kv_pages_total"] = ps["n_pages"]
                gauges["kv_pages_used"] = ps["used"]
                gauges["kv_pages_free"] = ps["free"]
                gauges["kv_pages_reserved"] = ps["reserved"]
                gauges["kv_pages_peak_used"] = ps["peak_used"]
                gauges["kv_page_bytes"] = ps["page_bytes"]
            if self.spec_k:
                # acceptance ratio is THE drafter-quality dial: low ratio
                # means the verify widths are wasted compute — shrink k
                # or disable spec for the workload (README guidance)
                gauges["spec_k"] = self.spec_k
                gauges["spec_acceptance_ratio"] = round(
                    self.spec_tokens_accepted
                    / max(self.spec_tokens_drafted, 1), 6)
            if self.prefix_store is not None:
                ratio = self.prefix_store.hit_ratio()
                gauges["prefix_hit_ratio"] = (round(ratio, 6)
                                              if ratio is not None else 0.0)
                gauges["prefix_entries"] = self.prefix_store.n_entries
                gauges["prefix_bytes"] = self.prefix_store.bytes_total
            # memory observatory: refresh the ledger from the live
            # arrays (metadata math — safe under the timed lock) and
            # export the component/watermark/attribution series; the
            # fleet scrape path relabels these per worker automatically
            self.memory_ledger.snapshot()
            gauges.update(self.memory_ledger.gauges())
            # always exported: a scrape gap (series absent until the
            # first deadline-carrying request) reads as "no data" on a
            # dashboard when the truth is "no misses"
            slo = self.slo_window.ratio()
            gauges["slo_miss_ratio"] = round(slo, 6) if slo is not None \
                else 0.0
            hists = {
                "ttft_seconds": self.ttft_hist,
                "tpot_seconds": self.tpot_hist,
                "queue_wait_seconds": self.queue_wait_hist,
                "e2e_seconds": self.e2e_hist,
                "tick_prefill_seconds": self.tick_prefill_hist,
            }
        finally:
            if locked:
                lock.release()
        return counters, gauges, hists

    def prometheus_text(self) -> str:
        """The ``GET /metrics`` body (Prometheus text exposition 0.0.4)."""
        counters, gauges, hists = self.metrics_snapshot()
        return render_prometheus(counters, gauges, hists,
                                 prefix="bllm_serve_")

    def healthz_payload(self) -> dict:
        """The ``GET /healthz`` body — one method so the single-engine
        frontend and the router's per-replica fleet view can't drift."""
        if self._dead is not None:
            status = "dead"
        elif self.draining:
            status = "draining"
        else:
            status = "serving"
        counters, gauges, _ = self.metrics_snapshot()
        return {
            # original fields (kept for compatibility)
            "status": status,
            "slots": self.n_slots,
            "active": self.scheduler.n_active,
            "queue_depth": len(self.queue),
            "queue_capacity": self.queue.max_size,
            "warmed_up": self.warmed_up,
            "kv_append": self.kv_append,
            "decode_attention": self.decode_attention,
            "chunk_attention": self.chunk_attention,
            "linear_attention": self.linear_attention,
            "selective_scan": self.selective_scan,
            "state_step": self.state_step,
            "expert_dispatch": self.expert_dispatch,
            **self.layout(),
            "setup": self.setup_books(),
            "draining": self.draining,
            "restarts": self.n_restarts,
            # structured snapshot (one probe answers "how is it
            # doing", not just "is it up")
            "uptime_s": round(self.uptime_s(), 3),
            "n_ticks": counters["engine_ticks"],
            "occupancy": self.scheduler.occupancy(),
            "slo_miss_ratio": gauges.get("slo_miss_ratio"),
            "counters": counters,
            # which phase of which tick the time last went into
            "last_ticks": last_ticks(self.replica),
        }


def service_estimate(queue_depth: int, n_active: int, n_slots: int,
                     tpot_ewma: Optional[float],
                     tokens_ewma: Optional[float],
                     max_new_tokens: int) -> Optional[float]:
    """THE SLO completion estimate (pure): predicted submit->finish
    seconds given a backlog and the live service EWMAs. Shared by
    ``DecodeEngine.estimate_completion_s`` (per-engine shed) and the
    fleet router's dispatch scoring — one formula, so fleet admission
    and per-engine shed can never disagree on what a predicted miss is.
    None without service history (admission stays optimistic)."""
    if tpot_ewma is None or tokens_ewma is None:
        return None
    per_request = tokens_ewma * tpot_ewma
    backlog = queue_depth + 0.5 * n_active
    wait = (backlog / max(n_slots, 1)) * per_request
    return wait + max_new_tokens * tpot_ewma


def queue_clear_estimate(queue_depth: int, n_active: int, n_slots: int,
                         tpot_ewma: Optional[float],
                         tokens_ewma: Optional[float]
                         ) -> Optional[float]:
    """Rough seconds until a backlog drains (Retry-After material) —
    the pure sibling of ``service_estimate``, shared with the router."""
    if tpot_ewma is None or tokens_ewma is None:
        return None
    per_request = tokens_ewma * tpot_ewma
    backlog = queue_depth + n_active
    return round((backlog / max(n_slots, 1)) * per_request, 3)


def _prng_key(seed: int):
    import jax

    return jax.random.PRNGKey(seed)
