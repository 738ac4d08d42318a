"""KV-cache memory engine: layout/dtype policy, shared-prefix store,
and the chunked-prefill pane primitives.

Before this module the serving KV tier hardcoded three assumptions that
each cost real capacity or latency at scale:

  1. every request prefills its FULL prompt from scratch — a fleet where
     millions of users share a handful of system prompts recomputes the
     same prefix forward pass per request;
  2. prompt prefill is monolithic — a 2k-token prompt holds the engine
     lock for one giant program call, stalling the decode tick for every
     co-resident request (PR 7's per-tick phase timeline measures exactly
     this head-of-line blocking);
  3. the slot cache is the model dtype, contiguous — KV bytes, not
     compute, cap ``n_slots`` well below what HBM allows.

One ``KVCachePolicy`` object (layout + dtype + prefix policy) replaces
all three:

  - **prefix caching** (``prefix_cache=True``): a hash-keyed
    (token-ids, model-fingerprint, adapter-tag) store of per-layer KV
    panes. A shared prefix prefills ONCE; later requests copy its panes
    into their slot with one batched dynamic-update-slice and
    chunk-prefill only the suffix — zero forward FLOPs for the cached
    span. Per-adapter namespacing (the registry's load tag) keeps each
    tenant's cached prefix adapter-consistent with unmerged-LoRA
    prefill, and a reloaded adapter gets a fresh tag so stale panes can
    never hit. LRU eviction under a byte budget with in-use pinning
    (the same non-reuse discipline as ``AdapterRegistry``).
  - **chunked prefill** (``prefill_chunk=C``): prompts prefill in
    fixed-size C-token chunks interleaved with decode ticks. The chunk
    shape is STATIC, so the whole prefill tier is ONE compiled program
    (vs one per prompt-length bucket) and ``tick_prefill_s`` is bounded
    by one chunk's wall time instead of the longest prompt's: a tick runs
    one chunk, for the slot in mid-prefill that was admitted first.
  - **int8 slot KV** (``kv_quant="int8"``): symmetric per-(slot, layer,
    head, position) scale quantization on append, dequantized inside
    ``decode_attention`` (scales fold into the score/value einsums, no
    dequantized cache copy ever materializes). KV data bytes halve
    exactly vs bf16; the fp32 scale sidecar adds 2/head_dim overhead
    (6.25% at head_dim 64), so total cache bytes are ~0.53x.

Compile discipline: pane width and chunk size are static; hit/miss/
evict, span length and slot index are DATA. The engine's frozen
``CompileWatcher`` set (prefill-or-chunk, copy, extract, decode) is
warmed up front, so live traffic — including store eviction and adapter
churn — runs with zero recompiles (test-pinned).

Layering: this module sees only configs + obs (events) + jax; the model
side (``models/transformer.py``) imports ``KVCachePolicy.alloc`` lazily
so train-time ``init_cache`` and serving ``init_slot_cache`` share one
allocation rule without an import cycle.
"""

from __future__ import annotations

import hashlib
import math
import threading
import time
from collections import OrderedDict
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from building_llm_from_scratch_tpu.configs import (
    ModelConfig,
    refuse_unsupported,
)
from building_llm_from_scratch_tpu.obs.metrics import get_metrics
from building_llm_from_scratch_tpu.utils.logging import setup_logger

logger = setup_logger(__name__)

Params = Dict[str, Any]

KV_QUANT_CHOICES = ("model", "int8")


@dataclass(frozen=True)
class KVCachePolicy:
    """Layout + dtype + prefix policy for the slot KV cache.

    The policy is STATIC per engine: it decides the cache pytree's
    structure (scale sidecars or not), leaf dtypes, and which prefill
    tier (monolithic-bucketed vs chunked) the engine compiles. Request
    traffic — hits, misses, spans, slots — is data against those fixed
    shapes.
    """

    kv_quant: str = "model"          # "model" (cfg dtype) | "int8"
    prefix_cache: bool = False
    prefill_chunk: int = 0           # 0 = monolithic bucketed prefill
    prefix_budget_bytes: int = 256 * 1024 ** 2
    paged: bool = False              # page-table layout over a shared pool
    page_tokens: int = 16            # positions per KV page (paged only)
    pool_pages: int = 0              # usable pool pages; 0 = n_slots full

    def __post_init__(self):
        if self.kv_quant not in KV_QUANT_CHOICES:
            raise ValueError(
                f"kv_quant must be one of {KV_QUANT_CHOICES}, "
                f"got '{self.kv_quant}'")
        if self.prefill_chunk < 0:
            raise ValueError("prefill_chunk must be >= 0")
        if self.prefix_cache and self.prefill_chunk <= 0:
            raise ValueError(
                "prefix_cache needs chunked prefill (prefill_chunk > 0): "
                "the suffix after a cached span prefills in chunks — the "
                "monolithic bucketed prefill always starts at position 0")
        if self.prefix_budget_bytes < 0:
            raise ValueError("prefix_budget_bytes must be >= 0")
        if self.page_tokens < 1:
            raise ValueError("page_tokens must be >= 1")
        if self.pool_pages < 0:
            raise ValueError("pool_pages must be >= 0")
        if self.paged:
            if self.prefill_chunk <= 0:
                raise ValueError(
                    "paged KV needs chunked prefill (prefill_chunk > 0): "
                    "pages are allocated on demand as the chunk frontier "
                    "advances — the monolithic bucketed prefill would "
                    "need every page up front per bucket")
            if self.prefill_chunk % self.page_tokens != 0:
                raise ValueError(
                    "paged KV needs prefill_chunk to be a multiple of "
                    f"page_tokens (got chunk {self.prefill_chunk}, page "
                    f"{self.page_tokens}): chunk boundaries must land on "
                    "page boundaries so mid-prefill appends never touch "
                    "an unallocated page and shared prefix spans are "
                    "whole pages")

    # -- layout ------------------------------------------------------------

    @property
    def quantized(self) -> bool:
        return self.kv_quant == "int8"

    def cache_dtype(self, cfg: ModelConfig):
        import jax.numpy as jnp

        return jnp.int8 if self.quantized else cfg.jax_dtype

    def alloc(self, cfg: ModelConfig, n_rows: int, max_length: int) -> Params:
        """Allocate the per-layer KV buffers: the ONE allocation rule
        behind train-time ``init_cache`` and serving ``init_slot_cache``
        (previously three identical ``jnp.zeros`` blocks that could
        silently drift).

        Layout (n_rows, Hkv, max_length, head_dim) — attention-native
        (see ``init_cache``'s docstring for the per-layer-buffer and
        layout rationale). Quantized policies add fp32 scale sidecars
        (n_rows, Hkv, max_length, 1): one symmetric scale per written
        position per head.
        """
        import jax.numpy as jnp

        if cfg.has_state_layers:
            # a state has no pages and no int8 form: said here too, for a
            # caller that allocates without an engine
            refuse_unsupported(cfg, paged=self.paged,
                               int8_cache=self.quantized)
        if self.paged:
            n_pages = self.total_pool_pages(n_rows, max_length)
            lead = [(n_pages, cfg.n_kv_groups, self.page_tokens)
                    ] * cfg.n_layers
        else:
            lead = [(n_rows, cfg.n_kv_groups, length)
                    for length in self.layer_lengths(cfg, max_length)]
        dt = self.cache_dtype(cfg)
        # a 'linear' or 'ssm' layer holds no positions: None in the lists of
        # keys and values, which stay indexed by layer
        zeros = lambda tail, dtype: [
            None if not s[2] else jnp.zeros(s + tail, dtype) for s in lead]
        cache: Params = {"k": zeros((cfg.head_dim,), dt),
                         "v": zeros((cfg.head_dim,), dt)}
        if self.quantized:
            cache["k_scale"] = zeros((1,), jnp.float32)
            cache["v_scale"] = zeros((1,), jnp.float32)
        if cfg.has_state_layers:
            # beside them, a 'linear' or 'ssm' layer's memory of a row: the
            # last K-1 tokens of its convolution's input and a float32 state
            # (``cfg.state_shapes``: a matrix a head, or N states a channel)
            held = set(cfg.state_layers)
            shapes = [cfg.state_shapes(cfg.layer_kind(l)) if l in held
                      else None for l in range(cfg.n_layers)]
            cache["conv"] = [
                jnp.zeros((n_rows,) + s[0], cfg.jax_dtype) if s else None
                for s in shapes]
            cache["state"] = [
                jnp.zeros((n_rows,) + s[1], jnp.float32) if s else None
                for s in shapes]
        return cache

    def ring_length(self, cfg: ModelConfig, max_length: int) -> int:
        """Positions a 'sliding' layer's buffer holds: under chunked
        prefill a ring of window + chunk (a chunk is written before it
        attends, and must not overwrite what its first query still sees),
        never more than the slot's length; under monolithic prefill the
        slot's length (the ring that never wraps: a whole prompt is
        written at once, and only the mask knows the window)."""
        if self.prefill_chunk <= 0:
            return max_length
        if cfg.sliding_window % self.prefill_chunk:
            raise ValueError(
                f"{cfg.name}: the window ({cfg.sliding_window}) must be "
                f"whole prefill chunks ({self.prefill_chunk}) so that no "
                "chunk wraps its ring")
        return min(max_length, cfg.sliding_window + self.prefill_chunk)

    def layer_lengths(self, cfg: ModelConfig, max_length: int) -> List[int]:
        """Each layer's positions a slot: ``ring_length`` for a 'sliding'
        layer, ``max_length`` for a 'full' one, none for a 'linear' or
        'ssm' one (its memory is a state: ``bytes_per_slot``)."""
        ring = (self.ring_length(cfg, max_length)
                if cfg.has_window_layers else max_length)
        by_kind = {"sliding": ring, "full": max_length, "linear": 0,
                   "ssm": 0}
        return [by_kind[cfg.layer_kind(l)] for l in range(cfg.n_layers)]

    # -- paged layout --------------------------------------------------------

    def pages_per_slot(self, max_length: int) -> int:
        """Page-table width: enough table columns to map a full-length
        row. A slot never maps more — oversubscription shrinks the POOL,
        never the table (the table shape is compiled into the programs)."""
        return -(-max_length // self.page_tokens)

    def total_pool_pages(self, n_rows: int, max_length: int) -> int:
        """Physical pages allocated on device: the usable pool
        (``pool_pages``, defaulting to ``n_rows`` full-length rows —
        contiguous-equivalent capacity) plus the reserved trash page 0.

        Page 0 is never owned by any slot: zeroed table entries point at
        it, so out-of-range appends (a free row's garbage lane, a
        mid-prefill row's clamped tail) land there instead of corrupting
        live pages, and gathers from it are always masked."""
        usable = self.pool_pages or n_rows * self.pages_per_slot(max_length)
        return usable + 1

    def page_bytes(self, cfg: ModelConfig) -> int:
        """Device bytes of ONE page across every layer and sidecar — the
        exact quantum the ledger reconciles against: total cache bytes
        == total_pool_pages x page_bytes."""
        import jax.numpy as jnp

        width = jnp.dtype(self.cache_dtype(cfg)).itemsize
        per = 2 * cfg.n_layers * cfg.n_kv_groups * self.page_tokens
        kv = per * cfg.head_dim * width
        scale = per * 4 if self.quantized else 0
        return kv + scale

    def bytes_per_slot(self, cfg: ModelConfig, max_length: int) -> Dict[str, int]:
        """Per-slot cache bytes under this policy: the HBM number that
        decides ``n_slots`` (proven against ``memory_analysis()`` /
        ``nbytes`` in tests). ``kv_bytes`` is the K+V data alone — int8
        halves it exactly vs bf16; ``scale_bytes`` is the quantization
        sidecar (0 unquantized)."""
        import jax.numpy as jnp

        per_pos = cfg.n_kv_groups * cfg.head_dim
        width = jnp.dtype(self.cache_dtype(cfg)).itemsize
        positions = sum(self.layer_lengths(cfg, max_length))
        kv = 2 * positions * per_pos * width
        scale = (2 * positions * cfg.n_kv_groups * 4
                 if self.quantized else 0)
        out = {"kv_bytes": kv, "scale_bytes": scale,
               "total_bytes": kv + scale,
               "bytes_per_token": (kv + scale) // max_length}
        if cfg.has_state_layers:
            # whatever the length: a float32 state and the convolution's
            # tail, a 'linear' or 'ssm' layer
            el = jnp.dtype(cfg.jax_dtype).itemsize
            out["state_bytes"] = sum(
                math.prod(state) * 4 + math.prod(tail) * el
                for tail, state in (cfg.state_shapes(cfg.layer_kind(l))
                                    for l in cfg.state_layers))
            out["total_bytes"] += out["state_bytes"]
        return out

    def describe(self) -> Dict[str, Any]:
        """Event-payload summary (rides ``serve_warmup``)."""
        out = {"kv_quant": self.kv_quant,
               "prefix_cache": self.prefix_cache,
               "prefill_chunk": self.prefill_chunk}
        if self.paged:
            out["kv_paged"] = True
            out["page_tokens"] = self.page_tokens
            out["pool_pages"] = self.pool_pages
        return out


#: slot caches allocated before the policy object existed (or by older
#: call sites passing policy=None) behave exactly like this
DEFAULT_POLICY = KVCachePolicy()


def cache_is_quantized(cache: Params) -> bool:
    """Data-driven quantization probe: the cache pytree itself says
    whether appends must quantize and attention must dequantize — the
    model code never needs the policy object."""
    return "k_scale" in cache


def cache_nbytes(cache: Params) -> int:
    """Total device bytes of one cache pytree — per-layer buffer LISTS
    (slot caches) or stacked pane ARRAYS (prefix panes) alike."""
    total = 0
    for leaves in cache.values():
        if isinstance(leaves, (list, tuple)):
            total += sum(leaf.nbytes for leaf in leaves if leaf is not None)
        else:
            total += leaves.nbytes
    return total


# ---------------------------------------------------------------------------
# the page pool (paged layout only; host-side allocator)
# ---------------------------------------------------------------------------

class PagePool:
    """Host-side allocator + refcounts for the shared device page pool.

    The device arrays are a flat pool of ``n_pages`` fixed-size pages;
    WHICH page holds WHICH slot's positions is pure host bookkeeping —
    the per-slot int32 page table rides the jitted programs as traced
    data (the adapter-pool trick: identity is data, capacity is static,
    so page churn never recompiles anything).

    Refcounts make prefix sharing copy-free: a prefix hit increfs the
    stored entry's pages and writes their ids into the slot's table —
    zero device work. A page returns to the free list only when its LAST
    owner (slot or store entry) drops it, so effective capacity is
    bounded by tokens in flight, not ``n_slots x Tmax``.

    ``reserved`` is the admission ledger: admitting a request reserves
    its worst-case PRIVATE page need up front (free pages minus reserved
    is what admission may promise next), and each on-demand allocation
    by that slot draws its reservation down — so two admitted requests
    can never deadlock mid-decode fighting over the same last page.

    Page 0 is the trash page: permanently allocated, never freed, the
    target of every zeroed table entry (see
    ``KVCachePolicy.total_pool_pages``).

    Thread-safe (leaf lock; callers hold the engine lock anyway, but
    stats/ledger probes may fire from admin threads).
    """

    def __init__(self, n_pages: int, page_bytes: int):
        if n_pages < 2:
            raise ValueError("PagePool needs >= 2 pages (trash + 1 usable)")
        self.n_pages = int(n_pages)
        self.page_bytes = int(page_bytes)
        self._lock = threading.Lock()
        self._refs = np.zeros(self.n_pages, np.int64)   # guarded-by: _lock
        self._refs[0] = 1                               # trash page: pinned
        # lowest-id-first free list keeps page ids dense and runs
        # byte-reproducible across identical request sequences
        self._free = list(range(self.n_pages - 1, 0, -1))  # pop() -> lowest
        self.reserved = 0               # guarded-by: _lock
        self.n_allocs = 0               # guarded-by: _lock
        self.n_frees = 0                # guarded-by: _lock
        self.peak_used = 0              # guarded-by: _lock

    # -- allocation ----------------------------------------------------------

    def alloc(self, *, from_reserved: bool = False) -> int:
        """Take the lowest free page (refcount 1). ``from_reserved=True``
        consumes one unit of the admission reservation that promised
        this page. Raises ``RuntimeError`` on exhaustion — admission
        checks ``available()`` first, so running dry here is an
        accounting bug, not an oversubscription event."""
        with self._lock:
            if not self._free:
                raise RuntimeError(
                    "page pool exhausted: admission reservation "
                    "accounting is broken (alloc past available())")
            page = self._free.pop()
            self._refs[page] = 1
            if from_reserved:
                self.reserved = max(self.reserved - 1, 0)
            self.n_allocs += 1
            used = self.n_pages - 1 - len(self._free)
            if used > self.peak_used:
                self.peak_used = used
            return page

    def incref(self, page: int) -> None:
        with self._lock:
            if page == 0 or self._refs[page] <= 0:
                raise RuntimeError(
                    f"incref on unallocated page {page} (use-after-free)")
            self._refs[page] += 1

    def decref(self, page: int) -> bool:
        """Drop one reference; returns True when the page went back to
        the free list."""
        with self._lock:
            if page == 0:
                return False            # trash page is never freed
            if self._refs[page] <= 0:
                raise RuntimeError(
                    f"decref on free page {page} (double free)")
            self._refs[page] -= 1
            if self._refs[page] == 0:
                self._free.append(page)
                self._free.sort(reverse=True)
                self.n_frees += 1
                return True
            return False

    def refcount(self, page: int) -> int:
        with self._lock:
            return int(self._refs[page])

    # -- admission ledger ----------------------------------------------------

    def available(self) -> int:
        """Free pages not yet promised to an admitted request — what
        admission may still hand out."""
        with self._lock:
            return len(self._free) - self.reserved

    def reserve(self, n: int) -> None:
        with self._lock:
            self.reserved += int(n)    # graft-ok: GL011 host int

    def unreserve(self, n: int) -> None:
        with self._lock:
            self.reserved = max(
                self.reserved - int(n), 0)  # graft-ok: GL011 host int

    # -- introspection -------------------------------------------------------

    @property
    def n_free(self) -> int:
        with self._lock:
            return len(self._free)

    def used_pages(self) -> int:
        """Allocated pages, trash page excluded."""
        with self._lock:
            return self.n_pages - 1 - len(self._free)

    def stats(self) -> dict:
        with self._lock:
            used = self.n_pages - 1 - len(self._free)
            return {"n_pages": self.n_pages - 1,     # usable (sans trash)
                    "page_bytes": self.page_bytes,
                    "used": used,
                    "free": len(self._free),
                    "reserved": self.reserved,
                    "peak_used": self.peak_used,
                    "allocs": self.n_allocs,
                    "frees": self.n_frees}


# ---------------------------------------------------------------------------
# pane primitives (jitted by the engine; pane width is STATIC)
# ---------------------------------------------------------------------------

def copy_prefix_into_slot(cache: Params, panes: Params, slot) -> Params:
    """Write a stored prefix's stacked per-layer panes into row ``slot``
    of the slot cache: one dynamic-update-slice per layer per k/v (and
    per scale sidecar when quantized). ``panes`` leaves are
    (L, Hkv, P, hd) / (L, Hkv, P, 1) with P static; ``slot`` is data.

    This is the whole prefix-HIT compute: no embedding, no projection,
    no attention — zero prompt-forward FLOPs for the cached span
    (test-asserted via a forward-call spy)."""
    import jax

    def write(bufs, pane):
        return [jax.lax.dynamic_update_slice(
                    buf, pane[layer][None].astype(buf.dtype),
                    (slot, 0, 0, 0))
                for layer, buf in enumerate(bufs)]

    return {name: write(bufs, panes[name]) for name, bufs in cache.items()}


def extract_prefix_panes(cache: Params, slot, n_valid, *,
                         pane_len: int) -> Params:
    """Read row ``slot``'s first ``pane_len`` positions out of the slot
    cache as stacked (L, Hkv, pane_len, ...) panes, ZEROING every
    position >= ``n_valid``.

    The zeroing is load-bearing, not cosmetic: positions past the
    prefix span hold whatever the slot saw last (the request's own
    suffix KV, a previous occupant's decode tail, pad garbage) — all of
    it request-private state that must never become shareable. Clamping
    to the span makes a stored pane a pure function of
    (prefix tokens, params, adapter): byte-deterministic, so its hash
    key and any audit of store contents are stable across donors."""
    import jax
    import jax.numpy as jnp

    keep = jnp.arange(pane_len) < n_valid

    def take(bufs):
        rows = []
        for buf in bufs:
            row = jax.lax.dynamic_slice(
                buf, (slot, 0, 0, 0), (1,) + buf.shape[1:])[0]
            row = row[:, :pane_len]
            rows.append(jnp.where(keep[None, :, None], row,
                                  jnp.zeros((), buf.dtype)))
        return jnp.stack(rows)

    return {name: take(bufs) for name, bufs in cache.items()}


# ---------------------------------------------------------------------------
# the prefix store
# ---------------------------------------------------------------------------

class _Entry:
    __slots__ = ("key", "panes", "span", "nbytes", "pins", "hits",
                 "t_insert", "tag", "pages")

    def __init__(self, key: str, panes: Optional[Params], span: int,
                 nbytes: int, tag: Optional[str] = None,
                 pages: Optional[List[int]] = None):
        self.key = key
        self.panes = panes
        self.span = span
        self.nbytes = nbytes
        self.pins = 0
        self.hits = 0
        self.t_insert = time.monotonic()
        # namespace tag (adapter identity) for per-tenant byte
        # attribution; None for raw-key imports (the donor's tag is
        # hashed into the key but not transported)
        self.tag = tag
        # paged layout: the store owns REFERENCES to shared pool pages
        # instead of a private pane copy (panes is None) — nbytes is the
        # pages' pool footprint, charged against the same LRU budget
        self.pages = pages


class PrefixStore:
    """Hash-keyed LRU store of device-resident prefix KV panes.

    Keys are sha1(model-fingerprint, adapter-tag, token-ids): a pane can
    only ever hit for the exact tokens, base weights, and adapter load
    it was computed under. Spans are CHUNK-GRANULAR — lookups probe the
    longest multiple-of-``chunk_tokens`` prefix first and walk down, so
    a prompt sharing only part of a stored prefix still reuses the
    shared chunks.

    Concurrency: the engine calls ``match``/``insert``/``release`` under
    its own lock, but mutations also serialize on the store lock so
    registry-style admin (stats, external eviction) is safe from any
    thread. Pinning follows the ``AdapterRegistry`` non-reuse
    discipline: an entry pinned by an in-flight copy is never evicted —
    eviction skips it and charges the budget overrun to the next insert.
    """

    def __init__(self, fingerprint: str, *, chunk_tokens: int,
                 budget_bytes: int, pane_tokens: int,
                 page_pool: Optional[PagePool] = None):
        if chunk_tokens < 1:
            raise ValueError("chunk_tokens must be >= 1")
        self.fingerprint = fingerprint
        self.chunk_tokens = int(chunk_tokens)
        self.budget_bytes = int(budget_bytes)
        self.pane_tokens = int(pane_tokens)
        # paged layout: entries hold pool page ids, and eviction must
        # return the store's references to this pool
        self.page_pool = page_pool
        self._lock = threading.Lock()
        self._entries: "OrderedDict[str, _Entry]" = OrderedDict()
        self.bytes_total = 0            # guarded-by: _lock
        self.n_hits = 0                 # guarded-by: _lock
        self.n_misses = 0               # guarded-by: _lock
        self.n_inserts = 0              # guarded-by: _lock
        self.n_evictions = 0            # guarded-by: _lock
        self.n_insert_skips = 0         # guarded-by: _lock

    # -- keys --------------------------------------------------------------

    def key(self, token_ids, tag: str) -> str:
        h = hashlib.sha1()
        h.update(self.fingerprint.encode())
        h.update(b"\x00")
        h.update(tag.encode())
        h.update(b"\x00")
        h.update(np.ascontiguousarray(token_ids, np.int32).tobytes())
        return h.hexdigest()

    def storable_span(self, prompt_len: int) -> int:
        """Longest chunk-aligned span of a ``prompt_len`` prompt worth
        storing: capped one below the prompt (a hit must leave >= 1
        suffix token to produce first-token logits) and at the static
        pane width."""
        span = ((prompt_len - 1) // self.chunk_tokens) * self.chunk_tokens
        return min(span, self.pane_tokens)

    # -- engine-side hot path ----------------------------------------------

    def match(self, prompt_ids, tag: str, *, min_span: int = 0,
              count_miss: bool = True) -> Tuple[int, Optional[_Entry]]:
        """Longest-prefix lookup for one prompt. Returns (span, entry):
        span 0 / None on a miss. A returned entry is PINNED — the caller
        must ``release`` it after copying its panes.

        ``min_span``: only spans strictly longer count (the mid-prefill
        catch-up probe — a pane no longer than what the slot already
        holds is not a hit). ``count_miss=False`` keeps that repeated
        probe from inflating the miss ratio: only admission-time misses
        are real workload misses."""
        n_max = self.storable_span(len(prompt_ids))
        for m in range(n_max // self.chunk_tokens, 0, -1):
            span = m * self.chunk_tokens
            if span <= min_span:
                break
            k = self.key(prompt_ids[:span], tag)
            with self._lock:
                entry = self._entries.get(k)
                if entry is not None:
                    self._entries.move_to_end(k)
                    entry.hits += 1
                    entry.pins += 1
                    self.n_hits += 1
                    return span, entry
        if count_miss:
            with self._lock:
                self.n_misses += 1
        return 0, None

    def release(self, entry: _Entry) -> None:
        with self._lock:
            entry.pins = max(entry.pins - 1, 0)

    def contains(self, token_ids, tag: str) -> bool:
        k = self.key(token_ids, tag)
        with self._lock:
            if k in self._entries:
                self._entries.move_to_end(k)
                return True
        return False

    def insert(self, token_ids, tag: str, panes: Params) -> int:
        """Store one prefix's panes under the LRU byte budget; evicts
        least-recently-used UNPINNED entries to make room. Returns the
        entry's byte size, or 0 — skipped (and counted) — when the
        entry alone exceeds the budget or everything evictable is
        pinned (also 0, uncounted, when the key is already stored)."""
        return self._insert_keyed(self.key(token_ids, tag), panes,
                                  len(token_ids), tag=tag)

    def insert_pages(self, token_ids, tag: str, pages: List[int]) -> int:
        """Paged insert: store REFERENCES to the donor slot's pool pages
        instead of copying panes — the store increfs each page (its own
        ownership, outliving the donor slot) and charges their pool
        footprint to the same LRU byte budget. Zero device work: the
        panes already live in the pool; sharing is bookkeeping."""
        if self.page_pool is None:
            raise RuntimeError("insert_pages needs a page_pool-backed "
                               "PrefixStore")
        return self._insert_keyed(
            self.key(token_ids, tag), None, len(token_ids), tag=tag,
            pages=list(pages),
            nbytes=len(pages) * self.page_pool.page_bytes)

    def import_entry(self, key: str, panes: Params, span: int) -> int:
        """Raw-key insert for cross-process pane handoff (fleet drain).

        The key is sha1(fingerprint, tag, tokens) computed by the donor;
        fingerprints are config-derived, so same-config workers agree on
        every key and the donor's keys import verbatim — the adoptee
        serves the shared prefix as a hit without recomputing anything.
        Same LRU/budget/pin discipline as ``insert``."""
        return self._insert_keyed(key, panes, int(span))

    def export_entries(self) -> list:
        """Snapshot ``[(key, span, panes)]`` LRU-first (so the adoptee's
        LRU order, rebuilt by importing in sequence, matches the
        donor's). Panes are the live device/host arrays — the transport
        layer serializes them."""
        with self._lock:
            return [(e.key, e.span, e.panes)
                    for e in self._entries.values()
                    if e.panes is not None]    # paged entries hold pool
                                               # page ids, meaningless in
                                               # another process's pool

    def _insert_keyed(self, k: str, panes: Optional[Params], span: int,
                      tag: Optional[str] = None,
                      pages: Optional[List[int]] = None,
                      nbytes: Optional[int] = None) -> int:
        if nbytes is None:
            nbytes = cache_nbytes(panes)
        evicted = []
        with self._lock:
            if k in self._entries:
                self._entries.move_to_end(k)
                return 0
            if nbytes > self.budget_bytes:
                self.n_insert_skips += 1
                return 0
            while self.bytes_total + nbytes > self.budget_bytes:
                victim_key = next(
                    (key for key, e in self._entries.items() if e.pins == 0),
                    None)
                if victim_key is None:       # everything evictable pinned
                    self.n_insert_skips += 1
                    return 0
                victim = self._entries.pop(victim_key)
                self.bytes_total -= victim.nbytes
                self.n_evictions += 1
                evicted.append(victim)
            if pages is not None:
                # the store's own references; the donor slot keeps its
                # refs and drops them independently at retirement
                for p in pages:
                    self.page_pool.incref(p)
            entry = _Entry(k, panes, span, nbytes, tag=tag, pages=pages)
            self._entries[k] = entry
            self.bytes_total += nbytes
            self.n_inserts += 1
            n_entries = len(self._entries)
            bytes_total = self.bytes_total
        for victim in evicted:
            self._release_victim_pages(victim)
            get_metrics().event(
                "prefix_evict", key=victim.key, bytes=victim.nbytes,
                span_tokens=victim.span, hits=victim.hits,
                age_s=round(time.monotonic() - victim.t_insert, 3),
                entries_left=n_entries, bytes_left=bytes_total)
        logger.debug("Prefix stored: %s span %d (%d bytes, %d entries, "
                     "%d evicted).", k[:12], span, nbytes,
                     n_entries, len(evicted))
        return nbytes

    def _release_victim_pages(self, victim: _Entry) -> None:
        """Return an evicted/cleared paged entry's page references to
        the pool (pages whose last owner was the store go back on the
        free list — eviction RECLAIMS capacity, exactly like freeing a
        pane copy did in the contiguous layout)."""
        if victim.pages is not None and self.page_pool is not None:
            for p in victim.pages:
                self.page_pool.decref(p)

    def clear(self) -> None:
        """Drop every entry, releasing paged page references. The paged
        engine restart path calls this: stored entries reference pages
        of the ABOUT-TO-BE-REPLACED pool arrays, so unlike the
        contiguous store (whose private pane copies survive a cache
        rebuild) they cannot outlive a restart."""
        with self._lock:
            victims = list(self._entries.values())
            self._entries.clear()
            self.bytes_total = 0
        for victim in victims:
            self._release_victim_pages(victim)

    # -- introspection -----------------------------------------------------

    @property
    def n_entries(self) -> int:
        return len(self._entries)

    def hit_ratio(self) -> Optional[float]:
        with self._lock:
            hits, misses = self.n_hits, self.n_misses
        n = hits + misses
        return (hits / n) if n else None

    def bytes_by_tag(self) -> Dict[str, int]:
        """Per-namespace byte attribution for the memory ledger: tag ->
        total pane bytes ("external" for raw-key imports, whose donor
        tag is hashed into the key but not transported)."""
        out: Dict[str, int] = {}
        with self._lock:
            for e in self._entries.values():
                tag = e.tag if e.tag is not None else "external"
                out[tag] = out.get(tag, 0) + e.nbytes
        return out

    def pinned_bytes(self) -> Tuple[int, List[str]]:
        """(bytes, keys) of currently pinned entries. Pins are transient
        by design — held only across one in-flight pane copy under the
        engine lock — so anything still pinned at a cadence boundary is
        an orphan: the memory ledger's ``pinned_orphan`` probe turns a
        non-empty answer into a ``memory_drift`` event."""
        with self._lock:
            pinned = [(e.key, e.nbytes) for e in self._entries.values()
                      if e.pins > 0]
        return sum(nb for _k, nb in pinned), [k for k, _nb in pinned]

    def stats(self) -> dict:
        with self._lock:
            return {
                "entries": len(self._entries),
                "bytes": self.bytes_total,
                "budget_bytes": self.budget_bytes,
                "hits": self.n_hits,
                "misses": self.n_misses,
                "inserts": self.n_inserts,
                "evictions": self.n_evictions,
                "insert_skips": self.n_insert_skips,
                "chunk_tokens": self.chunk_tokens,
                "pane_tokens": self.pane_tokens,
            }
