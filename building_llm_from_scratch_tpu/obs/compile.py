"""XLA compile telemetry: AOT compile capture, HLO cost/memory analysis,
recompile detection, and persistent-compilation-cache wiring.

The analytic MFU in obs/mfu.py trusts a hand-derived FLOPs formula; XLA
knows what it actually built. ``CompileWatcher`` wraps the trainer's jitted
train step and, on the first call for each argument signature, runs the
explicit AOT path (``lower()`` -> ``compile()``) so compile time becomes a
measured number instead of an invisible chunk of the first step, then reads
the executable's ``cost_analysis()`` (HLO-counted FLOPs -> an HLO-measured
MFU to cross-check the analytic one) and ``memory_analysis()`` (HBM
breakdown: arguments / outputs / temps / generated code vs device
capacity — the OOM postmortem numbers). Each capture lands as one
``compile`` event in the metrics JSONL plus gauges.

Every program the process builds, watched or not, also leaves one
memory-only ``program`` record in the metrics hub (``keep_program_books``:
one process-wide ``jax.monitoring`` listener, which fires only when JAX
builds something): tracing, lowering and cache load or compile apart, and
the persistent cache's verdict by JAX's own events. They are the books the
set-up metrics are read from (obs/schema.py ``PROGRAM_RECORD_FIELDS``).

A signature change after the first call is a RECOMPILE — the classic silent
TPU performance bug (a ragged last batch, a dtype drift after resume): the
watcher emits a ``recompile`` event naming the exact leaf-path shape/dtype
diff, then captures the new executable the same way. Steady-state calls are
a dict lookup + the dispatch itself.

``configure_compile_cache`` places JAX's persistent compilation cache with
entry-count/bytes telemetry: the compile event records whether this
process's compile was served from cache (no new entries written) or paid
for (new entries landed), so relaunch latency is measurable.

Failure policy: telemetry must never take down the run it observes. If the
AOT path raises for an exotic step builder, the watcher logs, emits a
``compile_fallback`` event, and permanently delegates to the wrapped jit
function (whose implicit compile still happens, just unmeasured).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from building_llm_from_scratch_tpu.obs.metrics import get_metrics
from building_llm_from_scratch_tpu.utils.logging import setup_logger

logger = setup_logger(__name__)

#: Active fingerprint collectors (obs/perf.FingerprintCollector installs
#: itself here for the duration of one bench run): every CompileWatcher
#: capture/recompile is reported to each, so a bench's structural
#: fingerprint covers EVERY watched program that compiled while it ran —
#: the trainer step and all five serving-engine programs alike.
_collectors: List[Any] = []


def add_collector(collector: Any) -> None:
    """Register a fingerprint collector (``on_compile(label, sig, stats,
    n_tokens=)`` / ``on_recompile(label, diff)`` duck type)."""
    _collectors.append(collector)


def remove_collector(collector: Any) -> None:
    try:
        _collectors.remove(collector)
    except ValueError:
        pass


def _notify_collectors(method: str, *args, **kw) -> None:
    # observation must never take down the observed program
    for c in list(_collectors):
        try:
            getattr(c, method)(*args, **kw)
        except Exception as e:            # pragma: no cover - collector bug
            logger.warning("fingerprint collector %s failed: %s", method, e)


#: memory_analysis() attributes surfaced in the compile event (bytes).
_MEMORY_FIELDS = (
    ("argument_size_in_bytes", "args_bytes"),
    ("output_size_in_bytes", "output_bytes"),
    ("temp_size_in_bytes", "temp_bytes"),
    ("alias_size_in_bytes", "alias_bytes"),
    ("generated_code_size_in_bytes", "generated_code_bytes"),
)


def fast_signature(tree: Any) -> Tuple:
    """Steady-state cache key for the watcher's per-step check: (treedef,
    per-leaf (shape, dtype, sharding)). Unlike ``tree_signature`` it builds
    NO path strings — shape tuples, dtype objects and shardings are
    existing hashables, so the hot loop pays one tree_flatten and a tuple
    build, keeping the no-per-step-host-work discipline. The treedef
    covers structural changes that path strings would have caught."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return treedef, tuple(
        (getattr(leaf, "shape", ()), getattr(leaf, "dtype", None),
         getattr(leaf, "sharding", None))
        for leaf in leaves)


def tree_signature(tree: Any) -> Tuple:
    """Hashable (path, shape, dtype, sharding) signature of a pytree of
    arrays — what XLA keys its compiled executables on. Shardings are part
    of the key because an AOT executable is pinned to them: under fsdp the
    optimizer-state shardings legitimately change between the first and
    second step (shard_state places them replicated, the step's
    with_sharding_constraint pins them sharded), which plain jit silently
    re-compiled for — the watcher must key on it too (and now reports it).
    Cheap host work: attribute reads only, no device sync."""
    flat, treedef = jax_tree_flatten_with_path(tree)
    return tuple(
        (path, tuple(getattr(leaf, "shape", ())),
         str(getattr(leaf, "dtype", type(leaf).__name__)),
         getattr(leaf, "sharding", None))
        for path, leaf in flat)


def jax_tree_flatten_with_path(tree: Any) -> Tuple[List[Tuple[str, Any]], Any]:
    """tree_flatten_with_path with the path rendered as a compact string
    ('trainable/blocks/attn/wq') so signature diffs read as leaf names."""
    import jax

    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        parts = []
        for p in path:
            key = getattr(p, "key", None)
            if key is None:
                key = getattr(p, "idx", None)
            parts.append(str(key))
        out.append(("/".join(parts), leaf))
    return out, treedef


def _leaf_desc(sig_entry) -> Dict[str, Any]:
    shape, dtype = sig_entry[0], sig_entry[1]
    out: Dict[str, Any] = {"shape": list(shape), "dtype": dtype}
    sharding = sig_entry[2] if len(sig_entry) > 2 else None
    if sharding is not None:
        spec = getattr(sharding, "spec", None)
        out["sharding"] = str(spec if spec is not None else sharding)
    return out


def signature_diff(old: Tuple, new: Tuple) -> List[Dict[str, Any]]:
    """Human-readable leaf-level diff between two tree signatures: changed
    shapes/dtypes/shardings plus added/removed leaves."""
    old_map = {e[0]: e[1:] for e in old}
    new_map = {e[0]: e[1:] for e in new}
    diff: List[Dict[str, Any]] = []
    for path in sorted(set(old_map) | set(new_map)):
        a, b = old_map.get(path), new_map.get(path)
        if a == b:
            continue
        entry: Dict[str, Any] = {"leaf": path}
        if a is None:
            entry["added"] = _leaf_desc(b)
        elif b is None:
            entry["removed"] = _leaf_desc(a)
        else:
            entry["was"] = _leaf_desc(a)
            entry["now"] = _leaf_desc(b)
        diff.append(entry)
    return diff


def extract_cost_analysis(compiled) -> Dict[str, float]:
    """Normalize ``Compiled.cost_analysis()`` across jax versions (dict in
    newer releases, [dict] per-device in 0.4.x) to flat float fields."""
    try:
        cost = compiled.cost_analysis()
    except Exception as e:                     # pragma: no cover - backend gap
        logger.warning("cost_analysis unavailable: %s", e)
        return {}
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    if not isinstance(cost, dict):
        return {}
    out: Dict[str, float] = {}
    for key in ("flops", "transcendentals", "bytes accessed"):
        val = cost.get(key)
        if isinstance(val, (int, float)):
            out[key.replace(" ", "_")] = float(val)
    return out


def extract_memory_analysis(compiled) -> Dict[str, int]:
    """``Compiled.memory_analysis()`` -> {args/output/temp/alias/
    generated_code}_bytes (+ total), or {} when the backend exposes none."""
    try:
        mem = compiled.memory_analysis()
    except Exception as e:                     # pragma: no cover - backend gap
        logger.warning("memory_analysis unavailable: %s", e)
        return {}
    if mem is None:
        return {}
    out: Dict[str, int] = {}
    for attr, name in _MEMORY_FIELDS:
        val = getattr(mem, attr, None)
        if isinstance(val, int):
            out[name] = val
    if out:
        # peak-footprint proxy: aliased bytes (donated inputs) are reused
        # by outputs, so counting args+outputs+temps double-counts them
        out["total_bytes"] = (out.get("args_bytes", 0)
                              + out.get("output_bytes", 0)
                              + out.get("temp_bytes", 0)
                              + out.get("generated_code_bytes", 0)
                              - out.get("alias_bytes", 0))
    return out


def device_hbm_capacity() -> Optional[int]:
    """bytes_limit of device 0, or None off-TPU (CPU memory_stats is None)."""
    try:
        import jax

        stats = jax.local_devices()[0].memory_stats()
    except Exception:
        return None
    if not stats:
        return None
    limit = stats.get("bytes_limit")
    return int(limit) if isinstance(limit, int) else None


def executable_device_count(compiled) -> int:
    """Number of devices the compiled executable spans, read off its input
    shardings (1 for a plain single-device jit). Needed to globalize
    ``cost_analysis()``: under SPMD it reports the PER-DEVICE module's
    numbers."""
    try:
        import jax

        best = 1
        for s in jax.tree_util.tree_leaves(compiled.input_shardings):
            device_set = getattr(s, "device_set", None)
            if device_set:
                best = max(best, len(device_set))
        return best
    except Exception:
        return 1


# ---------------------------------------------------------------------------
# The books of what the process builds: one `program` record a program
# ---------------------------------------------------------------------------

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_TO_MLIR = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_VERDICTS = {"/jax/compilation_cache/cache_hits": "hit",
                   "/jax/compilation_cache/cache_misses": "miss"}


class _Building(threading.local):
    """What JAX has said, on this thread, of the program being built: its
    events come on the thread that builds, in the order tracing, lowering,
    the cache's verdict, the retrieval's seconds, the backend's seconds."""

    #: inside ``aot_compile``: its caller books the program, the listener
    #: takes the tracing's seconds and the cache's verdict down
    watched = False
    cache = "off"
    retrieval_s: Optional[float] = None
    lower_s = 0.0
    #: the longest tracing JAX reported: every nested ``jit`` reports its own
    #: INSIDE the outer one's, so the outermost is the longest, not the sum
    trace_s = 0.0

    def take(self) -> Dict[str, Any]:
        """The cache's part of the record, and the slate wiped for the
        next program."""
        out: Dict[str, Any] = {"cache": self.cache}
        if self.retrieval_s is not None:
            out["retrieval_s"] = round(self.retrieval_s, 4)
        self.cache, self.retrieval_s, self.lower_s = "off", None, 0.0
        self.trace_s = 0.0
        return out


_building = _Building()
_listening = False
_listen_lock = threading.Lock()


def _on_event(name: str, **_) -> None:
    verdict = _CACHE_VERDICTS.get(name)
    if verdict is not None:
        _building.cache = verdict


def _on_duration(name: str, secs: float, **kw) -> None:
    if name == _TRACE:
        if _building.watched and secs > _building.trace_s:
            _building.trace_s = secs
    elif name == _CACHE_RETRIEVAL:
        _building.retrieval_s = secs
    elif _building.watched:
        return
    elif name == _TO_MLIR:
        _building.lower_s = secs
    elif name == _BACKEND_COMPILE:
        # a program no watcher wraps (an eager convert_element_type, a
        # request's _threefry_seed): booked under JAX's name for it
        lower_s = _building.lower_s
        _keep_program(str(kw.get("fun_name", "?")), None, round(lower_s, 4),
                      round(secs, 4), watched=False, **_building.take())


def keep_program_books() -> None:
    """Register THE one process-wide ``jax.monitoring`` listener pair (once;
    JAX keeps listeners for the life of the process). It fires only when
    JAX builds something: nothing per call, per tick or per step. Every
    entry point gets it through ``configure_compile_cache``, every engine
    and trainer through ``obs/timeline.books_init``, every watched build
    through ``aot_compile``."""
    global _listening
    with _listen_lock:
        if _listening:
            return
        import jax.monitoring

        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True


def _keep_program(label: str, trace_s: Optional[float], lower_s: float,
                  load_or_compile_s: float, *, watched: bool, cache: str,
                  retrieval_s: Optional[float] = None) -> None:
    """One ``program`` record (obs/schema.py ``PROGRAM_RECORD_FIELDS``),
    stamped now, on the thread that built the program."""
    rec = {"label": label, "t_end": time.perf_counter(),
           "time": time.time(), "trace_s": trace_s, "lower_s": lower_s,
           "load_or_compile_s": load_or_compile_s, "cache": cache,
           "watched": watched, "thread": threading.current_thread().name}
    if retrieval_s is not None:
        rec["retrieval_s"] = retrieval_s
    get_metrics().keep_record("program", rec)


def book_program(label: str, stats: Dict[str, Any]) -> None:
    """The record of a program ``aot_compile`` built, from its ``stats``."""
    trace_s = stats.get("trace_seconds", 0.0)
    _keep_program(label, trace_s,
                  round(stats.get("lower_seconds", 0.0) - trace_s, 4),
                  stats.get("backend_compile_seconds", 0.0), watched=True,
                  cache=stats.get("cache", "off"),
                  retrieval_s=stats.get("cache_retrieval_seconds"))


def program_table(records=None) -> List[Dict[str, Any]]:
    """The operator's reading of the ``program`` records: label, the three
    seconds and the cache's verdict, in the order the programs were built."""
    if records is None:
        records = get_metrics().recent("program")
    return [{k: r.get(k) for k in ("label", "trace_s", "lower_s",
                                   "load_or_compile_s", "cache")}
            for r in records]


def aot_compile(fn: Callable, *args) -> Tuple[Any, Dict[str, Any]]:
    """Explicitly lower+compile a jitted callable for ``args``; returns
    (compiled_executable, stats). Stats carry ``compile_seconds`` split
    into ``lower_seconds`` (tracing AND lowering, as it always read; of it
    ``trace_seconds``, the Python function run into a jaxpr, is JAX's own
    report of the outermost trace) and ``backend_compile_seconds`` (a load
    from the persistent cache or the compiler: ``cache`` says which, by
    JAX's own events), cost analysis and the memory breakdown.

    The stages are NOT taken apart by separate ``fn.trace()`` and
    ``.lower()`` calls: measured on the chip (PERF.md section 6, PR 44) that
    form costs a warm start 0.15 s a 48-layer program over this one call.

    Cost numbers are GLOBAL: ``cost_analysis()`` reports the per-device
    SPMD module (measured: a 2-device-sharded matmul reports half the
    single-device FLOPs), so ``flops``/``transcendentals``/
    ``bytes_accessed`` are scaled by the executable's device count —
    consumers divide by global token counts. The per-device figure stays
    as ``flops_per_device``; the ``memory`` breakdown is deliberately
    per-device (it is compared against one device's HBM capacity).

    Raises whatever the trace/compile raises — callers own fallback."""
    keep_program_books()
    _building.take()
    _building.watched = True
    try:
        t0 = time.perf_counter()
        lowered = fn.lower(*args)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t2 = time.perf_counter()
    finally:
        _building.watched = False
        trace_s = _building.trace_s
        cache = _building.take()
    stats: Dict[str, Any] = {
        "compile_seconds": round(t2 - t0, 4),
        "trace_seconds": round(min(trace_s, t1 - t0), 4),
        "lower_seconds": round(t1 - t0, 4),
        "backend_compile_seconds": round(t2 - t1, 4),
        "cache": cache["cache"],
    }
    if "retrieval_s" in cache:
        stats["cache_retrieval_seconds"] = cache["retrieval_s"]
    cost = extract_cost_analysis(compiled)
    n_dev = executable_device_count(compiled)
    stats["executable_device_count"] = n_dev
    if n_dev > 1 and "flops" in cost:
        cost["flops_per_device"] = cost["flops"]
        for key in ("flops", "transcendentals", "bytes_accessed"):
            if key in cost:
                cost[key] = cost[key] * n_dev
    stats.update(cost)
    mem = extract_memory_analysis(compiled)
    if mem:
        stats["memory"] = mem
    return compiled, stats


class CompileWatcher:
    """Wraps a jitted callable: AOT-compiles per argument signature, emits
    ``compile``/``recompile`` telemetry, and exposes the HLO-measured
    FLOPs for the trainer's MFU cross-check.

    Call-compatible with the wrapped step (any arity): for the trainer,
    ``watcher(state, batch)``.

    Two recompile policies:
      - default (``multi_program=False``, the train step): ONE signature is
        legitimate — any later signature change is a silent-perf-bug
        recompile.
      - ``multi_program=True`` (the serving engine's bucketed prefill /
        decode programs): a KNOWN SET of signatures is legitimate. New
        signatures during warmup are plain ``compile`` events; after the
        caller ``freeze()``s the set, an unseen signature is a bucket miss
        and emits ``recompile`` with the leaf diff — the silent latency
        cliff the serving telemetry exists to surface.
    """

    def __init__(self, fn: Callable, label: str = "train_step",
                 cache_dir: Optional[str] = None,
                 multi_program: bool = False):
        self._fn = fn
        self.label = label
        self.cache_dir = cache_dir
        self.multi_program = multi_program
        self.frozen = False
        self._compiled: Dict[Tuple, Callable] = {}
        self._last_sig: Optional[Tuple] = None
        self._disabled = False
        self.n_compiles = 0
        self.n_recompiles = 0
        self.compile_seconds_total = 0.0
        #: HLO-counted FLOPs for ONE step at the latest signature (None
        #: until the first capture, or when cost_analysis has no flops).
        self.hlo_flops_per_step: Optional[float] = None
        #: ... divided by the batch's token count (set when the batch
        #: carries an "inputs" leaf), for the HLO-measured MFU.
        self.hlo_flops_per_token: Optional[float] = None
        self.memory: Dict[str, int] = {}
        #: (start, end) of each capture on ``time.perf_counter``: where a
        #: caller's set-up timeline books the build it cannot see from
        #: outside (the trainer's first step)
        self.capture_stamps: List[Tuple[float, float]] = []

    # -- internals -------------------------------------------------------

    def _cache_entries(self) -> Optional[int]:
        if not self.cache_dir or not os.path.isdir(self.cache_dir):
            return None
        try:
            return sum(1 for n in os.listdir(self.cache_dir)
                       if n.endswith("-cache"))
        except OSError:
            return None

    @property
    def executables(self) -> List[Any]:
        """The compiled programs captured so far; ``.as_text()`` is the
        optimized HLO (chip_smoke.py looks for the kernels in it)."""
        return list(self._compiled.values())

    def freeze(self) -> None:
        """Close the legitimate-signature set (multi_program mode): the
        serving engine calls this after warming its prefill buckets and
        decode program — from here on, a new signature is a bucket miss."""
        self.frozen = True

    def _capture(self, sig: Tuple, *args) -> Callable:
        entries_before = self._cache_entries()
        t0 = time.perf_counter()
        compiled, stats = aot_compile(self._fn, *args)
        self.capture_stamps.append((t0, time.perf_counter()))
        entries_after = self._cache_entries()
        self.n_compiles += 1
        self.compile_seconds_total += stats["compile_seconds"]
        self.hlo_flops_per_step = stats.get("flops")
        self.memory = stats.get("memory", {})
        n_tokens = None
        try:
            batch = next(a for a in args
                         if isinstance(a, dict) and "inputs" in a)
            n_tokens = int(batch["inputs"].size)
        except (StopIteration, TypeError, KeyError, AttributeError):
            pass
        if n_tokens and self.hlo_flops_per_step:
            self.hlo_flops_per_token = self.hlo_flops_per_step / n_tokens
        event = dict(stats, label=self.label, n_compiles=self.n_compiles)
        if n_tokens:
            event["tokens_per_step"] = n_tokens
        capacity = device_hbm_capacity()
        if capacity and self.memory:
            event["hbm_capacity_bytes"] = capacity
            event["hbm_budget_frac"] = round(
                self.memory.get("total_bytes", 0) / capacity, 4)
        if entries_before is not None and entries_after is not None:
            event["cache_dir"] = self.cache_dir
            event["cache_entries"] = entries_after
            # a served-from-cache compile writes no new entries; count
            # deltas instead of guessing from timing
            event["cache_hit"] = (entries_after == entries_before
                                  and entries_before > 0)
        _notify_collectors("on_compile", self.label, sig, stats,
                           n_tokens=n_tokens)
        book_program(self.label, stats)
        sink = get_metrics()
        sink.event("compile", **event)
        sink.gauge("compile_seconds_total",
                   round(self.compile_seconds_total, 4))
        for name, val in self.memory.items():
            sink.gauge(f"hlo_{name}", val)
        logger.info(
            "%s compiled in %.2fs (HLO %s flops/step%s)", self.label,
            stats["compile_seconds"],
            f"{self.hlo_flops_per_step:.3g}" if self.hlo_flops_per_step
            else "n/a",
            f", temps {self.memory['temp_bytes'] / 1024**2:.0f} MiB"
            if "temp_bytes" in self.memory else "")
        return compiled

    # -- the step --------------------------------------------------------

    @property
    def __name__(self) -> str:
        # call-compatible includes introspection: tests (and tqdm-style
        # tooling) read the step function's name
        return getattr(self._fn, "__name__", self.label)

    def __call__(self, *args):
        if self._disabled:
            return self._fn(*args)
        key = tuple(fast_signature(a) for a in args)
        fn = self._compiled.get(key)
        if fn is None:
            # only a miss pays for the human-readable path-string
            # signature (the diff needs leaf names); steady-state steps
            # never build strings
            sig = tuple(tree_signature(a) for a in args)
            is_recompile = (self.frozen if self.multi_program
                            else self._last_sig is not None)
            if is_recompile:
                self.n_recompiles += 1
                diff = ([d for pair in zip(self._last_sig, sig)
                         for d in signature_diff(*pair)]
                        if self._last_sig is not None else [])
                sink = get_metrics()
                # a tree-wide drift (fsdp opt-state resharding, resume
                # dtype change) diffs every leaf — cap the serialized row
                sink.event("recompile", label=self.label,
                           n_recompiles=self.n_recompiles,
                           n_changed_leaves=len(diff), diff=diff[:50])
                _notify_collectors("on_recompile", self.label, diff)
                sink.gauge("recompile_count", self.n_recompiles)
                leaves = [d["leaf"] for d in diff]
                shown = "; ".join(leaves[:6]) + (
                    f"; … +{len(leaves) - 6} more" if len(leaves) > 6 else "")
                logger.warning(
                    "%s RECOMPILE #%d: argument signature changed (%s)",
                    self.label, self.n_recompiles, shown or "unknown leaf")
            try:
                fn = self._capture(sig, *args)
            except Exception as e:
                # telemetry must not kill the run: fall back to the plain
                # jit path (which will surface REAL trace errors itself)
                logger.warning(
                    "AOT compile capture failed for %s (%s: %s); compile "
                    "telemetry disabled for this step.", self.label,
                    type(e).__name__, e)
                get_metrics().event("compile_fallback", label=self.label,
                                    error=f"{type(e).__name__}: {e}")
                self._disabled = True
                return self._fn(*args)
            self._compiled[key] = fn
            self._last_sig = sig
        return fn(*args)


#: where compiled programs persist when nothing says otherwise: a fixed
#: path inside the checkout (git-ignored). The path is part of the cache
#: key's neighbourhood — a directory that moves never hits.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_compile_cache(cache_dir: Optional[str] = None) -> str:
    """Place JAX's persistent compilation cache — THE one rule, called
    first thing by every entry point (main, bench, fleet worker,
    chip_smoke): ``JAX_COMPILATION_CACHE_DIR``, when set, wins and no
    directory is set in code (jax reads the variable itself); otherwise
    ``cache_dir`` (--compile_cache_dir) or ``DEFAULT_CACHE_DIR``. A
    relaunch — the preemption-resume loop, the next run of the same
    command on the chip — then skips its XLA compiles. Thresholds are
    zeroed so every executable is eligible (default jax skips sub-second
    compiles, which would make smoke-test telemetry read as permanent
    misses). Returns the directory in use."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    keep_program_books()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    cache_dir = cache_dir or DEFAULT_CACHE_DIR
    if jax.config.jax_compilation_cache_dir != cache_dir:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        # a compile BEFORE the dir is set (a PRNG key, a device put)
        # memoizes "no cache" — drop that so the new dir takes effect
        compilation_cache.reset_cache()
        logger.info("Persistent compilation cache at %s", cache_dir)
    return cache_dir
