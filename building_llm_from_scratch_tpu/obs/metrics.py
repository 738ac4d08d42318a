"""Structured training telemetry: counters/gauges/timings and a JSONL sink.

The reference framework's only observability is per-module console logging
and a post-hoc loss plot (utils.py:171-191). Production TPU runs need the
numbers the systems literature treats as table stakes — per-step timing
breakdowns, MFU, HBM usage — as machine-readable ARTIFACTS, not grepped
logs. This module is the hub: one ``MetricLogger`` owns the JSONL file and
every other layer (trainer, resilience, checkpoint, retry, weight fetch)
reports through it.

JSONL schema (one JSON object per line, ``type`` discriminates):

  - ``header``  — exactly one, first line: run metadata (jax version,
    device kind/count, process count, mesh shape, model config, argv,
    parsed flags, schema_version).
  - ``metrics`` — per-cadence numbers: ``step`` plus free-form scalar
    fields (loss/lr/tok_s/mfu/step_time_s/memory gauges/...). ``step`` is
    monotonically increasing across rows.
  - ``health``  — per-cadence PER-LAYER-GROUP training-health arrays
    (obs/health.py): ``step``, ``groups`` (ordered names) and parallel
    ``grad_norm``/``param_norm``/``update_norm``/``update_ratio`` lists,
    plus ``first_nonfinite`` (group name or null). Separate from
    ``metrics`` so scalar-row consumers never see list-valued fields.
  - ``event``   — typed structured events (``event`` names the kind:
    checkpoint_save, checkpoint_fallback, preemption_stop, watchdog_halt,
    compile, recompile, retry, stall, ...), with free-form fields.
  - ``span``    — one closed wall-clock span (v3): ``name``, ``cat``,
    ``t0`` (unix seconds), ``dur_s``, optional nested ``children``
    (same shape, no further nesting) and correlation fields
    (``request_id``...). The serving engine emits one span row per
    request at its terminal state; ``obs/trace.py`` renders span rows
    (plus metric/event rows) as Chrome trace-event JSON for Perfetto.

One run = one file: if the path already holds a previous run's telemetry
(a ``--resume auto`` relaunch reuses the same command), the old file is
rotated aside (``.1``, ``.2``, ...) at first write, so every file keeps
the header-first / monotone-step invariants.

Coordinator-aware: by default only process 0 writes (the sink mirrors the
reference's rank-0 gating for artifacts). The module-level singleton
(``configure_metrics`` / ``get_metrics`` / ``emit_event``) lets deep layers
emit events without plumbing a logger handle through every call — when
nothing is configured, emission is a cheap no-op, so library use without a
run context costs nothing.

Writes are lock-guarded: the stall detector (obs/stall.py) emits from its
watcher thread.
"""

from __future__ import annotations

import bisect
import collections
import json
import os
import re
import sys
import threading
import time
from typing import Any, Dict, IO, List, Optional

from building_llm_from_scratch_tpu.obs.schema import SCHEMA_VERSION
from building_llm_from_scratch_tpu.utils.logging import setup_logger

logger = setup_logger(__name__)


def _is_coordinator() -> bool:
    """Lazy coordinator check that never *initializes* jax: metrics must be
    importable (and no-op usable) before ``jax.distributed.initialize``.
    One implementation, shared with the log-gating filter — the metrics
    sink and the console logs must never disagree about who writes."""
    from building_llm_from_scratch_tpu.utils.logging import (
        _coordinator_if_known,
    )

    return _coordinator_if_known()


def _jsonable(value: Any) -> Any:
    """Best-effort conversion to JSON-serializable values: numpy scalars
    become python scalars, unknown objects become their repr — a telemetry
    row must never crash the run it observes."""
    if value is None or isinstance(value, (bool, int, str)):
        return value
    if isinstance(value, float):
        # json rejects NaN/Inf under allow_nan=False; keep rows parseable
        import math

        return value if math.isfinite(value) else str(value)
    if isinstance(value, dict):
        return {str(k): _jsonable(v) for k, v in value.items()}
    if isinstance(value, (list, tuple)):
        return [_jsonable(v) for v in value]
    if getattr(value, "ndim", None):
        # numpy / jax arrays (health bundles): element-wise via tolist so
        # NaN/Inf entries still get the finite-only treatment above
        tolist = getattr(value, "tolist", None)
        if callable(tolist):
            try:
                return _jsonable(tolist())
            except Exception:
                pass
    item = getattr(value, "item", None)
    if callable(item):
        try:
            return _jsonable(item())
        except Exception:
            pass
    return repr(value)


# ---------------------------------------------------------------------------
# Serving-grade aggregation: fixed-bucket histograms + rolling SLO window
# ---------------------------------------------------------------------------

#: Default latency buckets (seconds) for TTFT/TPOT/e2e/queue-wait: log-ish
#: spacing from 1ms to 2min. Fixed buckets — unlike a reservoir deque, the
#: memory cost is O(buckets) forever and two scrapes of a long-running
#: server are COMPARABLE (Prometheus histogram semantics: cumulative
#: bucket counters, rate()-able).
LATENCY_BUCKETS_S = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                     0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0)


class Histogram:
    """Thread-safe fixed-bucket histogram (Prometheus semantics).

    ``bounds`` are the buckets' inclusive upper edges; an implicit +Inf
    bucket catches the tail. ``observe()`` is O(log buckets); state is
    cumulative and never forgets — this replaces the engine's bounded
    deque reservoirs, whose percentiles silently covered only the most
    recent 8192 requests of a long-running server.
    """

    def __init__(self, bounds=LATENCY_BUCKETS_S):
        self.bounds = tuple(sorted(float(b) for b in bounds))
        if not self.bounds:
            raise ValueError("histogram needs at least one bucket bound")
        self._lock = threading.Lock()
        self._counts = [0] * (len(self.bounds) + 1)   # guarded-by: _lock
        self.count = 0                                # guarded-by: _lock
        self.sum = 0.0                                # guarded-by: _lock

    def observe(self, value: float) -> None:
        value = float(value)
        i = bisect.bisect_left(self.bounds, value)
        with self._lock:
            self._counts[i] += 1
            self.count += 1
            self.sum += value

    def __len__(self) -> int:                 # observations, not buckets
        with self._lock:
            return self.count

    def snapshot(self) -> Dict[str, Any]:
        """{"buckets": [(le, cumulative_count), ..., ("+Inf", n)],
        "count": n, "sum": s} — a consistent point-in-time view."""
        with self._lock:
            counts = list(self._counts)
            total, s = self.count, self.sum
        cum, out = 0, []
        for le, c in zip(self.bounds, counts):
            cum += c
            out.append((le, cum))
        out.append(("+Inf", total))
        return {"buckets": out, "count": total, "sum": s}

    def percentile(self, p: float) -> Optional[float]:
        """Estimated p-th percentile: linear interpolation inside the
        target bucket (Prometheus ``histogram_quantile`` semantics; the
        +Inf bucket clamps to the largest finite bound). None when empty.
        """
        with self._lock:
            counts = list(self._counts)
            total = self.count
        if total == 0:
            return None
        rank = (p / 100.0) * total
        cum = 0
        for i, c in enumerate(counts):
            if c == 0:
                continue
            if cum + c >= rank:
                if i >= len(self.bounds):     # +Inf bucket: clamp
                    return self.bounds[-1]
                lo = self.bounds[i - 1] if i > 0 else 0.0
                hi = self.bounds[i]
                frac = (rank - cum) / c
                return lo + (hi - lo) * frac
            cum += c
        return self.bounds[-1]

    def percentiles(self, ps=(50, 95, 99)) -> Dict[str, float]:
        out = {}
        for p in ps:
            v = self.percentile(p)
            if v is not None:
                out[f"p{p}"] = round(v, 6)
        return out


class RollingRatio:
    """Rolling-window hit/miss ratio over wall time (SLO burn rate).

    Time is chopped into ``n_buckets`` sub-windows of the last
    ``window_s`` seconds; ``observe(miss)`` lands in the current
    sub-window and expired sub-windows are dropped lazily — so
    ``ratio()`` always answers "what fraction of deadline-carrying
    requests missed over the last window", which is the number an
    SLO-aware router alerts and routes on. O(n_buckets) memory forever.
    """

    def __init__(self, window_s: float = 300.0, n_buckets: int = 30):
        if window_s <= 0 or n_buckets < 1:
            raise ValueError("window_s > 0 and n_buckets >= 1 required")
        self.window_s = float(window_s)
        self.bucket_s = self.window_s / int(n_buckets)
        self._lock = threading.Lock()
        # bucket index -> [total, misses]
        self._buckets: Dict[int, list] = {}   # guarded-by: _lock

    # holds: _lock
    def _expire(self, now: float) -> None:
        horizon = now - self.window_s
        dead = [k for k in self._buckets
                if (k + 1) * self.bucket_s <= horizon]
        for k in dead:
            del self._buckets[k]

    def observe(self, miss: bool, now: Optional[float] = None) -> None:
        now = time.monotonic() if now is None else now
        k = int(now // self.bucket_s)
        with self._lock:
            self._expire(now)
            b = self._buckets.setdefault(k, [0, 0])
            b[0] += 1
            if miss:
                b[1] += 1

    def counts(self, now: Optional[float] = None) -> tuple:
        """(total, misses) inside the current window."""
        now = time.monotonic() if now is None else now
        with self._lock:
            self._expire(now)
            total = sum(b[0] for b in self._buckets.values())
            misses = sum(b[1] for b in self._buckets.values())
        return total, misses

    def ratio(self, now: Optional[float] = None) -> Optional[float]:
        total, misses = self.counts(now)
        if total == 0:
            return None
        return misses / total


# ---------------------------------------------------------------------------
# Prometheus text exposition (version 0.0.4; no client library needed)
# ---------------------------------------------------------------------------

def _prom_name(name: str) -> str:
    """Sanitize to the Prometheus metric-name charset."""
    return re.sub(r"[^a-zA-Z0-9_:]", "_", name)


def _prom_series(prefix: str, name: str, counter: bool = False) -> str:
    """Series name for one counter/gauge key. A key may carry a label
    set — ``adapter_requests_finished{adapter="x"}`` — in which case only
    the metric-name part is sanitized (and the counter ``_total`` suffix
    lands BEFORE the braces, per exposition-format grammar)."""
    base, sep, labels = name.partition("{")
    n = _prom_name(prefix + base)
    if counter and not n.endswith("_total"):
        n += "_total"
    return n + sep + labels


def render_prometheus(counters: Dict[str, float],
                      gauges: Dict[str, float],
                      histograms: Dict[str, "Histogram"],
                      prefix: str = "bllm_") -> str:
    """Render counters/gauges/histograms as Prometheus text exposition
    (``GET /metrics`` body). Counters get a ``_total`` suffix; histogram
    series follow the ``_bucket{le=}``/``_sum``/``_count`` convention, so
    ``histogram_quantile()`` works on them unmodified."""
    lines = []
    typed: set = set()

    def emit(name: str, v, kind: str, counter: bool) -> None:
        n = _prom_series(prefix, name, counter=counter)
        bare = n.partition("{")[0]
        # one TYPE line per metric name, even when labeled keys produce
        # several series of it (exposition-format requirement)
        if bare not in typed:
            typed.add(bare)
            lines.append(f"# TYPE {bare} {kind}")
        lines.append(f"{n} {v}")

    for name in sorted(counters):
        v = counters[name]
        if not isinstance(v, (int, float)):
            continue
        emit(name, v, "counter", counter=True)
    for name in sorted(gauges):
        v = gauges[name]
        if not isinstance(v, (int, float)):
            continue
        emit(name, v, "gauge", counter=False)
    for name in sorted(histograms):
        snap = histograms[name].snapshot()
        # histogram keys may carry a label set too (the replica router
        # re-exports each replica's histograms as ttft_seconds{replica=
        # "i"}): labels merge INSIDE the _bucket/_sum/_count series per
        # exposition grammar — ..._bucket{replica="i",le="0.1"}
        base, _sep, labels = name.partition("{")
        labels = labels[:-1] if labels else ""
        n = _prom_name(prefix + base)
        if n not in typed:
            typed.add(n)
            lines.append(f"# TYPE {n} histogram")
        pre = labels + "," if labels else ""
        suffix = "{" + labels + "}" if labels else ""
        for le, cum in snap["buckets"]:
            le_txt = "+Inf" if le == "+Inf" else repr(float(le))
            lines.append(f'{n}_bucket{{{pre}le="{le_txt}"}} {cum}')
        lines.append(f"{n}_sum{suffix} {snap['sum']}")
        lines.append(f"{n}_count{suffix} {snap['count']}")
    return "\n".join(lines) + "\n"


#: Newest rows kept in memory per kind, file or no file (``recent``), for
#: the kinds that have a reader in the process: ``RECENT_RECORDS`` of a
#: memory-only kind (``keep_record``: the engine's ``tick`` records, a dozen
#: numbers each, eight minutes of 60 ms ticks; one ``program`` record a
#: program the process builds and one ``setup`` record an engine or
#: trainer: obs/schema.py lists the fields of all three) and
#: ``RECENT_FILE_ROWS`` of
#: the JSONL row types ``RECENT_FILE_KINDS`` (larger: a cadence row carries
#: every counter and gauge). ``event`` and ``health`` rows go to the file
#: only.
RECENT_RECORDS = 8192
RECENT_FILE_ROWS = 2048
RECENT_FILE_KINDS = ("span", "metrics")


class MetricLogger:
    """Counters/gauges/timings plus a typed JSONL sink.

    ``jsonl_path=None`` keeps the in-memory aggregation (counters survive
    for tests/inspection) but writes nothing. All writes go through one
    lock; rows are flushed immediately — a preempted run keeps every row
    up to its last completed cadence.

    Whether or not a file is set, the newest rows of each kind stay in a
    bounded buffer that ``recent(kind)`` returns: what a benchmark reads
    back when its window closes, and what ``/healthz`` and the stall
    detector show of the ticks before a hang (``obs/stall.last_ticks``). The buffer holds
    JSON-plain values only, never a reference into the program's state.
    """

    def __init__(self, jsonl_path: Optional[str] = None,
                 coordinator_only: bool = True, append: bool = False):
        self.jsonl_path = jsonl_path
        self.coordinator_only = coordinator_only
        # append=True: a restarted process APPENDS to the existing file
        # instead of rotating it aside — the fleet-worker convention,
        # where one file accumulates one header per incarnation and the
        # renderer splits on headers (summarize_metrics.split_incarnations)
        self.append = append
        # REENTRANT: GracefulStopper's signal handler emits an event, and
        # the signal can land while THIS thread already holds the lock
        # inside a write — a plain Lock would self-deadlock. Reentry is
        # safe because every row is appended as one complete newline-
        # terminated write, so an interleaved handler row never splits a
        # line.
        self._lock = threading.RLock()
        self.counters: Dict[str, float] = {}      # guarded-by: _lock
        self.gauges: Dict[str, float] = {}        # guarded-by: _lock
        self._timings: Dict[str, float] = {}      # guarded-by: _lock
        self._file: Optional[IO[str]] = None      # guarded-by: _lock
        self._closed = False                      # guarded-by: _lock
        self._header_written = False              # guarded-by: _lock
        # rows emitted before the header (build-time fetch/retry events —
        # the run metadata needs the built components) are buffered and
        # flushed right after it, keeping the header the first line
        self._pre_header: list = []               # guarded-by: _lock
        self._last_step = -1                      # guarded-by: _lock
        self._recent: Dict[str, collections.deque] = {}  # guarded-by: _lock

    # -- aggregation -----------------------------------------------------

    def count(self, name: str, n: float = 1) -> None:
        """Monotonic counter (e.g. retries, checkpoints written)."""
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + n

    def gauge(self, name: str, value: float) -> None:
        """Last-value-wins gauge (e.g. bytes_in_use)."""
        with self._lock:
            self.gauges[name] = value

    def timing(self, name: str, seconds: float) -> None:
        """Accumulating timing bucket; drained into the next metrics row."""
        with self._lock:
            self._timings[name] = self._timings.get(name, 0.0) + seconds

    # -- sink ------------------------------------------------------------

    # holds: _lock
    def _writable(self) -> bool:
        # a closed sink stays closed: a late write (stall-detector thread
        # firing during teardown) must not reopen the path — that would
        # rotate the COMPLETED run's artifact aside for one stray row
        if self.jsonl_path is None or self._closed:
            return False
        return not self.coordinator_only or _is_coordinator()

    def keep_record(self, kind: str, row: Dict[str, Any]) -> None:
        """Keep one memory-only record of ``kind`` (never written to the
        file). The caller hands over a dict of plain numbers and strings
        that it does not touch again."""
        with self._lock:
            self._keep(kind, row, RECENT_RECORDS)

    # holds: _lock
    def _keep(self, kind: str, row: Dict[str, Any], maxlen: int) -> None:
        buf = self._recent.get(kind)
        if buf is None:
            buf = self._recent[kind] = collections.deque(maxlen=maxlen)
        buf.append(row)

    def recent(self, kind: str) -> List[Dict[str, Any]]:
        """The newest rows of ``kind``, oldest first: a JSONL row type of
        ``RECENT_FILE_KINDS`` or a ``keep_record`` kind (``tick``,
        ``program``, ``setup``)."""
        with self._lock:
            return list(self._recent.get(kind, ()))

    def _write_row(self, row: Dict[str, Any]) -> None:
        """Keep one row in memory and, where a file is set, append it.
        Never raises: telemetry failure must not take down the training
        loop it observes."""
        row = _jsonable(row)
        try:
            with self._lock:
                if row["type"] in RECENT_FILE_KINDS:
                    self._keep(row["type"], row, RECENT_FILE_ROWS)
                # writability is decided under the lock: a close() racing
                # this write either lands before (row dropped) or after
                # (row flushed) — never between check and write
                if not self._writable():
                    return
                if not self._header_written and row.get("type") != "header":
                    self._pre_header.append(row)
                    return
                if self._file is None:
                    d = os.path.dirname(self.jsonl_path)
                    if d:
                        os.makedirs(d, exist_ok=True)
                    # one run = one file: a --resume auto relaunch reuses
                    # the same path, and appending would put a second
                    # header mid-file and restart the monotone step
                    # sequence. Rotate the previous run's file aside
                    # (.1, .2, ...) instead of truncating it — the killed
                    # run's telemetry is exactly what a postmortem needs.
                    # append mode opts out: restarted fleet workers stack
                    # incarnations (header-delimited) in ONE file, so the
                    # victim's last rows and its successor's share a path.
                    if not self.append and os.path.exists(
                            self.jsonl_path) and os.path.getsize(
                            self.jsonl_path) > 0:
                        n = 1
                        while os.path.exists(f"{self.jsonl_path}.{n}"):
                            n += 1
                        os.rename(self.jsonl_path, f"{self.jsonl_path}.{n}")
                    self._file = open(self.jsonl_path, "a")
                self._file.write(json.dumps(row) + "\n")
                self._file.flush()
        except OSError as e:
            logger.warning("Metrics sink write failed (%s); row dropped.", e)

    def write_header(self, **metadata: Any) -> None:
        row = {"type": "header", "time": time.time(),
               "schema_version": SCHEMA_VERSION}
        row.update(metadata)
        with self._lock:
            self._header_written = True
            buffered, self._pre_header = self._pre_header, []
        self._write_row(row)
        for b in buffered:
            self._write_row(b)

    def log_metrics(self, step: int, monotonic: bool = True,
                    **values: Any) -> None:
        """One ``metrics`` row; merges and drains the timing buckets and
        attaches current counters/gauges. ``monotonic=False`` skips the
        step-regression warning — fleet-serving replicas interleave
        their per-engine tick counters into one sink by design."""
        with self._lock:
            timings = {f"{k}_s": round(v, 6)
                       for k, v in self._timings.items()}
            self._timings.clear()
            extra = dict(self.counters)
            extra.update(self.gauges)
        row = {"type": "metrics", "time": time.time(), "step": int(step)}
        row.update(timings)
        row.update(extra)
        row.update(values)
        with self._lock:
            if monotonic and step < self._last_step:
                logger.warning("Metrics row step went backwards (%d < %d)",
                               step, self._last_step)
            self._last_step = max(self._last_step, int(step))
        self._write_row(row)

    def log_health(self, step: int, groups, **arrays: Any) -> None:
        """One ``health`` row: ordered group names + parallel per-group
        arrays (obs/health.py bundle). List-valued by design — kept out of
        the scalar ``metrics`` rows so existing consumers stay flat."""
        row = {"type": "health", "time": time.time(), "step": int(step),
               "groups": list(groups)}
        row.update(arrays)
        self._write_row(row)

    def log_span(self, name: str, t0: float, dur_s: float,
                 cat: str = "span", children=None, **fields: Any) -> None:
        """One closed wall-clock ``span`` row: ``t0`` is unix seconds,
        ``dur_s`` its duration; ``children`` is an optional list of
        ``{"name", "t0", "dur_s"}`` sub-spans (one level — the serving
        request tree is root + phases). Correlation keys (``request_id``)
        ride as free-form fields; ``obs/trace.py`` joins them."""
        row: Dict[str, Any] = {"type": "span", "time": time.time(),
                               "name": name, "cat": cat,
                               "t0": round(float(t0), 6),
                               "dur_s": round(float(dur_s), 6)}
        if children:
            # clamp children inside the ROUNDED root: rounding t0/dur_s
            # independently can push a child's end past the root's by up
            # to ~1.5us, and consumers (Perfetto nesting, the span tests)
            # rely on strict containment
            root_t0 = row["t0"]
            root_end = root_t0 + row["dur_s"]
            kids = []
            for c in children:
                ct0 = min(max(round(float(c["t0"]), 6), root_t0), root_end)
                cdur = max(min(round(float(c["dur_s"]), 6),
                               root_end - ct0), 0.0)
                kids.append({"name": c["name"], "t0": ct0, "dur_s": cdur})
            row["children"] = kids
        row.update(fields)
        self._write_row(row)

    def event(self, kind: str, step: Optional[int] = None,
              **fields: Any) -> None:
        """One typed ``event`` row (also bumps the ``event:<kind>``
        counter, so unconfigured library use still aggregates)."""
        self.count(f"event:{kind}")
        row = {"type": "event", "time": time.time(), "event": kind}
        if step is not None:
            row["step"] = int(step)
        row.update(fields)
        self._write_row(row)

    def close(self) -> None:
        # a run that dies before its header still keeps its buffered rows:
        # a headerless telemetry file beats a silently empty one. The
        # buffer check happens under the lock (two racing close() calls
        # must not both claim the buffer); the flush itself re-enters
        # _write_row, which the RLock permits.
        with self._lock:
            buffered, self._pre_header = self._pre_header, []
            if buffered:
                self._header_written = True
        for b in buffered:
            self._write_row(b)
        with self._lock:
            if self._file is not None:
                self._file.close()
                self._file = None
            self._closed = True


# ---------------------------------------------------------------------------
# Module-level singleton: deep layers emit without plumbing
# ---------------------------------------------------------------------------

_global_logger = MetricLogger(None)
_atexit_registered = False


def _close_global_at_exit() -> None:
    # closes whatever logger is CURRENT at interpreter exit — registered
    # once, so repeated configure_metrics calls (tests, multiple main()
    # runs in one process) neither stack callbacks nor pin old loggers
    _global_logger.close()


def configure_metrics(jsonl_path: Optional[str],
                      run_metadata: Optional[Dict[str, Any]] = None,
                      append: bool = False) -> MetricLogger:
    """Install the process-global MetricLogger (closing any previous one).
    With ``run_metadata`` the header is written immediately; without it,
    rows buffer until the caller's ``write_header`` (main.py configures
    before component build so fetch/retry events are captured, then writes
    the header once mesh + model metadata exist). ``jsonl_path=None``
    resets to the no-op sink (tests use this to isolate). ``append=True``
    appends to an existing file instead of rotating it (fleet workers:
    one file per replica, one header per incarnation)."""
    global _global_logger, _atexit_registered
    _global_logger.close()
    _global_logger = MetricLogger(jsonl_path, append=append)
    if jsonl_path is not None and not _atexit_registered:
        # flush-at-exit makes the pre-header buffering promise real: if
        # the run dies before its header (e.g. build_components exhausts
        # its fetch retries and raises), the buffered retry/fetch events
        # still land in a headerless file instead of vanishing. close()
        # is idempotent, so the normal path is unaffected.
        import atexit

        atexit.register(_close_global_at_exit)
        _atexit_registered = True
    if jsonl_path is not None and run_metadata is not None:
        _global_logger.write_header(**run_metadata)
    return _global_logger


def get_metrics() -> MetricLogger:
    return _global_logger


def emit_event(kind: str, step: Optional[int] = None, **fields: Any) -> None:
    """Fire-and-forget structured event through the global logger. Safe to
    call from any layer at any time (no-op sink when unconfigured)."""
    _global_logger.event(kind, step=step, **fields)


def run_metadata(args=None, cfg=None, plan=None) -> Dict[str, Any]:
    """Assemble the header row's run metadata: jax version, device
    kind/count, process count, mesh shape, model config, argv, flags.
    Call AFTER ``initialize_distributed`` so the distributed view is real.
    """
    import dataclasses

    import jax

    devices = jax.devices()
    meta: Dict[str, Any] = {
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "device_kind": devices[0].device_kind if devices else "unknown",
        "device_count": len(devices),
        "local_device_count": jax.local_device_count(),
        "process_count": jax.process_count(),
        "process_index": jax.process_index(),
        "argv": list(sys.argv),
    }
    if plan is not None and getattr(plan, "mesh", None) is not None:
        meta["mesh_shape"] = {str(k): int(v)
                              for k, v in plan.mesh.shape.items()}
    else:
        meta["mesh_shape"] = None
    if cfg is not None:
        meta["model"] = dataclasses.asdict(cfg)
    if args is not None:
        meta["flags"] = dict(vars(args))
    return meta
