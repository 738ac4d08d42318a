"""Per-host stall detector: dump thread stacks + HBM stats when the step
loop stops making progress.

The failure mode this targets: one host of a pod slice wedges inside a
collective (a peer died, a DMA hung, the data pipeline deadlocked) and the
job sits silent for hours burning reserved capacity. The ONLY safe
diagnostic at that point is strictly host-local — any cross-host collective
would itself hang behind the wedged one — so this watcher:

  - runs a daemon thread per host, armed by ``Trainer`` heartbeats
    (``notify_step`` once per step-loop iteration);
  - fires when no heartbeat lands within ``timeout`` seconds, or within
    ``factor`` x the rolling median step interval once enough history
    exists (whichever is SOONER — a run stepping at 100ms that goes quiet
    for minutes is stalled long before a 600s timeout). The adaptive
    trigger is floored at ``median_floor`` (default 30s): heartbeats come
    once per step-LOOP iteration, and an iteration legitimately stretches
    far past 10x the median step when cadence work runs (first-compile
    eval, checkpoint saves) — without the floor a fast-stepping run
    false-fires on its first eval;
  - on firing, logs every Python thread's stack (``sys._current_frames``)
    and live ``device.memory_stats()`` for the local devices and, in a
    serving process, the engine's last tick records (``last_ticks``: which
    phase of which tick the time went into before the hang), and emits
    a structured ``stall`` event — all local, no collectives;
  - never kills anything: it is a flight recorder, not a watchdog. It
    re-arms after the next heartbeat, so an intermittent stall produces
    one dump per episode instead of a dump per poll tick.

Opt-in via ``--stall_timeout N`` (seconds; 0 = off). The first interval
gets ``first_grace`` x the threshold: the first step pays jit tracing +
compilation, which on big models legitimately takes minutes.
"""

from __future__ import annotations

import sys
import threading
import time
import traceback
from typing import List, Optional

from building_llm_from_scratch_tpu.utils.logging import setup_logger

logger = setup_logger(__name__)


def format_all_stacks() -> str:
    """Every live Python thread's stack as one readable block."""
    names = {t.ident: t.name for t in threading.enumerate()}
    parts: List[str] = []
    for ident, frame in sys._current_frames().items():
        name = names.get(ident, "unknown")
        parts.append(f"--- Thread {name} (ident {ident}) ---")
        parts.append("".join(traceback.format_stack(frame)).rstrip())
    return "\n".join(parts)


#: Tick records a dump and ``/healthz`` show: the ticks before the hang.
LAST_TICKS = 8


def last_ticks(replica: Optional[int] = None) -> List[dict]:
    """The serving engine's newest ``LAST_TICKS`` tick records (of
    ``replica``, where a fleet shares the process), from the metrics hub,
    each cut to what an operator reads: which tick, how long ago it ended,
    its wall and phases in ms, the rows that decoded of the program's, the
    requests it admitted and the queue it started behind. A wedged tick
    books no record: these are the ones before it. Empty in a process
    that serves nothing."""
    from building_llm_from_scratch_tpu.obs.metrics import get_metrics

    now = time.perf_counter()
    ticks = [t for t in get_metrics().recent("tick")
             if replica is None or t.get("replica") == replica]
    return [{"tick": t["tick"], "ended_s_ago": round(now - t["t1"], 3),
             "wall_ms": round(1e3 * (t["t1"] - t["t0"]), 3),
             "phases_ms": {ph: round(1e3 * dt, 3)
                           for ph, dt in t["phases"].items()},
             "rows": t["rows"], "n_slots": t["n_slots"],
             "admitted": t["admitted"], "queue_depth": t["queue_depth"],
             **({"replica": t["replica"]} if "replica" in t else {})}
            for t in ticks[-LAST_TICKS:]]


def _device_memory_report() -> dict:
    """Live HBM stats per local device (best-effort, strictly local)."""
    try:
        import jax

        from building_llm_from_scratch_tpu.utils.memory import (
            device_memory_stats,
        )

        return {str(d): device_memory_stats(d) for d in jax.local_devices()}
    except Exception as e:
        return {"error": repr(e)}


class StallDetector:
    """See module docstring. Thread-safe: heartbeats come from the trainer
    thread, checks run on the watcher thread."""

    def __init__(self, timeout: float, factor: float = 10.0,
                 poll_interval: float = 0.25, first_grace: float = 5.0,
                 median_floor: float = 30.0, history: int = 64,
                 on_stall=None):
        if timeout <= 0:
            raise ValueError(f"timeout must be > 0 seconds, got {timeout}")
        self.timeout = float(timeout)
        self.factor = float(factor)
        self.median_floor = float(median_floor)
        self.poll_interval = float(poll_interval)
        self.first_grace = float(first_grace)
        self.on_stall = on_stall          # test hook: fn(elapsed, threshold)
        self.stall_count = 0
        self._history_max = history
        self._intervals: List[float] = []
        self._last: Optional[float] = None
        self._fired_for_current_gap = False
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._lock = threading.Lock()

    # -- heartbeat (trainer thread) --------------------------------------

    def notify_step(self) -> None:
        now = time.monotonic()
        with self._lock:
            if self._last is not None:
                self._intervals.append(now - self._last)
                if len(self._intervals) > self._history_max:
                    del self._intervals[0]
            self._last = now
            self._fired_for_current_gap = False

    # -- watcher ---------------------------------------------------------

    def threshold(self) -> float:
        """Current firing threshold in seconds."""
        with self._lock:
            intervals = list(self._intervals)
            armed = self._last is not None
        thr = self.timeout
        if len(intervals) >= 8:
            srt = sorted(intervals)
            median = srt[len(srt) // 2]
            # adaptive trigger, floored (see module docstring: cadence
            # work inside one loop iteration legitimately dwarfs the
            # median step interval)
            thr = min(thr, max(self.factor * median, self.median_floor))
        if armed and not intervals:
            thr *= self.first_grace     # first step pays compilation
        return thr

    def _check(self) -> None:
        with self._lock:
            last = self._last
            fired = self._fired_for_current_gap
        if last is None or fired:
            return
        elapsed = time.monotonic() - last
        thr = self.threshold()
        if elapsed < thr:
            return
        with self._lock:
            if self._last != last:
                # a heartbeat landed between the read above and here: the
                # gap we measured just ended, and marking the NEW gap as
                # fired would permanently silence the detector for the
                # very intermittent-stall pattern it exists to catch
                return
            self._fired_for_current_gap = True
        self.stall_count += 1
        self._dump(elapsed, thr)
        if self.on_stall is not None:
            try:
                self.on_stall(elapsed, thr)
            except Exception:
                logger.exception("stall callback failed")

    def _dump(self, elapsed: float, thr: float) -> None:
        mem = _device_memory_report()
        logger.error(
            "STALL: no train step completed in %.1fs (threshold %.1fs). "
            "Dumping all Python thread stacks (host-local; no collectives):"
            "\n%s\nDevice memory stats: %s\nLast serving ticks: %s",
            elapsed, thr, format_all_stacks(), mem, last_ticks())
        from building_llm_from_scratch_tpu.obs.metrics import emit_event

        emit_event("stall", elapsed_s=round(elapsed, 3),
                   threshold_s=round(thr, 3), memory=mem)

    def _run(self) -> None:
        while not self._stop.wait(self.poll_interval):
            try:
                self._check()
            except Exception:
                # the flight recorder must never crash the run
                logger.exception("stall detector check failed")

    # -- lifecycle -------------------------------------------------------

    def start(self) -> "StallDetector":
        if self._thread is not None:
            return self
        with self._lock:
            if self._last is None:
                # arm NOW: a run that wedges in its very first step (first
                # batch's data pipeline, first collective, jit compile) is
                # the headline failure mode and must still dump — the
                # first monitored gap simply gets first_grace x the
                # threshold (threshold() applies it while no step interval
                # exists yet) to cover legitimate compilation time
                self._last = time.monotonic()
        self._stop.clear()
        self._thread = threading.Thread(target=self._run,
                                        name="stall-detector", daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5 * self.poll_interval + 1)
            self._thread = None

    def __enter__(self) -> "StallDetector":
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False
