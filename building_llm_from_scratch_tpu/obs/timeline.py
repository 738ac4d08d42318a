"""Per-step wall-clock breakdown + profiler trace annotation, for the
trainer's step loop and the serving engine's tick alike.

Two jobs, one API:

  1. **Accounting** — the trainer's cadence window needs to know where the
     wall-clock went: waiting on the data pipeline (``data_wait``),
     dispatching the jitted step (``dispatch`` — NOT execution: steps are
     async), blocking host fetches (``host_fetch``), and the non-step
     cadence work (``eval``/``sample``/``checkpoint``) whose time must be
     EXCLUDED from tok/s so the reported throughput measures training, not
     sampling (ISSUE-2 satellite: the old ``t_tokens/t_start`` window
     deflated tok/s whenever a sample or save fired inside it).
  2. **Navigability** — the same spans become ``jax.profiler``
     ``TraceAnnotation`` blocks, and each train step or engine tick gets
     a ``StepTraceAnnotation``, so an xplane capture shows named regions
     instead of an undifferentiated op soup, and a gap in the device's
     work can be named by what the host was doing in it.

Annotations are no-ops when no trace is active (jax makes them ~free), so
the spans stay on permanently — they are NOT gated on ``--profile``.
"""

from __future__ import annotations

import time
from typing import Dict, Mapping, Optional

import jax

#: Segments excluded from the throughput window: host-side cadence work
#: that is not training (the step loop is paused, not slow).
NON_STEP_SEGMENTS = ("eval", "sample", "checkpoint")

#: Named ``jax.profiler.TraceAnnotation`` span, for a stretch of a loop's
#: life that belongs to no segment (the engine loop between two ticks), and
#: the ``StepTraceAnnotation`` that holds one step of a loop.
annotate = jax.profiler.TraceAnnotation
annotate_step = jax.profiler.StepTraceAnnotation


class _Span:
    """One open span of a ``StepTimeline`` (see ``StepTimeline.span``)."""

    __slots__ = ("tl", "segment", "ann", "t0", "closed0")

    def __init__(self, tl: "StepTimeline", segment: str, ann):
        self.tl, self.segment, self.ann = tl, segment, ann

    def __enter__(self) -> None:
        self.ann.__enter__()
        self.closed0 = self.tl._closed
        self.t0 = time.perf_counter()

    def __exit__(self, *exc) -> None:
        tl = self.tl
        now = time.perf_counter()
        # self time: the spans that closed inside this one have booked
        # exactly the part of this interval their own names cover
        own = max(now - self.t0 - (tl._closed - self.closed0), 0.0)
        tl._closed += own
        tl.seconds[self.segment] = tl.seconds.get(self.segment, 0.0) + own
        tl.ends[self.segment] = now
        self.ann.__exit__(*exc)


class StepTimeline:
    """Accumulates named wall-clock segments between ``drain()`` calls:
    the one span primitive of both loops. The trainer drains once per
    logging cadence, the serving engine once per tick; the returned dict
    is the breakdown in seconds. Spans double as profiler trace
    annotations (see module docstring).

    ``span_names`` maps a segment to its name in the trace where that is
    not the segment's own (``obs/schema.py`` ``TICK_SPANS``).
    Spans nest: a segment's seconds are its SELF time, its duration less
    what the spans inside it booked, so segments never count an interval
    twice and their sum cannot pass the wall. One thread at a time.
    """

    def __init__(self, span_names: Optional[Mapping[str, str]] = None):
        self.span_names = span_names or {}
        self.seconds: Dict[str, float] = {}
        #: ``time.perf_counter`` at which each segment last closed
        self.ends: Dict[str, float] = {}
        self.steps_in_window = 0
        self._closed = 0.0      # self seconds of every span closed so far

    def span(self, segment: str) -> _Span:
        """Time a block into ``segment`` and annotate it in the trace."""
        return _Span(self, segment,
                     annotate(self.span_names.get(segment, segment)))

    def step_span(self, step_num: int) -> _Span:
        """One train step: ``StepTraceAnnotation`` (so xplane groups ops
        per step) + ``dispatch`` accounting. The measured time is DISPATCH
        latency — jitted steps return before the device finishes; the
        execution catch-up is visible as ``host_fetch`` at cadence. (The
        engine's tick opens its own ``StepTraceAnnotation`` and books its
        wall itself, from the spans inside it.)"""
        self.steps_in_window += 1
        return _Span(self, "dispatch",
                     annotate_step("train", step_num=step_num))

    def drain(self) -> Dict[str, float]:
        """Return and reset the current window's breakdown. The dict also
        carries ``steps`` (steps accounted in the window)."""
        out, self.seconds = self.seconds, {}
        out["steps"] = self.steps_in_window
        self.ends = {}
        self.steps_in_window = 0
        return out


def window_stats(window: Dict[str, float], elapsed: float,
                 tokens: int) -> Dict[str, Optional[float]]:
    """Throughput/step-time numbers for one drained cadence window.

    ``elapsed`` is the full wall-clock since the window opened; the
    non-step segments (eval/sample/checkpoint) are subtracted so tok/s and
    step_time measure the training loop only.
    """
    non_step = sum(window.get(k, 0.0) for k in NON_STEP_SEGMENTS)
    step_seconds = max(elapsed - non_step, 0.0)
    steps = int(window.get("steps", 0))
    return {
        "tok_s": tokens / step_seconds if step_seconds > 0 else 0.0,
        "step_time_s": step_seconds / steps if steps else None,
        "step_seconds": step_seconds,
        "non_step_seconds": non_step,
    }
