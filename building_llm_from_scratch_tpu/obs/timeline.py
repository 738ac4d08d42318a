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

import collections
import functools
import time
from typing import Any, Callable, Dict, List, Mapping, Optional

import jax

from building_llm_from_scratch_tpu.obs.compile import keep_program_books
from building_llm_from_scratch_tpu.obs.metrics import get_metrics

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


class _BookedSpan(_Span):
    """A span of a ``SetupTimeline``: the same self-time accounting, and
    one entry of its own in the timeline's books."""

    __slots__ = ("entry",)

    def __enter__(self) -> None:
        tl = self.tl
        self.entry = {"name": self.segment, "depth": tl._depth}
        tl.spans.append(self.entry)
        tl._depth += 1
        super().__enter__()

    def __exit__(self, *exc) -> None:
        tl = self.tl
        closed = tl._closed
        super().__exit__(*exc)
        tl._depth -= 1
        self.entry.update(t0=self.t0, dur_s=tl.ends[self.segment] - self.t0,
                          self_s=tl._closed - closed)


class SetupTimeline(StepTimeline):
    """The books of one engine's or trainer's set-up: a ``StepTimeline``
    that also keeps every span it closes (name, start, duration, self
    seconds, depth), in the order they opened. Set-up runs once, so nothing
    is drained between spans; ``record()`` is the whole of it, as
    ``obs/schema.py`` ``SETUP_RECORD_FIELDS`` lists it. One thread at a
    time, like its base."""

    def __init__(self):
        super().__init__()
        self.spans: List[Dict[str, Any]] = []
        self._depth = 0
        #: one reading of both clocks, to say a span's start in unix time
        self._anchor = (time.perf_counter(), time.time())

    def span(self, segment: str) -> _BookedSpan:
        return _BookedSpan(self, segment, annotate(segment))

    def book(self, segment: str, t0: float, t1: float) -> None:
        """Book a top-level span from its two ``perf_counter`` stamps: one
        that no single block of code holds (the trainer's first runs end in
        another method than they begin in)."""
        self.spans.append({"name": segment, "depth": 0, "t0": t0,
                           "dur_s": t1 - t0, "self_s": t1 - t0})

    def unix(self, t: float) -> float:
        """A ``perf_counter`` stamp of this process in unix seconds."""
        return self._anchor[1] + (t - self._anchor[0])

    def record(self, source: str) -> Optional[Dict[str, Any]]:
        """The closed spans as one record (None before the first closes).
        The root runs from the first span's start to the last one's end;
        its self seconds are what lies between the phases."""
        spans = [dict(s) for s in self.spans if "dur_s" in s]
        if not spans:
            return None
        t0 = spans[0]["t0"]
        t_end = max(s["t0"] + s["dur_s"] for s in spans)
        phases = sum(s["dur_s"] for s in spans if s["depth"] == 0)
        return {"source": source, "t0": t0, "t_end": t_end,
                "time": self.unix(t0), "wall_s": t_end - t0,
                "self_s": max(t_end - t0 - phases, 0.0), "spans": spans}


def books_init(build: Callable) -> Callable:
    """For the method that builds an engine or a trainer: a fresh
    ``SetupTimeline`` as ``self._setup_tl``, the method run inside its
    ``init`` span, and the programs built from here on booked
    (obs/compile.py)."""

    @functools.wraps(build)
    def booked(self, *args, **kwargs):
        keep_program_books()
        self._setup_tl = SetupTimeline()
        with self._setup_tl.span("init"):
            return build(self, *args, **kwargs)

    return booked


def emit_setup_record(record: Dict[str, Any]) -> None:
    """Hand one set-up record to the metrics hub: kept in memory for readers
    (``recent("setup")``) and written as one ``span`` row named ``setup``,
    its spans flattened into the children (a trace nests them by time)."""
    sink = get_metrics()
    sink.keep_record("setup", record)
    shift = record["time"] - record["t0"]
    extra = ({"replica": record["replica"]} if "replica" in record else {})
    sink.log_span("setup", record["time"], record["wall_s"], cat="setup",
                  children=[{"name": s["name"], "t0": s["t0"] + shift,
                             "dur_s": s["dur_s"]} for s in record["spans"]],
                  source=record["source"], **extra)


def setup_line(record: Optional[Dict[str, Any]],
               programs: List[Dict[str, Any]]) -> str:
    """One line for an operator: where set-up went (phases, the spans inside
    them in brackets) and what the programs cost (``programs``: the records
    or ``program_table()``; one whose ``trace_s`` is None had no watcher)."""
    parts = []
    for s in (record or {}).get("spans", ()):
        text = f"{s['name']} {s['dur_s']:.2f}"
        if s["depth"] == 0:
            parts.append([text])
        else:
            parts[-1].append(text)
    phases = ", ".join(p[0] + (f" ({', '.join(p[1:])})" if p[1:] else "")
                       for p in parts)
    watched = [p for p in programs if p["trace_s"] is not None]
    total = lambda rows, key: sum(r[key] or 0.0 for r in rows)
    verdicts = collections.Counter(p["cache"] for p in programs)
    return (f"set-up {(record or {}).get('wall_s', 0.0):.2f} s: {phases}; "
            f"{len(watched)} watched programs: trace "
            f"{total(watched, 'trace_s'):.2f} lower "
            f"{total(watched, 'lower_s'):.2f} load or compile "
            f"{total(watched, 'load_or_compile_s'):.2f} s; "
            f"{len(programs) - len(watched)} others "
            f"{total(programs, 'load_or_compile_s') - total(watched, 'load_or_compile_s'):.2f} s; "
            f"cache {dict(verdicts)}")


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
