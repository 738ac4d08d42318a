"""From a profiler trace (``.xplane.pb``) to device busy time, program times,
the costliest device operations and the longest idle gaps.

A device plane is named ``/device:TPU:<n>``; its line ``XLA Modules`` holds
one event per run of a compiled program (``jit_train_step(...)``), its line
``XLA Ops`` one per operation. Host planes hold the spans of host threads,
among them ``jax.profiler.TraceAnnotation`` spans. All planes share one clock.
Read with ``jax.profiler.ProfileData`` alone.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import os
import re
import time
from typing import Any, Dict, List, Optional, Tuple

#: gaps shorter than this between two device operations are the chip's own
#: hand-over from one operation to the next, not the host's doing
MIN_GAP_NS = 20_000
#: naming a gap searches the host spans, so only the longest are named
MAX_NAMED_GAPS = 400

Interval = Tuple[int, int]


def union(intervals: List[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def program_name(event_name: str) -> str:
    """``jit_train_step(1234567)`` -> ``jit_train_step``."""
    return re.sub(r"\(.*\)$", "", event_name).strip()


def _clean(name: str, width: int = 64) -> str:
    return re.sub(r"[^A-Za-z0-9_.\-]", "_", name)[:width]


def op_name(event_name: str) -> str:
    """``%copy.5 = bf16[50257,1600]{1,0:T(8,128)} copy(...)`` ->
    ``copy.5 bf16[50257,1600]``: the operation and what it produces."""
    m = re.match(r"%?([\w.\-]+) = \(?(\w+\[[\d,]*\])?", event_name)
    if not m:
        return event_name
    return m.group(1) + (" " + m.group(2) if m.group(2) else "")


def load(path: str) -> Dict[str, Any]:
    """{device planes: {name: {modules, ops}}, host: [(start, end, name)]},
    every time in nanoseconds."""
    import jax

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    else:
        data = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, Dict[str, list]] = {}
    host: List[Tuple[int, int, str]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            entry = devices.setdefault(plane.name, {"modules": [], "ops": []})
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(
                    line.name)
                if key is None:
                    continue
                for ev in line.events:
                    a = int(ev.start_ns)
                    entry[key].append((a, a + int(ev.duration_ns), ev.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    a = int(ev.start_ns)
                    host.append((a, a + int(ev.duration_ns), ev.name))
    return {"devices": devices, "host": host}


def _host_span_at(host: List[Tuple[int, int, str]], t: int) -> str:
    """The shortest host span that covers ``t``."""
    best = None
    for a, b, name in host:
        if a <= t < b and (best is None or b - a < best[0]):
            best = (b - a, name)
    return best[1] if best else "no_host_span"


def reduce(loaded: Dict[str, Any], window_s: Optional[float] = None
           ) -> Optional[Dict[str, Any]]:
    """The metrics of one trace, or None where no operation ran on a device.

    ``window_s`` is the traced window by the host's clock; without it the
    span from the first to the last device operation is taken."""
    devices = {n: d for n, d in loaded["devices"].items()
               if d["ops"] or d["modules"]}
    if not devices:
        return None
    busy_ns, spans = [], []
    op_totals: Dict[str, int] = {}
    programs: Dict[str, List[float]] = {}
    gaps: Dict[str, int] = {}
    small_gaps = 0
    first = min(devices)        # gaps and operations are named on one chip
    for name, dev in devices.items():
        merged = union([(a, b) for a, b, _ in (dev["ops"] or dev["modules"])])
        busy_ns.append(sum(b - a for a, b in merged))
        spans.append(merged[-1][1] - merged[0][0])
        if name != first:
            continue
        for a, b, op in dev["ops"]:
            op = op_name(op)
            op_totals[op] = op_totals.get(op, 0) + (b - a)
        for a, b, mod in dev["modules"]:
            programs.setdefault(program_name(mod), []).append((b - a) / 1e9)
        mods = sorted(dev["modules"])
        starts = [m[0] for m in mods]
        idle = sorted(((start - end, end, start) for (_, end), (start, _)
                       in zip(merged, merged[1:])), reverse=True)
        for rank, (length, end, start) in enumerate(idle):
            if length < MIN_GAP_NS:
                small_gaps += length
            elif rank >= MAX_NAMED_GAPS:
                gaps["shorter_gaps_not_named"] = gaps.get(
                    "shorter_gaps_not_named", 0) + length
            else:
                i = bisect.bisect_left(starts, start)
                label = "{}___after_{}_before_{}".format(
                    _host_span_at(loaded["host"], (start + end) // 2),
                    program_name(mods[i - 1][2]) if i else "start",
                    program_name(mods[i][2]) if i < len(mods) else "end")
                gaps[label] = gaps.get(label, 0) + length
    if small_gaps:
        gaps["gaps_under_20_us_between_ops"] = small_gaps
    top = lambda d: [[_clean(k), v / 1e9] for k, v in sorted(
        d.items(), key=lambda kv: -kv[1])[:10]]
    span_s = sum(spans) / len(spans) / 1e9
    return {"busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
            "window_s": window_s if window_s is not None else span_s,
            "span_s": span_s, "programs": programs,
            "device_ops": top(op_totals), "idle_gaps": top(gaps)}


class Capture:
    """Traces a part of the window: ``start()`` and ``stop()`` by the clock
    of whoever drives the window; ``result()`` reduces what was written."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.t_start = self.t_stop = None

    def start(self) -> None:
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0      # spans, not every Python call
        jax.profiler.start_trace(self.log_dir, profiler_options=options)
        self.t_start = time.perf_counter()

    def stop(self) -> None:
        import jax

        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()

    def result(self) -> Optional[Dict[str, Any]]:
        if self.t_stop is None:
            return None
        files = sorted(glob.glob(os.path.join(
            self.log_dir, "plugins", "profile", "*", "*.xplane.pb")),
            key=os.path.getmtime)
        if not files:
            return None
        return reduce(load(files[-1]), self.t_stop - self.t_start)
