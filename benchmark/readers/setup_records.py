"""From the books the program keeps of its own set-up, in memory, in its
metrics hub (``obs/metrics.py``: ``get_metrics().recent(kind)``): one
``program`` record a program the process built (``obs/compile.py``: the
seconds of its tracing, its lowering and its cache load or compile, and the
cache's verdict by JAX's own events), one ``setup`` record an engine or
trainer (``obs/timeline.py`` ``SetupTimeline``: the phases of set-up and the
spans inside them). ``obs/schema.py`` lists the fields of both.

Only what ended before the window opened is counted, so nothing the
reference builds afterwards is: a serving cell's cut is the first window
request's ``t_submit`` (``program_records.requests_of_window``), and its
window opens when the last of the harness's warm requests is done
(``modes/serve.py`` ``warm_requests``: that request's ``t_submit`` plus its
duration, a few milliseconds before the harness's own stamp); the train
cell's cut is the trainer's last cadence row's ``time``. The metric's
``stat`` names which number:

* ``setup_trace_lower_s``: ``trace_s + lower_s`` summed over the watched
  programs: the Python every process runs whatever the compile cache holds
  (the function into a jaxpr, the jaxpr into StableHLO, each pallas kernel's
  body into Mosaic).
* ``setup_load_or_compile_s``: ``load_or_compile_s`` summed over the
  programs, watched or not, built from the start of warm-up (serving) or from
  the end of the trainer's construction (training, on the trainer's thread:
  the harness builds its own norms on another) to the window's opening: a
  load from the cache on a warm start, the compiler on a cold one.
* ``setup_init_s``: the ``init`` phase, the program's constructor
  (``DecodeEngine.__init__``, ``Trainer._setup``), the programs it builds
  itself inside it.
* ``setup_first_runs_s``: serving: the wall of ``warmup()`` plus the wall
  from ``start()`` to the window's opening, less the two sums above: first
  executions, the unwatched programs' tracing, the warm requests and the
  Python around them; the remainder by construction, so the three add up to
  the harness's ``warmup`` + ``first_requests`` items. Training: the
  ``first_runs`` phase, from the first step's build to the end of the
  trainer's first blocking fetch.

The reader called for ``setup_first_runs_s`` also prints the books it read
from, itemised (``setup_books``): the phases, the watched programs one by
one, and what was built outside the program's phases (the harness's
``make_params``), which no metric counts. A program that keeps no such
records gives nothing to read: ``None``.
"""

import collections
import json

from benchmark.readers.program_records import (
    WARM_REQUESTS,
    recent,
    requests_of_window,
)


def phases_of(record):
    """The record's phases by name (a phase met twice: the later)."""
    return {s["name"]: s for s in record["spans"] if s["depth"] == 0}


def end_of(span) -> float:
    return span["t0"] + span["dur_s"]


def newest(records, source: str, before: float, clock: str):
    """The newest ``setup`` record of ``source`` begun before the cut."""
    found = [r for r in records
             if r.get("source") == source and r[clock] < before]
    return found[-1] if found else None


def itemised(record, programs, counted, books):
    """The books beside what they were read from, for the run's output."""
    counted_ids = {id(p) for p in counted}
    init = phases_of(record)["init"]
    in_init = [p for p in programs
               if init["t0"] <= p["t_end"] <= end_of(init)]
    outside = [p for p in programs if id(p) not in counted_ids
               and not init["t0"] <= p["t_end"] <= end_of(init)]
    seconds = lambda rows: round(sum(p["load_or_compile_s"] + p["lower_s"]
                                     for p in rows), 4)
    return dict(
        books,
        phases=[[s["name"], s["depth"], round(s["dur_s"], 4),
                 round(s["self_s"], 4)] for s in record["spans"]],
        between_phases_s=round(record["self_s"], 4),
        watched=[[p["label"], p["trace_s"], p["lower_s"],
                  p["load_or_compile_s"], p["cache"]]
                 for p in counted if p["watched"]],
        cache=dict(collections.Counter(
            p["cache"] for p in counted if p["watched"])),
        unwatched={"n": sum(not p["watched"] for p in counted),
                   "lower_s": round(sum(p["lower_s"] for p in counted
                                        if not p["watched"]), 4),
                   "load_or_compile_s": round(sum(
                       p["load_or_compile_s"] for p in counted
                       if not p["watched"]), 4)},
        inside_init={"n": len(in_init), "lower_and_load_s": seconds(in_init)},
        outside_the_phases={"n": len(outside),
                            "lower_and_load_s": seconds(outside)})


def sums(counted):
    watched = [p for p in counted if p["watched"]]
    return {"setup_trace_lower_s": sum(p["trace_s"] + p["lower_s"]
                                       for p in watched),
            "setup_load_or_compile_s": sum(p["load_or_compile_s"]
                                           for p in counted)}


def serve_books(programs, setups, spans):
    window = requests_of_window(spans)
    if not window:
        return None
    cut = window[0]["t_submit"]
    record = newest(setups, "serve", cut, "t0")
    warm = sorted((r for r in spans if r.get("name") == "request"
                   and "t_submit" in r and r["t_submit"] < cut),
                  key=lambda r: r["t_submit"])[-WARM_REQUESTS:]
    phases = phases_of(record) if record else {}
    if not warm or not {"init", "warmup", "start"} <= set(phases):
        return None
    t_open = max(r["t_submit"] + r["dur_s"] for r in warm)
    before = [p for p in programs if p["t_end"] < cut]
    counted = [p for p in before
               if phases["warmup"]["t0"] <= p["t_end"] <= t_open]
    books = sums(counted)
    wall = phases["warmup"]["dur_s"] + t_open - phases["start"]["t0"]
    books["setup_init_s"] = phases["init"]["dur_s"]
    books["setup_first_runs_s"] = (wall - books["setup_trace_lower_s"]
                                   - books["setup_load_or_compile_s"])
    return itemised(record, before, counted, dict(
        books, warmup_s=phases["warmup"]["dur_s"],
        start_to_open_s=t_open - phases["start"]["t0"]))


def train_books(programs, setups, rows):
    cadence = [r for r in rows if r.get("steps_in_window")]
    if not cadence:
        return None
    cut = cadence[-1]["time"]
    record = newest(setups, "train", cut, "time")
    phases = phases_of(record) if record else {}
    if not {"init", "build:train_step", "first_runs"} <= set(phases):
        return None
    lo, hi = end_of(phases["init"]), end_of(phases["first_runs"])
    before = [p for p in programs if p["time"] < cut]
    built = [p for p in before if lo <= p["t_end"] <= hi]
    threads = {p["thread"] for p in built if p["watched"]}
    counted = [p for p in built if p["thread"] in threads]
    books = sums(counted)
    books["setup_init_s"] = phases["init"]["dur_s"]
    books["setup_first_runs_s"] = phases["first_runs"]["dur_s"]
    return itemised(record, before, counted, dict(
        books, build_s=phases["build:train_step"]["dur_s"],
        init_to_first_fetch_s=hi - phases["init"]["t0"]))


def read(metric, ctx):
    programs, setups = recent("program"), recent("setup")
    if ctx["cell"].mode == "train":
        books = train_books(programs, setups, recent("metrics"))
    else:
        books = serve_books(programs, setups, recent("span"))
    if books is None:
        return None
    if metric["stat"] == "setup_first_runs_s":
        print(json.dumps({"setup_books": books}), flush=True)
    return books[metric["stat"]]
