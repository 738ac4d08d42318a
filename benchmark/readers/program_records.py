"""From the records the program keeps of its own work, in memory, in its
metrics hub (``obs/metrics.py``: ``get_metrics().recent(kind)``). The reader is
handed no stamps of the window, so the serving metrics take their own from
the records: every request's span row carries its ``t_submit`` on the clock of
the tick records, and the load generator submits from the window's opening to
its close, so the ticks that began between the first submit (``modes/serve.py``
``warm_requests``' two left out) and the last submit or admission are the
window's. The ticks before (warm-up) and after (an open loop's drain, few rows
live) are not. The metric's ``stat`` names which number:

* ``tick_decode_p50_ms``: from the serving engine's one record a tick (fields:
  ``obs/schema.py`` ``TICK_RECORD_FIELDS``), the median wall of a tick that ran
  the decode program and no prefill, prefix copy or chunk.
* ``tick_host_p50_ms``: median, over consecutive decode ticks, from the end of
  one tick's ``host_fetch`` to the end of the next tick's ``decode_dispatch``,
  less the prefill in between: the host's serial part, in which the device has
  no decode program queued.
* ``decode_rows_used_pct``: rows that decoded a token over rows of the
  fixed-shape program, summed over the ticks that ran it.
* ``span_child_ms``: from the engine's one span row a request
  (``Request.trace_row``: a ``request`` root with ``queued`` / ``prefill`` /
  ``decode`` children), the metric's ``percentile`` of the duration of its
  ``child`` over the window's requests that finished with their tokens.
  ``queued`` is admit less submit, ``prefill`` first token less admit.
* ``cadence_segment_ms``: from the trainer's cadence rows (one ``metrics`` row
  every ``log_every`` steps with the seconds of each segment of its step
  timeline), the metric's ``segment`` in milliseconds a step, over the rows
  whose steps all lie in the window: the cell's ``check_steps`` and
  ``warm_steps`` come first, and a row that holds any of them is left out.
  ``data_wait`` is the loop's thread waiting for a batch; steps are dispatched
  ahead of the device, so it costs tokens a second only once it passes the
  slack a step leaves (the step's device time less the host's own part).

A program that keeps no such records gives nothing to read: ``None``.
"""

import statistics

from benchmark import stats

PREFILL_PHASES = ("prefill", "prefill_shard", "prefix_copy")
#: ``modes/serve.py`` ``warm_requests`` sends this many through the engine's
#: own admission before the window opens
WARM_REQUESTS = 2


def recent(kind: str):
    from building_llm_from_scratch_tpu.obs.metrics import get_metrics

    rows = getattr(get_metrics(), "recent", None)
    return rows(kind) if rows is not None else []


def requests_of_window(spans):
    """The request rows by submit, the warm-up's left out."""
    rows = [r for r in spans
            if r.get("name") == "request" and "t_submit" in r]
    return sorted(rows, key=lambda r: r["t_submit"])[WARM_REQUESTS:]


def ticks_of_window(ticks, requests):
    """The ticks that began between the first submit and the later of the last
    submit and the last admission: a request still in flight when the engine
    is stopped leaves no row, so a closed loop's last submits are not seen;
    the ticks that admitted them are."""
    if not requests:
        return []
    lo = requests[0]["t_submit"]
    hi = max([requests[-1]["t_submit"]]
             + [t["t0"] for t in ticks if t.get("admitted")])
    return [t for t in ticks if lo <= t["t0"] <= hi]


def ran_decode(tick) -> bool:
    return "decode_dispatch" in tick["phases"]


def prefill_s(tick) -> float:
    return sum(tick["phases"].get(ph, 0.0) for ph in PREFILL_PHASES)


def tick_decode_p50_ms(ticks):
    walls = [t["t1"] - t["t0"] for t in ticks
             if ran_decode(t) and not prefill_s(t)]
    return 1e3 * statistics.median(walls) if walls else None


def tick_host_p50_ms(ticks):
    parts = [b["t_dispatch"] - a["t_fetch"] - prefill_s(b)
             for a, b in zip(ticks, ticks[1:])
             if ran_decode(a) and ran_decode(b)]
    return 1e3 * statistics.median(parts) if parts else None


def decode_rows_used_pct(ticks):
    decode = [t for t in ticks if ran_decode(t)]
    if not decode:
        return None
    return 100.0 * sum(t["rows"] for t in decode) / sum(
        t["n_slots"] for t in decode)


def span_child_ms(spans, child: str, percentile: float):
    seconds = [c["dur_s"] for r in spans
               if r.get("outcome") in ("length", "eos")
               for c in r.get("children", ()) if c["name"] == child]
    return 1e3 * stats.percentile(seconds, percentile) if seconds else None


def cadence_segment_ms(rows, segment: str, first_step: int):
    """``first_step``: the first step of the window, counted from 1."""
    rows = [r for r in rows if r.get("steps_in_window")
            and r["step"] - r["steps_in_window"] + 1 >= first_step]
    steps = sum(r["steps_in_window"] for r in rows)
    if not steps:
        return None
    return 1e3 * sum(r.get(segment + "_s", 0.0) for r in rows) / steps


def read(metric, ctx):
    stat = metric["stat"]
    if stat == "cadence_segment_ms":
        traffic = ctx["cell"].traffic
        return cadence_segment_ms(
            recent("metrics"), metric["segment"],
            traffic["check_steps"] + traffic["warm_steps"] + 1)
    requests = requests_of_window(recent("span"))
    if stat == "span_child_ms":
        return span_child_ms(requests, metric["child"], metric["percentile"])
    return {"tick_decode_p50_ms": tick_decode_p50_ms,
            "tick_host_p50_ms": tick_host_p50_ms,
            "decode_rows_used_pct": decode_rows_used_pct}[stat](
                ticks_of_window(recent("tick"), requests))
