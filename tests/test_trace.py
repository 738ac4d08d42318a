"""Serving observability tests: request-span tracing + Chrome trace
export (obs/trace.py), the per-tick engine phase breakdown, the
Prometheus ``/metrics`` endpoint and structured ``/healthz``, the
histogram/rolling-window aggregation primitives, and the serving
extension of the no-per-step-host-sync guard (instrumentation must add
ZERO device fetches to the decode tick).
"""

import http.client
import json
import threading
import time

import jax
import numpy as np
import pytest

from building_llm_from_scratch_tpu.configs import ModelConfig
from building_llm_from_scratch_tpu.models import init_params
from building_llm_from_scratch_tpu.obs import (
    Histogram,
    RollingRatio,
    chrome_trace,
    configure_metrics,
    export_chrome_trace,
    get_metrics,
    render_prometheus,
)
from building_llm_from_scratch_tpu.obs.trace import TICK_PHASES
from building_llm_from_scratch_tpu.serving import (
    DecodeEngine,
    QueueFullError,
    SamplingParams,
    SLOShedError,
)


def tiny_cfg(ctx=64, **kw):
    base = dict(name="trace-tiny", vocab_size=96, context_length=ctx,
                emb_dim=32, n_heads=2, n_layers=2, hidden_dim=64,
                n_kv_groups=2, norm="layernorm", positional="learned",
                activation="gelu", drop_rate=0.0, eos_id=1)
    base.update(kw)
    return ModelConfig(**base)


@pytest.fixture(scope="module")
def model():
    cfg = tiny_cfg()
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture
def sink(tmp_path):
    """A fresh JSONL metrics sink for one test; always detached after."""
    path = tmp_path / "metrics.jsonl"
    logger = configure_metrics(str(path), run_metadata={"test": True})
    yield str(path)
    logger.close()
    configure_metrics(None)


def load_rows(path):
    return [json.loads(line) for line in open(path)]


# ---------------------------------------------------------------------------
# aggregation primitives (no jax)
# ---------------------------------------------------------------------------

def test_histogram_bucket_counts_match_observations():
    h = Histogram(bounds=(0.01, 0.1, 1.0))
    values = [0.005, 0.005, 0.05, 0.5, 5.0]        # 2 / 1 / 1 / 1(+Inf)
    for v in values:
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == len(values)
    assert snap["sum"] == pytest.approx(sum(values))
    assert snap["buckets"] == [(0.01, 2), (0.1, 3), (1.0, 4), ("+Inf", 5)]
    # upper-edge inclusivity (prometheus `le` semantics)
    h2 = Histogram(bounds=(1.0, 2.0))
    h2.observe(1.0)
    assert h2.snapshot()["buckets"][0] == (1.0, 1)
    # percentile interpolates inside the target bucket; +Inf clamps
    assert 0.0 < h.percentile(10) <= 0.01
    assert h.percentile(99) == 1.0                  # clamped to last bound
    assert Histogram().percentile(50) is None       # empty


def test_rolling_ratio_window_expires_old_misses():
    r = RollingRatio(window_s=10.0, n_buckets=5)
    t0 = 1000.0
    r.observe(True, now=t0)
    r.observe(True, now=t0)
    r.observe(False, now=t0 + 1)
    assert r.ratio(now=t0 + 1) == pytest.approx(2 / 3)
    # 11s later the misses have aged out; only fresh observations count
    r.observe(False, now=t0 + 12)
    assert r.ratio(now=t0 + 12) == 0.0
    assert RollingRatio().ratio() is None           # nothing observed


def test_render_prometheus_exposition_format():
    h = Histogram(bounds=(0.1, 1.0))
    h.observe(0.05)
    h.observe(5.0)
    text = render_prometheus({"done": 3}, {"occupancy": 0.5},
                             {"ttft_seconds": h}, prefix="x_")
    lines = text.splitlines()
    assert "x_done_total 3" in lines
    assert "x_occupancy 0.5" in lines
    assert 'x_ttft_seconds_bucket{le="0.1"} 1' in lines
    assert 'x_ttft_seconds_bucket{le="+Inf"} 2' in lines
    assert "x_ttft_seconds_count 2" in lines
    # every non-comment line is "name{labels} value" with a float value
    for line in lines:
        if line.startswith("#") or not line:
            continue
        name, value = line.rsplit(" ", 1)
        float(value)
        assert name[0].isalpha()


# ---------------------------------------------------------------------------
# request span trees + Chrome trace export
# ---------------------------------------------------------------------------

def test_request_spans_and_chrome_export_round_trip(model, sink, tmp_path):
    cfg, params = model
    eng = DecodeEngine(cfg, params, n_slots=2, max_len=64, metrics_every=2)
    eng.warmup()
    handles = [eng.submit(np.array([3, 4, 5], np.int32),
                          SamplingParams(max_new_tokens=5, ignore_eos=True,
                                         seed=i))
               for i in range(3)]
    eng.run_until_idle()
    for h in handles:
        h.result(timeout=10)
    eng.shutdown()
    rows = load_rows(sink)
    spans = [r for r in rows if r.get("type") == "span"]
    done = [r for r in rows if r.get("event") == "request_done"]
    # exactly one span row per completed request
    assert len(spans) == len(done) == 3
    for s in spans:
        assert s["name"] == "request" and s["outcome"] == "length"
        kids = {c["name"]: c for c in s["children"]}
        assert set(kids) == {"queued", "prefill", "decode"}
        # children nest inside the root span and all spans are closed
        t0, t1 = s["t0"], s["t0"] + s["dur_s"]
        for c in s["children"]:
            assert c["dur_s"] >= 0
            assert c["t0"] >= t0 - 1e-6
            assert c["t0"] + c["dur_s"] <= t1 + 1e-6
        # phases tile the root span in lifecycle order
        assert kids["queued"]["t0"] <= kids["prefill"]["t0"]
        assert kids["prefill"]["t0"] <= kids["decode"]["t0"]

    out = tmp_path / "trace.json"
    meta = export_chrome_trace(sink, str(out))
    assert meta["n_request_spans"] == 3
    assert meta["n_tick_windows"] >= 1
    trace = json.load(open(out))                   # valid JSON round-trip
    events = trace["traceEvents"]
    assert events
    xs = [e for e in events if e["ph"] == "X"]
    for e in events:
        assert e["ph"] in ("X", "i", "C", "M")
        if e["ph"] == "X":
            assert e["dur"] >= 0 and e["ts"] >= 0
    # one root request slice per request_done, on that request's track
    roots = [e for e in xs if e["name"] == "request"]
    assert len(roots) == 3
    assert len({e["tid"] for e in roots}) == 3
    # tick windows made it out too
    assert any(e["name"].startswith("ticks") for e in xs)


def test_trace_lifecycle_audit_every_outcome_closes_one_tree(model, sink):
    """Satellite: submit one request per terminal outcome (done, rejected,
    shed, expired, failed) and assert the trace joins never drop one —
    every lifecycle event carries request_id (and reason), and every id
    closes exactly one span tree."""
    cfg, params = model
    eng = DecodeEngine(cfg, params, n_slots=1, max_len=64, max_queue=1,
                       metrics_every=0)
    eng.warmup()

    # DONE
    done_h = eng.submit(np.array([3, 4], np.int32),
                        SamplingParams(max_new_tokens=3, ignore_eos=True))
    eng.run_until_idle()
    done_h.result(timeout=10)

    # FAILED: a raising client callback is the request's own fault
    def bad_cb(req, tok, piece):
        raise RuntimeError("client exploded")

    failed_h = eng.submit(np.array([5], np.int32),
                          SamplingParams(max_new_tokens=3, ignore_eos=True),
                          on_token=bad_cb)
    eng.run_until_idle()
    with pytest.raises(RuntimeError):
        failed_h.result(timeout=10)

    # REJECTED: queue capacity 1, nothing ticking
    held = eng.submit(np.array([6], np.int32),
                      SamplingParams(max_new_tokens=2, ignore_eos=True))
    with pytest.raises(QueueFullError):
        eng.submit(np.array([7], np.int32),
                   SamplingParams(max_new_tokens=2, ignore_eos=True))

    # EXPIRED: deadline passes while queued
    eng.run_until_idle()                            # finishes `held`
    held.result(timeout=10)
    # (no clock decides which outcome this is: with no service estimate
    # submit cannot predict a miss and shed it, whatever the finished
    # requests above taught the EWMAs on a loaded host; and the deadline
    # is moved into the past by hand, not slept out)
    eng._tpot_ewma = eng._tokens_ewma = None
    expired_h = eng.submit(np.array([8], np.int32),
                           SamplingParams(max_new_tokens=2,
                                          ignore_eos=True,
                                          deadline_s=60.0))
    expired_h.t_deadline = time.monotonic() - 1.0   # deadline passes
    eng.run_until_idle()
    from building_llm_from_scratch_tpu.serving.request import (
        RequestExpiredError,
    )

    with pytest.raises(RequestExpiredError):
        expired_h.result(timeout=10)

    # SHED: with a service estimate (set, not measured: one second a
    # token) an impossible deadline is rejected at submit (predicted
    # miss), without ever entering the queue
    eng._tpot_ewma, eng._tokens_ewma = 1.0, 8.0
    with pytest.raises(SLOShedError):
        eng.submit(np.array([9], np.int32),
                   SamplingParams(max_new_tokens=60, ignore_eos=True,
                                  deadline_s=1e-6))
    eng.shutdown()

    rows = load_rows(sink)
    events = [r for r in rows if r.get("type") == "event"]
    spans = [r for r in rows if r.get("type") == "span"]
    by_kind = {}
    for e in events:
        by_kind.setdefault(e["event"], []).append(e)
    # every lifecycle event names its request and its reason
    for kind in ("request_rejected", "request_shed", "request_expired",
                 "request_failed"):
        assert by_kind.get(kind), f"missing {kind} event"
        for e in by_kind[kind]:
            assert isinstance(e.get("request_id"), int), (kind, e)
            assert e.get("reason"), (kind, e)
    # exactly ONE closed span tree per request id, outcome attached
    by_id = {}
    for s in spans:
        by_id.setdefault(s["request_id"], []).append(s)
    assert all(len(v) == 1 for v in by_id.values()), by_id
    outcomes = {s["request_id"]: s["outcome"] for s in spans}
    expected = {"length", "error", "rejected", "shed", "expired"}
    assert expected <= set(outcomes.values()), outcomes
    for s in spans:
        assert s["dur_s"] >= 0 and s["children"], s
        assert s["children"][0]["name"] == "queued"
    # ... and the trace join sees them all (5 requests -> 5 trees:
    # done, failed, held/done, rejected, expired, shed = 6 actually)
    trace = chrome_trace(rows)
    assert trace["metadata"]["n_request_spans"] == len(spans) == 6


def test_trace_export_handles_training_fixture(tmp_path):
    """The exporter renders TRAINING runs too: the checked-in fixture's
    StepTimeline cadence rows become train windows and its compile events
    become slices — one exporter for both tiers."""
    out = tmp_path / "train_trace.json"
    meta = export_chrome_trace("tests/fixtures/metrics_fixture.jsonl",
                               str(out))
    assert meta["n_train_windows"] >= 1
    trace = json.load(open(out))
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert any(e["name"].startswith("steps") for e in xs)
    assert any(e["name"].startswith("compile:") for e in xs)
    assert any(e["cat"] == "steps_phase" for e in xs)
    # incidents (watchdog_halt in the fixture) land as instants
    instants = [e for e in trace["traceEvents"] if e["ph"] == "i"]
    assert any(e["name"] == "watchdog_halt" for e in instants)


# ---------------------------------------------------------------------------
# per-tick engine phase breakdown
# ---------------------------------------------------------------------------

def test_tick_phase_breakdown_sums_to_tick_wall_time(model, sink):
    cfg, params = model
    eng = DecodeEngine(cfg, params, n_slots=2, max_len=64, metrics_every=4)
    eng.warmup()
    handles = [eng.submit(np.array([3, 4, 5, 6], np.int32),
                          SamplingParams(max_new_tokens=12,
                                         ignore_eos=True, seed=i))
               for i in range(4)]
    eng.run_until_idle()
    for h in handles:
        h.result(timeout=10)
    eng.shutdown()
    rows = load_rows(sink)
    ticks = [r for r in rows if r.get("type") == "metrics"
             and isinstance(r.get("tick_total_s"), (int, float))
             and r.get("ticks_in_window")]
    assert ticks, "no serving cadence rows with a tick breakdown"
    for r in ticks:
        phase_sum = sum(r[f"tick_{ph}_s"] for ph in TICK_PHASES)
        total = r["tick_total_s"]
        # phases are measured sub-intervals of the tick: their sum can
        # never exceed the tick wall time, and the unattributed remainder
        # (branching, scheduler bookkeeping) must stay small
        assert phase_sum <= total * 1.02 + 1e-6, r
        assert phase_sum >= total * 0.5, r
        assert r["win_dur_s"] > 0 and r["win_t0"] > 0
    # cumulative totals cover the whole run for /metrics counters
    assert eng.tick_seconds_total > 0
    assert sum(eng.tick_phase_totals.values()) <= eng.tick_seconds_total * 1.02
    # decode must be a real, nonzero phase on every loaded window
    assert all(r["tick_decode_dispatch_s"] > 0 for r in ticks)


def _burst(eng, n=4, new_tokens=10):
    handles = [eng.submit(np.array([3, 4, 5, 6], np.int32),
                          SamplingParams(max_new_tokens=new_tokens,
                                         ignore_eos=True, seed=i))
               for i in range(n)]
    for h in handles:
        h.result(timeout=60)
    return handles


def test_tick_spans_reach_the_profiler_trace_and_name_idle_gaps(
        model, tmp_path):
    """The engine's tick uses the trainer's span: under a profiler session
    (the benchmark's ``Capture``) every phase that ran is a host span of
    its registered name inside a ``tick`` step, and a device-idle gap
    between two decode programs is named by a ``tick.*`` span, not
    ``no_host_span``."""
    from benchmark import trace as btrace
    from building_llm_from_scratch_tpu.obs.schema import (
        TICK_BETWEEN,
        TICK_SPANS,
        TICK_STEP,
    )

    cfg, params = model
    configure_metrics(None)
    eng = DecodeEngine(cfg, params, n_slots=2, max_len=64, metrics_every=4)
    eng.warmup()
    eng.start()
    capture = btrace.Capture(str(tmp_path / "trace"))
    capture.start()
    try:
        _burst(eng)
        with eng._lock:     # a request is done before its last tick is:
            pass            # let that tick close its spans
    finally:
        capture.stop()
        eng.shutdown(drain=False)
    files = sorted((tmp_path / "trace").rglob("*.xplane.pb"))
    assert files, "the profiler wrote no trace"
    loaded = btrace.load(str(files[-1]))
    by_name = {}
    for a, b, name in loaded["host"]:
        by_name.setdefault(name, []).append((a, b))
    ran = {ph for r in get_metrics().recent("tick") for ph in r["phases"]}
    assert {"admit", "prefill", "decode_dispatch", "host_fetch",
            "sample_commit", "callback_detok"} <= ran
    ticks = by_name[TICK_STEP]
    for ph in ran:
        spans = by_name.get(TICK_SPANS[ph])
        assert spans, f"phase {ph} ran and left no span {TICK_SPANS[ph]}"
        for a, b in spans:
            assert any(ta <= a and b <= tb for ta, tb in ticks), ph
    assert by_name.get(TICK_BETWEEN), "the loop's own span is missing"
    # a device that ran each decode program from its dispatch to the end
    # of its fetch, as the chip does: what it idles on is the host between
    # one tick's fetch and the next tick's dispatch
    dispatches = sorted(by_name[TICK_SPANS["decode_dispatch"]])
    fetches = sorted(by_name[TICK_SPANS["host_fetch"]])
    runs = [(d[0], f[1], "jit__decode_impl(1)")
            for d, f in zip(dispatches, fetches)]
    assert len(runs) >= 8
    loaded["devices"] = {"/device:TPU:0": {"modules": runs, "ops": runs}}
    # (on the CPU the backend's own threads write their operations into
    # the host plane; the chip's do not, so they are left out here)
    loaded["host"] = [h for h in loaded["host"] if h[2].startswith("tick")]
    gaps = dict(map(tuple, btrace.reduce(loaded)["idle_gaps"]))
    named = {k: v for k, v in gaps.items() if "___after_" in k}
    by_tick = sum(v for k, v in named.items() if k.startswith("tick"))
    assert any(k.startswith("tick.") and k.endswith(
        "___after_jit__decode_impl_before_jit__decode_impl") for k in named)
    assert by_tick >= 0.75 * sum(named.values()), named


def test_one_tick_record_on_every_exit_of_step(model):
    """One plain record a tick, whatever way ``step()`` left its timed
    part (decode, admission only, idle, a generation abort, a raising
    tick): phases sum to the wall, the stamps are ordered, and the ring
    keeps numbers only, so the engine is collectable after shutdown."""
    import gc
    import weakref

    from building_llm_from_scratch_tpu.obs import metrics as obs_metrics
    from building_llm_from_scratch_tpu.obs.schema import TICK_RECORD_FIELDS
    from building_llm_from_scratch_tpu.serving.supervisor import FaultHooks

    cfg, params = model
    sink = configure_metrics(None)

    class Hooks(FaultHooks):
        abort = raise_ = False

        def before_tick(self, engine):
            if self.abort:
                engine._generation += 1       # what _restart does first
            if self.raise_:
                raise RuntimeError("tick exploded")

    hooks = Hooks()
    eng = DecodeEngine(cfg, params, n_slots=2, max_len=64, metrics_every=0,
                       hooks=hooks)
    eng.warmup()
    n_steps = 0

    def step():
        nonlocal n_steps
        n_steps += 1
        return eng.step()

    assert step() is False                       # idle: nothing queued
    eng.submit(np.array([3, 4, 5], np.int32),
               SamplingParams(max_new_tokens=1, ignore_eos=True))
    assert step() is False                       # admission only: one token
    for i in range(3):
        eng.submit(np.array([3, 4, 5, 6], np.int32),
                   SamplingParams(max_new_tokens=6, ignore_eos=True, seed=i))
    while step():
        pass
    hooks.raise_ = True
    with pytest.raises(RuntimeError):
        step()
    hooks.raise_, hooks.abort = False, True
    assert step() is False                       # generation abort
    recs = sink.recent("tick")
    assert len(recs) == n_steps
    decode = [r for r in recs if "decode_dispatch" in r["phases"]]
    assert decode and recs[1]["admitted"] == 1
    assert set(recs[0]["phases"]) == {"admit"} and recs[0]["rows"] == 0
    assert max(r["queue_depth"] for r in recs) >= 2
    for r in recs:
        wall = r["t1"] - r["t0"]
        assert 0 <= sum(r["phases"].values()) <= wall * 1.001 + 1e-9, r
        assert set(r["phases"]) <= set(TICK_PHASES)
        assert set(r) <= set(TICK_RECORD_FIELDS)
        assert r["n_slots"] == 2 and 0 <= r["rows"] <= 2
    for r in decode:
        assert r["t0"] <= r["t_dispatch"] <= r["t_fetch"] <= r["t1"]
        assert r["rows"] >= 1
    assert [r["tick"] for r in decode] == sorted(r["tick"] for r in decode)
    assert sum(r["rows"] for r in decode) + 4 == eng.tokens_generated
    # totals are the records' sums: one account, read two ways
    assert eng.tick_seconds_total == pytest.approx(
        sum(r["t1"] - r["t0"] for r in recs))
    for ph in TICK_PHASES:
        assert eng.tick_phase_totals[ph] == pytest.approx(
            sum(r["phases"].get(ph, 0.0) for r in recs))

    # the ring never passes its length
    for i in range(obs_metrics.RECENT_RECORDS + 10):
        sink.keep_record("tick", {"tick": i})
    assert len(sink.recent("tick")) == obs_metrics.RECENT_RECORDS
    assert sink.recent("tick")[-1] == {"tick": obs_metrics.RECENT_RECORDS + 9}

    # ... and holds plain numbers and strings, no way back to the engine:
    # walked through every container, and the engine is collectable
    todo, plain = list(recs), (dict, list, str, int, float, type(None))
    while todo:
        obj = todo.pop()
        assert isinstance(obj, plain), type(obj)
        if isinstance(obj, (dict, list)):
            todo.extend(gc.get_referents(obj))
    sink.keep_record("tick", recs[-1])
    ref = weakref.ref(eng)
    eng.shutdown(drain=False)
    del eng, hooks, step
    gc.collect()
    assert ref() is None
    configure_metrics(None)


def test_a_tick_abandoned_in_its_fetch_leaves_the_new_timeline_alone(
        model, monkeypatch):
    """``_restart`` bumps the generation and gives the engine a fresh
    timeline while a wedged tick still sits in ``host_fetch``. When that
    tick un-wedges it opens no span on the new timeline, commits nothing,
    and books what it did from the timeline it started with."""
    from building_llm_from_scratch_tpu.obs.schema import TICK_SPANS
    from building_llm_from_scratch_tpu.obs.timeline import StepTimeline

    cfg, params = model
    sink = configure_metrics(None)
    eng = DecodeEngine(cfg, params, n_slots=2, max_len=64, metrics_every=0)
    eng.warmup()
    handle = eng.submit(np.array([3, 4, 5], np.int32),
                        SamplingParams(max_new_tokens=4, ignore_eos=True))
    assert eng.step()                       # admits and decodes once
    old, n_out = eng._tl, len(handle.output_ids)
    real_get = jax.device_get

    def wedged_get(x):
        out = real_get(x)
        if eng._tl is old:                  # what _restart does meanwhile
            eng._generation += 1
            eng._tl = StepTimeline(TICK_SPANS)
        return out

    monkeypatch.setattr(jax, "device_get", wedged_get)
    assert eng.step() is False
    assert eng._tl is not old
    assert eng._tl.seconds == {} and eng._tl.ends == {}
    assert len(handle.output_ids) == n_out
    rec = sink.recent("tick")[-1]
    assert "host_fetch" in rec["phases"]
    assert "sample_commit" not in rec["phases"]
    assert sum(rec["phases"].values()) <= (rec["t1"] - rec["t0"]) * 1.001
    monkeypatch.undo()
    eng.shutdown(drain=False)
    configure_metrics(None)


def test_last_ticks_reach_healthz_and_the_stall_dump(model):
    """The operator's view of the tick records: ``/healthz`` and the stall
    detector's dump show the newest ticks (which tick, how long, which
    phases, rows, admissions, the queue), each replica its own."""
    import io
    import logging

    from building_llm_from_scratch_tpu.obs import stall

    cfg, params = model
    configure_metrics(None)
    engines = [DecodeEngine(cfg, params, n_slots=2, max_len=64,
                            metrics_every=0, replica=name)
               for name in (0, 1)]
    for eng, n in zip(engines, (stall.LAST_TICKS + 4, 2)):
        eng.warmup()
        eng.submit(np.array([3, 4, 5], np.int32),
                   SamplingParams(max_new_tokens=n, ignore_eos=True))
        while eng.step():
            pass
    for eng, name in zip(engines, (0, 1)):
        ticks = eng.healthz_payload()["last_ticks"]
        assert ticks and len(ticks) <= stall.LAST_TICKS
        assert {t["replica"] for t in ticks} == {name}
        assert ticks[-1]["tick"] == eng.n_ticks
        assert [t["tick"] for t in ticks] == sorted(t["tick"] for t in ticks)
        for t in ticks:
            assert t["n_slots"] == 2 and t["rows"] <= 1
            assert 0 <= sum(t["phases_ms"].values()) <= t["wall_ms"] * 1.01
            assert t["ended_s_ago"] >= 0
            assert t["queue_depth"] == t["admitted"]    # its one request
        assert sum(t["admitted"] for t in ticks) <= 1
    assert len(engines[0].healthz_payload()["last_ticks"]) == stall.LAST_TICKS
    json.dumps(engines[0].healthz_payload())       # the body stays JSON

    # obs loggers don't propagate: a handler of its own, not caplog
    handler = logging.StreamHandler(io.StringIO())
    stall.logger.addHandler(handler)
    try:
        stall.StallDetector(timeout=0.01)._dump(1.0, 0.01)
    finally:
        stall.logger.removeHandler(handler)
    text = handler.stream.getvalue()
    assert "Last serving ticks: [{'tick': " in text
    assert "'replica': 1}" in text
    for eng in engines:
        eng.shutdown(drain=False)
    configure_metrics(None)


def test_hub_keeps_rows_with_and_without_a_file(model, tmp_path):
    """``get_metrics().recent(kind)`` returns request span rows and cadence
    rows with no file configured; with one, the same rows reach the JSONL;
    kinds no reader asks for (``event``, ``health``) go to the file only;
    ``configure_metrics`` starts both anew."""
    cfg, params = model

    def run():
        eng = DecodeEngine(cfg, params, n_slots=2, max_len=64,
                           metrics_every=2)
        eng.warmup()
        eng.start()
        handles = _burst(eng, n=3, new_tokens=5)
        eng.shutdown(drain=False)
        return {h.id for h in handles}

    def requests(hub):
        # the engine's one `setup` row is a span row too (obs/timeline.py)
        return [r for r in hub.recent("span") if r["name"] == "request"]

    hub = configure_metrics(None)
    ids = run()
    spans = requests(hub)
    assert {s["request_id"] for s in spans} == ids
    assert len(hub.recent("span")) == len(spans) + 1
    for s in spans:
        assert [c["name"] for c in s["children"]] == [
            "queued", "prefill", "decode"]
        assert s["t_submit"] > 0 and s["outcome"] == "length"
    assert any("tick_total_s" in r for r in hub.recent("metrics"))
    assert hub.recent("tick") and hub.recent("event") == []

    path = tmp_path / "metrics.jsonl"
    hub = configure_metrics(str(path), run_metadata={"test": True})
    assert hub.recent("span") == [] and hub.recent("tick") == []
    ids = run()
    hub.close()
    rows = load_rows(str(path))
    for kind in ("span", "metrics"):
        assert [r for r in rows if r["type"] == kind] == hub.recent(kind)
    assert any(r["type"] == "event" for r in rows)
    assert {r["request_id"] for r in requests(hub)} == ids
    assert not any(r["type"] == "tick" for r in rows)   # memory only
    configure_metrics(None)


def test_tick_steady_state_has_zero_implicit_transfers(model):
    """Serving extension of the PR-3 no-per-step-host-sync guard, now via
    the transfer-guard sentry (analysis/runtime.py — replaces the old
    hand-rolled 'exactly 2 conversions per tick' spy): a full serving
    burst — admissions, prefill, decode ticks, retirement, cadence
    metrics flushes — runs with ZERO implicit device->host transfers.
    The tick's sanctioned fetches are explicit ``jax.device_get`` (which
    the sentry admits); anything implicit (a float()/np.asarray sneaking
    into the tick or the metrics flush) raises ImplicitTransferError.
    The KV cache must also never round-trip through the host."""
    from building_llm_from_scratch_tpu.analysis.runtime import (
        ImplicitTransferError,
        no_implicit_device_to_host,
    )

    import jax as _jax

    cfg, params = model
    eng = DecodeEngine(cfg, params, n_slots=2, max_len=64, metrics_every=2,
                       watch_compiles=False)
    eng.warmup()
    handles = [eng.submit(np.array([3, 4], np.int32),
                          SamplingParams(max_new_tokens=8, ignore_eos=True,
                                         seed=i))
               for i in range(3)]
    # count the EXPLICIT fetches too: the sentry proves nothing implicit
    # remains, and the spy keeps the old per-tick budget pinned — a new
    # device_get added to the tick (a real extra host sync, even though
    # explicit) must fail this test, not ship silently
    n_gets = {"n": 0}
    real_device_get = _jax.device_get

    def counting_device_get(x):
        n_gets["n"] += 1
        return real_device_get(x)

    _jax.device_get = counting_device_get
    try:
        with no_implicit_device_to_host():
            eng.run_until_idle()
    finally:
        _jax.device_get = real_device_get
    for h in handles:
        h.result(timeout=10)
    assert eng.n_ticks >= 8
    # the sanctioned budget: 2 fetches per decode tick (next-token row +
    # finite-ok mask) and 3 per admission (PRNG key, prefill ok, first
    # token) — nothing else
    assert n_gets["n"] == 2 * eng.n_ticks + 3 * len(handles), (
        n_gets, eng.n_ticks)
    # the KV cache stayed on device end to end
    import jax as _jax

    for pane in ("k", "v"):
        for layer in eng.cache[pane]:
            assert isinstance(layer, _jax.Array), type(layer)

    # the sentry has teeth on this very engine: an implicit fetch of a
    # device value inside the guarded region raises
    with pytest.raises(ImplicitTransferError):
        with no_implicit_device_to_host():
            float(eng.cache["k"][0][0, 0, 0, 0])
    eng.shutdown()


def test_trainer_step_off_cadence_has_zero_implicit_transfers(tmp_path):
    """The trainer twin: with every cadence (eval/sample/checkpoint/log)
    pushed beyond the horizon, a whole training epoch — step loop,
    deferred-DMA lr/health bookkeeping, the final metrics flush — runs
    under the transfer sentry. The sanctioned cadence fetch point
    (``Trainer._flush_metrics``) uses explicit ``jax.device_get``, so
    steady-state training performs zero implicit device->host
    transfers."""
    from building_llm_from_scratch_tpu.analysis.runtime import (
        no_implicit_device_to_host,
    )
    from building_llm_from_scratch_tpu.data.pretrain import PretrainLoader
    from building_llm_from_scratch_tpu.data.tokenizers import ByteTokenizer
    from building_llm_from_scratch_tpu.training.trainer import Trainer

    cfg = tiny_cfg(ctx=32, vocab_size=256, eos_id=0, name="sentry-train")
    tok = ByteTokenizer()
    datafile = tmp_path / "corpus.txt"
    datafile.write_text("steady state corpus " * 60)
    loader = PretrainLoader(tok, batch_size=4, max_length=cfg.context_length)
    trainer = Trainer(cfg, init_params(cfg, jax.random.PRNGKey(0)), tok,
                      loader, output_dir=str(tmp_path / "out"),
                      eval_freq=10**6, print_sample_iter=10**6,
                      save_ckpt_freq=10**6, warmup_steps=2, log_every=0,
                      show_progress=False)
    with no_implicit_device_to_host():
        trainer.train_model([str(datafile)], 1, start_context="the ")
    assert trainer.global_step >= 4
    # the deferred fetches DID land (explicitly) at the final flush
    assert len(trainer.track_lrs) == trainer.global_step


# ---------------------------------------------------------------------------
# /metrics + structured /healthz over HTTP
# ---------------------------------------------------------------------------

def _parse_exposition(text):
    """Tiny Prometheus text-format parser: {series_name: [(labels, value)]}
    — raises on any malformed line, which IS the format assertion."""
    series = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        name_part, value = line.rsplit(" ", 1)
        if "{" in name_part:
            name, labels = name_part.split("{", 1)
            assert labels.endswith("}")
            labels = labels[:-1]
        else:
            name, labels = name_part, ""
        series.setdefault(name, []).append((labels, float(value)))
    return series


def test_metrics_endpoint_exposition_and_structured_healthz(model):
    cfg, params = model
    from building_llm_from_scratch_tpu.serving.frontend import (
        make_http_server,
    )

    eng = DecodeEngine(cfg, params, n_slots=2, max_len=64)
    eng.warmup()
    eng.start()
    server = make_http_server(eng, 0, host="127.0.0.1")
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        for i in range(3):
            body = json.dumps({"prompt_ids": [5, 6, 7],
                               "max_new_tokens": 4, "ignore_eos": True,
                               "seed": i, "deadline_s": 60.0})
            conn.request("POST", "/generate", body=body)
            assert conn.getresponse().status == 200

        conn.request("GET", "/metrics")
        resp = conn.getresponse()
        assert resp.status == 200
        assert resp.getheader("Content-Type").startswith("text/plain")
        series = _parse_exposition(resp.read().decode())

        pre = "bllm_serve_"
        assert series[pre + "requests_finished_total"][0][1] == 3
        # histogram bucket counts match the number of finished requests
        for h in ("ttft_seconds", "e2e_seconds", "queue_wait_seconds"):
            buckets = dict(series[pre + h + "_bucket"])
            assert buckets['le="+Inf"'] == 3, (h, buckets)
            assert series[pre + h + "_count"][0][1] == 3
            # cumulative and monotone in `le`
            counts = [v for _, v in series[pre + h + "_bucket"]]
            assert counts == sorted(counts)
        # key gauges for the replica router
        assert pre + "slot_occupancy" in series
        assert pre + "queue_depth" in series
        assert pre + "engine_up" in series
        assert series[pre + "uptime_seconds"][0][1] > 0
        # deadline-carrying requests all finished in time -> burn 0.0
        assert series[pre + "slo_miss_ratio"][0][1] == 0.0
        # per-phase tick time is exported as counters
        assert pre + "tick_decode_dispatch_seconds_total" in series

        conn.request("GET", "/healthz")
        health = json.loads(conn.getresponse().read())
        assert health["status"] == "serving"
        assert health["slots"] == 2                 # compat fields intact
        assert health["uptime_s"] > 0
        assert health["n_ticks"] >= 1
        assert 0.0 <= health["occupancy"] <= 1.0
        assert health["counters"]["requests_finished"] == 3
        conn.close()
    finally:
        server.shutdown()
        server.server_close()
        eng.shutdown()
