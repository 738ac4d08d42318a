"""The readers of the program's own records (``readers/program_records.py``)
on hand-made records: which ticks a median takes, what the host's serial part
spans, which ticks and requests are the window's, which cadence rows count, and
that an empty log, or a program that keeps none, gives ``None``."""

import pytest

from benchmark import spec
from benchmark.readers import program_records as pr

MS = 1e-3


def tick(n, t0, dispatch_ms=3.0, fetch_ms=55.0, rest_ms=1.0, rows=32,
         **phases_ms):
    """A decode tick that starts at ``t0``: the phases given (admit and
    prefill first), then dispatch, fetch and the rest."""
    before = sum(phases_ms.values()) * MS
    t_dispatch = t0 + before + dispatch_ms * MS
    t_fetch = t_dispatch + fetch_ms * MS
    phases = {k: v * MS for k, v in phases_ms.items()}
    phases.update(decode_dispatch=dispatch_ms * MS, host_fetch=fetch_ms * MS,
                  sample_commit=rest_ms * MS)
    return {"tick": n, "t0": t0, "t1": t_fetch + rest_ms * MS,
            "t_dispatch": t_dispatch, "t_fetch": t_fetch, "phases": phases,
            "rows": rows, "n_slots": 32, "admitted": 0, "queue_depth": 0}


def test_a_prefill_tick_is_left_out_of_the_decode_median():
    ticks = [tick(1, 0.0), tick(2, 0.1, fetch_ms=57.0),
             tick(3, 0.2, admit=0.5, prefill=11.0),
             tick(4, 0.3, admit=0.5, prefix_copy=2.0),
             {"tick": 4, "t0": 0.4, "t1": 0.401, "phases": {"admit": 1 * MS},
              "rows": 0, "n_slots": 32, "admitted": 0, "queue_depth": 0}]
    # 59 and 61 ms; not the 70.5 ms prefill tick, the copy tick or the idle
    assert pr.tick_decode_p50_ms(ticks) == pytest.approx(60.0)


def test_the_host_part_spans_two_ticks_less_the_prefill_between():
    a = tick(1, 0.0)
    b = tick(2, a["t1"] + 0.4 * MS, admit=0.6)       # 1 + 0.4 + 0.6 + 3
    c = tick(3, b["t1"] + 0.4 * MS, admit=0.6, prefill=11.0)
    idle = {"tick": 3, "t0": c["t1"], "t1": c["t1"] + MS,
            "phases": {"admit": MS}, "rows": 0, "n_slots": 32}
    d = tick(4, idle["t1"] + 50 * MS)                # after an idle wait
    assert pr.tick_host_p50_ms([a, b]) == pytest.approx(5.0)
    # the prefill program ran on the device meanwhile: not the host's part
    assert pr.tick_host_p50_ms([b, c]) == pytest.approx(5.0)
    # no pair across a tick that ran no decode program
    assert pr.tick_host_p50_ms([a, b, c, idle, d]) == pytest.approx(5.0)
    assert pr.tick_host_p50_ms([c, idle, d]) is None


def test_rows_used_is_useful_rows_over_rows_of_the_program():
    ticks = [tick(1, 0.0, rows=32), tick(2, 0.1, rows=16, prefill=10.0),
             {"tick": 2, "t0": 0.2, "t1": 0.3, "phases": {}, "rows": 0,
              "n_slots": 32}]
    assert pr.decode_rows_used_pct(ticks) == pytest.approx(75.0)


def span(outcome, queued_ms, prefill_ms, t_submit=0.0):
    return {"type": "span", "name": "request", "outcome": outcome,
            "t_submit": t_submit,
            "children": [{"name": "queued", "t0": 0.0,
                          "dur_s": queued_ms * MS},
                         {"name": "prefill", "t0": 0.0,
                          "dur_s": prefill_ms * MS}]}


def test_span_children_of_finished_requests_only():
    spans = [span("length", q, 10.0 + q) for q in range(1, 21)]
    spans += [span("expired", 5000.0, 0.0)]
    assert pr.span_child_ms(spans, "queued", 95) == pytest.approx(19.05)
    assert pr.span_child_ms(spans, "prefill", 50) == pytest.approx(20.5)
    assert pr.span_child_ms(spans, "decode", 95) is None


def test_the_window_is_from_the_first_to_the_last_submit_after_warm_up(
        monkeypatch):
    """Rows arrive in the order the requests ended; the two submitted first
    are the warm-up's. Ticks of the warm-up and of the drain are left out."""
    spans = [span("length", 60.0, 20.0, t_submit=10.0 + i) for i in range(20)]
    spans += [span("length", 900.0, 300.0, t_submit=1.0),      # warm-up
              span("length", 900.0, 300.0, t_submit=1.5),
              {"type": "span", "name": "rpc", "t0": 5.0}]
    spans.reverse()
    requests = pr.requests_of_window(spans)
    assert [r["t_submit"] for r in requests] == [10.0 + i for i in range(20)]
    assert pr.requests_of_window(spans[:3]) == []      # the warm-up alone
    ticks = ([tick(i, 1.0 + 0.1 * i, fetch_ms=80.0, rows=1)
              for i in range(4)]                                # warm-up
             + [tick(10 + i, 10.0 + 0.5 * i) for i in range(19)]
             + [tick(40 + i, 29.5 + 0.5 * i, fetch_ms=40.0, rows=2)
                for i in range(30)])                            # the drain
    mine = pr.ticks_of_window(ticks, requests)
    assert [t["tick"] for t in mine] == list(range(10, 29))
    assert pr.ticks_of_window(ticks, []) == []
    kinds = {"span": spans, "tick": ticks}
    monkeypatch.setattr(pr, "recent", lambda kind: kinds[kind])
    assert pr.read({"stat": "tick_decode_p50_ms"}, {}) == pytest.approx(59.0)
    assert pr.read({"stat": "decode_rows_used_pct"}, {}) == pytest.approx(100)
    assert pr.read({"stat": "span_child_ms", "child": "queued",
                    "percentile": 100}, {}) == pytest.approx(60.0)
    assert pr.decode_rows_used_pct(ticks) < 50.0     # all of them would read
    # a closed loop's last requests are in flight when the engine stops and
    # leave no row: the window runs on to the last tick that admitted one
    ticks[25]["admitted"] = 1
    assert [t["tick"] for t in pr.ticks_of_window(ticks, requests)] == (
        list(range(10, 29)) + [40, 41, 42])


def test_cadence_rows_of_the_check_and_warm_steps_are_left_out():
    rows = [{"step": 10, "steps_in_window": 10, "data_wait_s": 5.0},
            {"step": 20, "steps_in_window": 10, "data_wait_s": 0.001},
            {"step": 30, "steps_in_window": 10, "data_wait_s": 0.003},
            {"step": 32, "tick_total_s": 1.0}]       # an engine's row
    assert pr.cadence_segment_ms(rows, "data_wait", 9) == pytest.approx(0.2)
    assert pr.cadence_segment_ms(rows, "data_wait", 31) is None


def test_an_empty_log_reads_none(monkeypatch):
    from building_llm_from_scratch_tpu.obs import configure_metrics

    configure_metrics(None)
    cell = spec.load_cell("serve_gpt2_1p5b_chat")
    train = spec.load_cell("train_gpt2_124m_pretrain")
    new = [m for m in cell.per_layer + train.per_layer
           if m["reader"] == "program_records"]
    assert len(new) == 6
    for metric in new:
        ctx = {"cell": train if "train" in metric["name"] else cell,
               "peaks": None, "trace": None, "window": {}}
        assert pr.read(metric, ctx) is None, metric["name"]
    # a program whose hub has no such buffer (the parent commit's) reads
    # the same, and does not raise
    from building_llm_from_scratch_tpu.obs import metrics

    monkeypatch.setattr(metrics, "get_metrics", lambda: object())
    assert all(pr.read(m, {"cell": train}) is None for m in new)
