"""``readers/setup_records.py`` on records a test writes, and on the books a
whole CPU run of a tiny cell leaves (``run_cell``): nothing built after the
window opened is counted, the serving books close on the harness's own
items by construction, all four metrics read a number in a serving cell and
in the train cell, and a program that keeps no books reads ``None``."""

import json

import pytest

from benchmark import run, spec
from benchmark.readers import setup_records
from benchmark.tests.tiny import tiny_cell

SEED = 3_000_000_019
STATS = ("setup_trace_lower_s", "setup_load_or_compile_s", "setup_init_s",
         "setup_first_runs_s")


def program(label, t_end, trace_s=None, lower_s=0.0, load=0.0, **kw):
    return dict({"label": label, "t_end": t_end, "time": 1e9 + t_end,
                 "trace_s": trace_s, "lower_s": lower_s,
                 "load_or_compile_s": load, "cache": "hit",
                 "watched": trace_s is not None, "thread": "MainThread"},
                **kw)


def span(name, t0, dur_s, depth=0):
    return {"name": name, "t0": t0, "dur_s": dur_s, "self_s": dur_s,
            "depth": depth}


def request(t_submit, dur_s):
    return {"type": "span", "name": "request", "t_submit": t_submit,
            "dur_s": dur_s}


#: an engine built at 10 s, warmed from 12 to 17, started at 17; the two warm
#: requests are done by 17.5 and the window's first request comes at 18
SERVE_SETUP = {"source": "serve", "t0": 10.0, "t_end": 17.01,
               "time": 1e9 + 10, "wall_s": 7.01, "self_s": 0.0,
               "spans": [span("init", 10.0, 2.0), span("warmup", 12.0, 5.0),
                         span("build:serve_decode", 12.0, 4.0, 1),
                         span("start", 17.0, 0.01)]}
SERVE_SPANS = [request(17.1, 0.2), request(17.2, 0.3), request(18.0, 9.0),
               request(19.0, 5.0)]
SERVE_PROGRAMS = [
    program("jit(make_params)", 9.0, lower_s=0.5, load=3.0),     # harness
    program("jit(dynamic_slice)", 11.0, lower_s=0.1, load=0.2),  # in init
    program("serve_prefill_chunk", 14.0, trace_s=0.5, lower_s=0.7, load=1.5),
    program("serve_decode", 16.0, trace_s=0.4, lower_s=0.6, load=0.9),
    program("jit(_threefry_seed)", 17.3, lower_s=0.01, load=0.05,
            thread="engine"),
    program("reference_pass", 90.0, trace_s=2.0, lower_s=3.0, load=40.0),
    program("jit(reference_eager)", 91.0, lower_s=1.0, load=2.0)]


def test_nothing_built_after_the_window_opened_is_counted():
    books = setup_records.serve_books(SERVE_PROGRAMS, [SERVE_SETUP],
                                      SERVE_SPANS)
    assert books["setup_trace_lower_s"] == pytest.approx(0.5 + 0.7 + 0.4 + 0.6)
    assert books["setup_load_or_compile_s"] == pytest.approx(1.5 + 0.9 + 0.05)
    assert [row[0] for row in books["watched"]] == ["serve_prefill_chunk",
                                                    "serve_decode"]
    # the reference's programs are in the hub and in none of the sums; built
    # during warm-up they would be in them
    late = setup_records.serve_books(
        [dict(p, t_end=p["t_end"] - 75) if "reference" in p["label"] else p
         for p in SERVE_PROGRAMS], [SERVE_SETUP], SERVE_SPANS)
    assert late["setup_load_or_compile_s"] > 40


def test_the_serving_books_close_on_warmup_and_first_requests():
    books = setup_records.serve_books(SERVE_PROGRAMS, [SERVE_SETUP],
                                      SERVE_SPANS)
    # warm-up's wall, and from start() to the end of the last warm request
    wall = 5.0 + (17.5 - 17.0)
    assert sum(books[s] for s in STATS if s != "setup_init_s") == (
        pytest.approx(wall))
    assert books["setup_init_s"] == 2.0
    assert books["setup_first_runs_s"] == pytest.approx(wall - 2.2 - 2.45)
    # what the constructor built is inside `init`; the harness's own
    # (make_params, before any phase) is counted nowhere and said beside
    assert books["inside_init"] == {"n": 1, "lower_and_load_s": 0.3}
    assert books["outside_the_phases"] == {"n": 1, "lower_and_load_s": 3.5}
    assert books["unwatched"]["n"] == 1 and books["cache"] == {"hit": 2}


def test_the_train_books_leave_out_the_harness_thread_and_the_reference():
    setup = {"source": "train", "t0": 10.0, "t_end": 20.0, "time": 1e9 + 10,
             "wall_s": 10.0, "self_s": 0.5,
             "spans": [span("init", 10.0, 1.5),
                       span("build:train_step", 12.0, 6.0),
                       span("first_runs", 18.0, 2.0)]}
    programs = [
        program("jit(_copy)", 11.0, lower_s=0.1, load=0.3),      # in init
        program("train_step", 17.9, trace_s=1.0, lower_s=0.8, load=4.0),
        program("jit(<lambda>)", 18.5, lower_s=0.2, load=0.6,
                thread="train-prefetch"),                        # harness
        program("jit(convert_element_type)", 18.7, lower_s=0.01, load=0.02),
        program("reference_steps", 95.0, trace_s=1.0, lower_s=2.0, load=30.0)]
    rows = [{"type": "metrics", "step": 10, "steps_in_window": 10,
             "time": 1e9 + 20}, {"type": "metrics", "step": 600,
                                 "steps_in_window": 10, "time": 1e9 + 70}]
    books = setup_records.train_books(programs, [setup], rows)
    assert books["setup_trace_lower_s"] == pytest.approx(1.8)
    assert books["setup_load_or_compile_s"] == pytest.approx(4.02)
    assert (books["setup_init_s"], books["setup_first_runs_s"]) == (1.5, 2.0)
    assert books["unwatched"]["n"] == 1
    assert books["outside_the_phases"]["n"] == 1      # the harness's norms


def test_a_program_that_keeps_no_books_reads_none(monkeypatch):
    assert setup_records.serve_books([], [], SERVE_SPANS) is None
    assert setup_records.serve_books(SERVE_PROGRAMS, [SERVE_SETUP], []) is None
    assert setup_records.train_books([], [], []) is None
    monkeypatch.setattr(setup_records, "recent", lambda kind: [])
    cell = spec.load_cell("serve_gpt2_1p5b_chat")
    for stat in STATS:
        assert setup_records.read({"stat": stat}, {"cell": cell}) is None


def test_each_metric_lists_the_six_cells_and_one_layer():
    for name in [w["name"] for w in json.load(open(
            spec.ROOT + "/BENCHMARK.json"))["workloads"]][:6]:
        metrics = {m["name"]: m for m in spec.load_cell(name).per_layer
                   if m["reader"] == "setup_records"}
        assert sorted(metrics) == sorted(STATS)
        assert {m["moves"] for m in metrics.values()} == {"setup_s"}
        assert len({m["layer"] for m in metrics.values()}) == 1


def read_all(cell):
    return {m["stat"]: setup_records.read(m, {"cell": cell})
            for m in cell.per_layer if m["reader"] == "setup_records"}


def said_books(capsys):
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()
             if line.startswith("{")]
    items = dict(next(d["setup_items"] for d in lines if "setup_items" in d))
    return next(d["setup_books"] for d in lines if "setup_books" in d), items


@pytest.fixture()
def fresh_hub():
    from building_llm_from_scratch_tpu.obs.metrics import configure_metrics

    configure_metrics(None)
    yield
    configure_metrics(None)


def test_all_four_read_in_the_tiny_serving_cell_and_close(fresh_hub, capsys):
    cell = tiny_cell("serve_gpt2_1p5b_chat")
    result = run.run_cell(cell, SEED, 2.0, False, None)
    assert result["correct"]
    values = read_all(cell)
    assert sorted(values) == sorted(STATS)
    assert all(v is not None and v >= 0 for v in values.values()), values
    books, items = said_books(capsys)
    # the harness's own stopwatch agrees with the program's books
    assert sum(values[s] for s in STATS if s != "setup_init_s") == (
        pytest.approx(items["warmup"] + items["first_requests"], abs=0.2))
    assert values["setup_init_s"] < items["weights_and_cache"]
    # every bucket's prefill program and the tick's, and no reference's
    labels = [row[0] for row in books["watched"]]
    assert set(labels) == {"serve_prefill", "serve_decode"}
    assert values["setup_load_or_compile_s"] < items["warmup"] + items[
        "first_requests"]


def test_all_four_read_in_the_tiny_train_cell(fresh_hub, capsys):
    cell = tiny_cell("train_gpt2_124m_pretrain")
    run.run_cell(cell, SEED, 0.5, False, None)
    values = read_all(cell)
    assert sorted(values) == sorted(STATS)
    assert all(v is not None and v >= 0 for v in values.values()), values
    books, items = said_books(capsys)
    assert [row[0] for row in books["watched"]] == ["train_step"]
    # constructor, the step's build and the first runs lie inside the
    # harness's `first_steps`, which runs on to the last warm step
    assert books["init_to_first_fetch_s"] <= items["first_steps"]
    assert (values["setup_trace_lower_s"] + values["setup_load_or_compile_s"]
            <= books["build_s"] + values["setup_first_runs_s"])
