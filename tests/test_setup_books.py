"""The books of set-up (obs/compile.py, obs/timeline.py): one ``program``
record a program the process builds, watched or not, with its tracing, its
lowering and its cache load or compile apart and the cache's verdict by
JAX's own events; one ``setup`` record an engine or trainer, whose spans
nest and whose self seconds sum to its wall; an operator's reading of both
in ``stats()``, ``/healthz`` and the ``serve_warmup`` event. And what the
books may not touch: the tick, the watcher's hit path, the trainer's step
loop."""

import hashlib
import inspect
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from building_llm_from_scratch_tpu.data import ByteTokenizer, PretrainLoader
from building_llm_from_scratch_tpu.models import init_params
from building_llm_from_scratch_tpu.obs import (
    CompileWatcher,
    SetupTimeline,
    aot_compile,
    configure_metrics,
    get_metrics,
    program_table,
    setup_line,
)
from building_llm_from_scratch_tpu.obs import schema
from building_llm_from_scratch_tpu.obs.compile import keep_program_books
from building_llm_from_scratch_tpu.serving.engine import DecodeEngine
from building_llm_from_scratch_tpu.serving.request import SamplingParams
from building_llm_from_scratch_tpu.training import Trainer
from tests.test_obs import read_rows, tiny_cfg as train_cfg
from tests.test_serving import tiny_cfg


@pytest.fixture()
def hub():
    """A fresh memory-only hub for one test: no record of another test's
    programs, and none of this test's left behind."""
    keep_program_books()
    yield configure_metrics(None)
    configure_metrics(None)


@pytest.fixture()
def engine(hub):
    cfg = tiny_cfg()
    return DecodeEngine(cfg, init_params(cfg, jax.random.PRNGKey(0)), None,
                        n_slots=2, warmup_prompt_cap=32)


def watched(records=None):
    rows = get_metrics().recent("program") if records is None else records
    return [r for r in rows if r["watched"]]


def test_warmup_leaves_one_watched_record_a_program(engine):
    t0 = time.perf_counter()
    engine.warmup()
    wall = time.perf_counter() - t0
    records = watched()
    # one prefill program a bucket, then the tick's
    assert [r["label"] for r in records] == (
        ["serve_prefill"] * len(engine.prompt_buckets()) + ["serve_decode"])
    for r in records:
        assert set(r) <= set(schema.PROGRAM_RECORD_FIELDS)
        assert min(r["trace_s"], r["lower_s"], r["load_or_compile_s"]) >= 0
        assert r["cache"] == "off"          # the suite keeps the cache off
    ends = [r["t_end"] for r in records]
    assert ends == sorted(ends) and t0 < ends[0] and ends[-1] < t0 + wall
    assert sum(r["trace_s"] + r["lower_s"] + r["load_or_compile_s"]
               for r in records) < wall


def test_a_program_met_again_is_not_built(engine):
    engine.warmup()
    n = len(get_metrics().recent("program"))
    engine.warmup()                          # every signature is known
    engine.submit([3, 4, 5], SamplingParams(max_new_tokens=4))
    engine.run_until_idle()
    assert len(watched()) == len(watched(get_metrics().recent("program")[:n]))


def test_a_program_no_watcher_wraps_is_booked_under_its_fun_name(hub):
    x = jnp.ones((3, 7, 11), jnp.float32)
    jax.block_until_ready(x)
    before = len(get_metrics().recent("program"))
    jax.block_until_ready(x.astype(jnp.float16))    # eager: no watcher
    new = get_metrics().recent("program")[before:]
    assert [r["label"] for r in new] == ["jit(convert_element_type)"]
    rec = new[0]
    assert rec["watched"] is False and rec["trace_s"] is None
    assert rec["lower_s"] > 0 and rec["load_or_compile_s"] > 0
    assert rec["thread"] == "MainThread"
    jax.block_until_ready(x.astype(jnp.float16))    # met again: not built
    assert len(get_metrics().recent("program")) == before + 1


def test_a_second_build_of_the_same_text_reads_cache_hit(hub, tmp_path):
    from jax.experimental.compilation_cache import compilation_cache

    saved = {k: getattr(jax.config, k) for k in (
        "jax_enable_compilation_cache", "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    try:
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        compilation_cache.reset_cache()
        x = np.ones((5, 13), np.float32)
        verdicts = []
        for _ in range(2):          # two fresh functions of one text
            _, stats = aot_compile(jax.jit(lambda a: jnp.tanh(a) * 3 + 1), x)
            verdicts.append(stats["cache"])
        assert verdicts == ["miss", "hit"]
        assert stats["cache_retrieval_seconds"] >= 0
        w = CompileWatcher(jax.jit(lambda a: jnp.tanh(a) * 3 + 1),
                           label="same_text")
        w(x)
        rec = watched()[-1]
        assert (rec["label"], rec["cache"]) == ("same_text", "hit")
        assert "retrieval_s" in rec
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


def test_the_compile_event_keeps_lower_seconds_and_gains_trace_seconds(
        tmp_path):
    path = str(tmp_path / "m.jsonl")
    configure_metrics(path, run_metadata={"test": True})
    try:
        w = CompileWatcher(jax.jit(lambda a: a @ a.T), label="evented")
        w(np.ones((4, 6), np.float32))
        ev = [r for r in read_rows(path) if r.get("event") == "compile"][-1]
        assert 0 <= ev["trace_seconds"] <= ev["lower_seconds"]
        assert ev["compile_seconds"] == pytest.approx(
            ev["lower_seconds"] + ev["backend_compile_seconds"], abs=1e-3)
        assert ev["cache"] == "off"
        assert schema.validate_event("compile", ev) == []
        # a record is memory-only: no row of the file holds one
        assert not [r for r in read_rows(path) if r["type"] == "program"]
        (t0, t1), = w.capture_stamps
        assert t1 - t0 >= ev["compile_seconds"] - 1e-3
    finally:
        configure_metrics(None)


def check_nesting(record):
    """Every span lies inside the nearest span above it of lesser depth,
    and self seconds, the root's with them, sum to the wall."""
    open_spans = []
    for s in record["spans"]:
        del open_spans[s["depth"]:]
        if open_spans:
            parent = open_spans[-1]
            assert parent["depth"] == s["depth"] - 1
            assert parent["t0"] <= s["t0"]
            assert (s["t0"] + s["dur_s"]
                    <= parent["t0"] + parent["dur_s"] + 1e-9)
        else:
            assert s["depth"] == 0 and s["t0"] >= record["t0"]
        open_spans.append(s)
    total = record["self_s"] + sum(s["self_s"] for s in record["spans"])
    assert total == pytest.approx(record["wall_s"], abs=1e-6)


def test_the_setup_record_nests_and_its_self_times_sum_to_its_wall(engine):
    engine.warmup()
    engine.start()
    try:
        record, = get_metrics().recent("setup")
    finally:
        engine.shutdown(drain=False)
    assert set(record) <= set(schema.SETUP_RECORD_FIELDS)
    assert record["source"] == "serve"
    names = [(s["name"], s["depth"]) for s in record["spans"]]
    builds = [("build:serve_prefill", 1)] * len(engine.prompt_buckets())
    assert names == ([("init", 0), ("cache_alloc", 1), ("weights_layout", 1),
                      ("warmup", 0)] + builds
                     + [("build:serve_decode", 1), ("first_runs", 1),
                        ("start", 0)])
    assert tuple(n for n, d in names if d == 0) == schema.SETUP_PHASES[
        "serve"]
    check_nesting(record)
    assert abs(record["time"] - time.time()) < 600
    # its twin for the trace export: one span row, the spans flattened
    row, = [r for r in get_metrics().recent("span") if r["name"] == "setup"]
    assert [c["name"] for c in row["children"]] == [n for n, _ in names]
    assert row["dur_s"] == pytest.approx(record["wall_s"], abs=1e-5)
    assert "setup" in schema.SPAN_NAMES


def test_the_setup_record_is_handed_over_once(engine):
    engine.warmup()
    engine.start()
    engine.shutdown(drain=False)
    engine.start()
    engine.shutdown(drain=False)
    assert len(get_metrics().recent("setup")) == 1


def test_an_operator_reads_the_books_from_stats_healthz_and_the_event(
        tmp_path):
    path = str(tmp_path / "m.jsonl")
    configure_metrics(path, run_metadata={"test": True})
    keep_program_books()
    try:
        cfg = tiny_cfg()
        eng = DecodeEngine(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                           None, n_slots=2, warmup_prompt_cap=16)
        eng.warmup()
        for books in (eng.stats()["setup"], eng.healthz_payload()["setup"]):
            phases = [s["name"] for s in books["record"]["spans"]
                      if s["depth"] == 0]
            assert phases == ["init", "warmup"]       # not started yet
            table = books["programs"]
            assert {"serve_prefill", "serve_decode"} <= {
                p["label"] for p in table}
            assert all(set(p) == {"label", "trace_s", "lower_s",
                                  "load_or_compile_s", "cache"}
                       for p in table)
        ev = [r for r in read_rows(path)
              if r.get("event") == "serve_warmup"][-1]
        assert ev["seconds"] > 0
        assert [p["label"] for p in ev["programs"]
                if p["trace_s"] is not None] == ["serve_prefill",
                                                 "serve_decode"]
        assert schema.validate_event("serve_warmup", ev) == []
        line = setup_line(**eng.stats()["setup"])
        assert "init" in line and "2 watched programs" in line
        assert "cache {'off'" in line
    finally:
        configure_metrics(None)


def test_the_trainer_books_init_the_steps_build_and_its_first_runs(
        hub, tmp_path):
    datafile = tmp_path / "c.txt"
    datafile.write_text("a stitch in time saves nine, they say. " * 16)
    cfg = train_cfg()
    tok = ByteTokenizer()
    # what an earlier test of this process built would be served from JAX's
    # own caches and leave no record here: the constructor's programs are
    # booked when they are built
    jax.clear_caches()
    trainer = Trainer(cfg, init_params(cfg, jax.random.PRNGKey(0)), tok,
                      PretrainLoader(tok, batch_size=2, max_length=16),
                      output_dir=str(tmp_path / "out"), eval_freq=4,
                      print_sample_iter=10 ** 6, save_ckpt_freq=10 ** 6,
                      warmup_steps=2, show_progress=False)
    trainer.train_model([str(datafile)], n_epochs=1, start_context="a")
    assert trainer.global_step > 4 and trainer._setup_tl is None
    record, = get_metrics().recent("setup")         # one, whatever the fetches
    assert record["source"] == "train"
    assert tuple(s["name"] for s in record["spans"]) == schema.SETUP_PHASES[
        "train"]
    check_nesting(record)
    init, build, first = record["spans"]
    step, = [r for r in watched() if r["label"] == "train_step"]
    assert build["t0"] < step["t_end"] <= build["t0"] + build["dur_s"] + 1e-3
    assert first["t0"] == pytest.approx(build["t0"] + build["dur_s"])
    assert step["trace_s"] + step["lower_s"] + step[
        "load_or_compile_s"] <= build["dur_s"]
    # programs the constructor builds itself are booked inside `init`
    assert any(init["t0"] <= r["t_end"] <= init["t0"] + init["dur_s"]
               for r in get_metrics().recent("program"))


def test_a_setup_timeline_books_a_span_from_its_stamps():
    tl = SetupTimeline()
    with tl.span("init"):
        with tl.span("inner"):
            time.sleep(0.002)
    t1 = time.perf_counter()
    tl.book("late", t1, t1 + 0.5)
    record = tl.record("train")
    assert [(s["name"], s["depth"]) for s in record["spans"]] == [
        ("init", 0), ("inner", 1), ("late", 0)]
    check_nesting(record)
    assert record["spans"][0]["self_s"] < record["spans"][0]["dur_s"]
    assert tl.record("train")["wall_s"] == record["wall_s"]   # not drained
    assert SetupTimeline().record("serve") is None


#: sha256 of the source of what the books may not touch, at the parent
#: commit (fcf8daa): the tick, the watcher's call (its hit path with it),
#: the trainer's step loop. ``_tick`` as PR 45 left it: its
#: ``state_rows_touched`` is what the state step did touch
#: (``state_rows_walked``), no other line moved
HOT_PATHS = {
    "DecodeEngine.step":
        "a81839718718eba117a43a4d7417f9f077bdd5de6db5318f1ef303635f5174eb",
    "DecodeEngine._tick":
        "40cbacba0a5d26ff76caceb2df075f4836440e50be86454bb1e96a921bcfe5ba",
    "DecodeEngine._chunk_tick":
        "dd541c60773592879a1b37813e2e0c64132cf2fb1888647b8736b6dcf957fa05",
    "CompileWatcher.__call__":
        "0336144460e7627efd382d6e8203607ac8aaa52e2b4b8931228883bb429dce6b",
    "Trainer._epoch_steps":
        "927b9c8d7599e5277c1a584781564e14c4996550e62bef1ff64881d756813d2d",
}


@pytest.mark.parametrize("name", sorted(HOT_PATHS))
def test_the_hot_paths_read_as_they_did_at_the_parent(name):
    cls, method = name.split(".")
    fn = getattr({"DecodeEngine": DecodeEngine, "Trainer": Trainer,
                  "CompileWatcher": CompileWatcher}[cls], method)
    assert hashlib.sha256(
        inspect.getsource(fn).encode()).hexdigest() == HOT_PATHS[name]


def test_the_registry_knows_the_new_kinds_and_fields():
    assert schema.SCHEMA_VERSION >= 17
    assert {"label", "t_end", "time", "trace_s", "lower_s",
            "load_or_compile_s", "cache", "watched"} <= set(
        schema.PROGRAM_RECORD_FIELDS)
    assert {"t0", "wall_s", "self_s", "spans"} <= set(
        schema.SETUP_RECORD_FIELDS)
    assert {"trace_seconds", "lower_seconds", "cache", "cache_hit"} <= set(
        schema.EVENTS["compile"].optional)
    assert "programs" in schema.EVENTS["serve_warmup"].optional
    assert schema.SETUP_PHASES["train"][1].startswith(
        schema.SETUP_BUILD_PREFIX)
    assert program_table([{"label": "x", "trace_s": 1.0, "lower_s": 2.0,
                           "load_or_compile_s": 3.0, "cache": "hit",
                           "watched": True, "t_end": 0.0}]) == [
        {"label": "x", "trace_s": 1.0, "lower_s": 2.0,
         "load_or_compile_s": 3.0, "cache": "hit"}]
