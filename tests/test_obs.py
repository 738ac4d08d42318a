"""Observability subsystem tests (obs/): JSONL schema round-trip, MFU
analytic-FLOPs math, the step timeline's non-step exclusion, the stall
detector, the no-per-step-host-sync invariant, and the CPU smoke run
acceptance case (main() + --metrics_jsonl)."""

import json
import os
import threading
import time

import jax
import numpy as np
import pytest

from building_llm_from_scratch_tpu.configs import get_config
from building_llm_from_scratch_tpu.data import ByteTokenizer, PretrainLoader
from building_llm_from_scratch_tpu.models import init_params
from building_llm_from_scratch_tpu.obs import (
    MetricLogger,
    StallDetector,
    StepTimeline,
    compute_mfu,
    configure_metrics,
    device_peak_flops,
    emit_event,
    flops_per_token,
    format_mfu,
    get_metrics,
    window_stats,
)
from building_llm_from_scratch_tpu.training import Trainer


def read_rows(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


@pytest.fixture()
def global_sink(tmp_path):
    """Route the process-global sink to a tmp JSONL for one test, restoring
    the no-op sink afterwards so tests stay isolated."""
    path = str(tmp_path / "metrics.jsonl")
    logger = configure_metrics(path, run_metadata={"test": True})
    yield logger, path
    configure_metrics(None)


def tiny_cfg():
    # same fast fixture shape as test_resilience: real train steps, tiny
    # compiles
    return get_config("GPT2", "124M", debug=True).replace(
        emb_dim=32, hidden_dim=64, n_layers=2, n_heads=2, vocab_size=257,
        context_length=16)


def make_trainer(tmp_path, params, **kw):
    tok = ByteTokenizer()
    loader = PretrainLoader(tok, batch_size=2, max_length=16)
    defaults = dict(output_dir=str(tmp_path / "out"), eval_freq=4,
                    print_sample_iter=100000, save_ckpt_freq=100000,
                    warmup_steps=2, show_progress=False)
    defaults.update(kw)
    return Trainer(tiny_cfg(), params, tok, loader, **defaults)


# ---------------------------------------------------------------------------
# JSONL schema round-trip
# ---------------------------------------------------------------------------

def test_jsonl_schema_roundtrip(tmp_path):
    path = str(tmp_path / "m.jsonl")
    lg = MetricLogger(path)
    lg.write_header(jax_version="0.0", device_kind="test", device_count=1)
    lg.count("widgets", 2)
    lg.gauge("hbm", 123)
    lg.timing("data_wait", 0.25)
    lg.timing("data_wait", 0.25)
    lg.log_metrics(5, lr=1e-3, tok_s=100.0)
    lg.event("checkpoint_save", step=5, bytes=42, seconds=0.1)
    lg.log_metrics(10, lr=2e-3, tok_s=200.0, train_loss=float("nan"))
    lg.close()

    rows = read_rows(path)
    assert [r["type"] for r in rows] == ["header", "metrics", "event",
                                        "metrics"]
    from building_llm_from_scratch_tpu.obs.metrics import SCHEMA_VERSION

    header = rows[0]
    assert header["schema_version"] == SCHEMA_VERSION
    assert header["device_kind"] == "test"
    m1, ev, m2 = rows[1], rows[2], rows[3]
    # timings drained into the first row only, counters/gauges attached
    assert m1["data_wait_s"] == pytest.approx(0.5)
    assert "data_wait_s" not in m2
    assert m1["widgets"] == 2 and m1["hbm"] == 123
    assert ev["event"] == "checkpoint_save" and ev["bytes"] == 42
    # monotonically increasing step across metric rows
    steps = [r["step"] for r in rows if r["type"] == "metrics"]
    assert steps == sorted(steps) == [5, 10]
    # non-finite values stay parseable (stringified, not bare NaN)
    assert isinstance(m2["train_loss"], str)


def test_pre_header_rows_buffer_until_header(tmp_path):
    """Events fired before the run metadata exists (build-time fetches)
    must land AFTER the header line, not before or nowhere."""
    path = str(tmp_path / "m.jsonl")
    lg = MetricLogger(path)
    lg.event("hf_fetch", repo="x/y")
    assert not os.path.exists(path)          # buffered, not written
    lg.write_header(device_kind="test")
    rows = read_rows(path)
    assert [r["type"] for r in rows] == ["header", "event"]
    assert rows[1]["event"] == "hf_fetch"
    lg.close()


def test_jsonl_rotates_previous_run_file(tmp_path):
    """One run = one file: a --resume relaunch reusing the same path must
    rotate the killed run's telemetry aside, not append a second header
    mid-file / restart the monotone step sequence."""
    path = str(tmp_path / "m.jsonl")
    lg = MetricLogger(path)
    lg.write_header(run=1)
    lg.log_metrics(90, lr=1.0)
    lg.close()
    lg2 = MetricLogger(path)
    lg2.write_header(run=2)
    lg2.log_metrics(5, lr=2.0)               # restarts below the old 90
    lg2.close()
    rows = read_rows(path)
    assert [r["type"] for r in rows] == ["header", "metrics"]
    assert rows[0]["run"] == 2 and rows[1]["step"] == 5
    prev = read_rows(path + ".1")
    assert prev[0]["run"] == 1 and prev[1]["step"] == 90


def test_closed_sink_never_reopens_or_rotates(tmp_path):
    """A write after close() (stall-detector thread firing during
    teardown) must not reopen the path — reopening would rotate the
    COMPLETED run's artifact aside for one stray row."""
    path = str(tmp_path / "m.jsonl")
    lg = MetricLogger(path)
    lg.write_header(run=1)
    lg.log_metrics(1, lr=0.1)
    lg.close()
    lg.event("stall")                        # dropped, not written
    assert not os.path.exists(path + ".1")
    assert [r["type"] for r in read_rows(path)] == ["header", "metrics"]


def test_noop_sink_counts_but_never_writes(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    lg = MetricLogger(None)
    lg.event("stall")
    lg.log_metrics(1, lr=0.1)
    assert lg.counters["event:stall"] == 1
    assert list(tmp_path.iterdir()) == []


def test_global_sink_emit_event(global_sink):
    logger, path = global_sink
    assert get_metrics() is logger
    emit_event("custom", step=3, detail="x")
    rows = read_rows(path)
    assert rows[0]["type"] == "header"
    assert rows[-1]["event"] == "custom" and rows[-1]["step"] == 3


# ---------------------------------------------------------------------------
# MFU math
# ---------------------------------------------------------------------------

def test_flops_per_token_matches_hand_computation():
    cfg = tiny_cfg()
    # hand-computed for this exact config (GPT-2 shape: qkv_bias=False from
    # debug replace of the base config, biased out-proj/MLP/norms):
    d, v, t, L, f = 32, 257, 16, 2, 64
    qkv = d * d + 2 * d * d                   # wq + wk,wv (n_kv == n_heads)
    attn_out = d * d + d                      # biased out proj
    mlp = 2 * d * f + (f + d)                 # biased in/out linears
    norms = 2 * (2 * d)                       # 2 biased layernorms
    per_layer = qkv + attn_out + mlp + norms
    n_matmul = per_layer * L + 2 * d + d * v  # + final norm + head
    expected = 6 * n_matmul + 12 * L * d * t
    assert cfg.num_params(exclude_embeddings=True) == n_matmul
    assert flops_per_token(cfg) == expected
    # seq_len override scales only the attention term
    assert flops_per_token(cfg, seq_len=2 * t) - flops_per_token(cfg) == (
        12 * L * d * t)


def test_device_peak_flops_table():
    class FakeDev:
        def __init__(self, kind):
            self.device_kind = kind

    assert device_peak_flops(FakeDev("TPU v4")) == 275e12
    assert device_peak_flops(FakeDev("TPU v5 lite")) == 197e12
    assert device_peak_flops(FakeDev("TPU v5p")) == 459e12
    assert device_peak_flops(FakeDev("cpu")) is None
    # the CPU test backend reports n/a, not a made-up number
    assert device_peak_flops() is None
    assert format_mfu(None) == "MFU n/a"
    assert format_mfu(0.414) == "41.4% MFU"


def test_compute_mfu_against_explicit_peak():
    cfg = tiny_cfg()
    per_tok = flops_per_token(cfg)
    mfu = compute_mfu(1000.0, cfg, n_devices=2, peak=1e12)
    assert mfu == pytest.approx(1000.0 * per_tok / 2e12)
    assert compute_mfu(1000.0, cfg, n_devices=1, peak=None) is None
    assert compute_mfu(0.0, cfg, n_devices=1, peak=1e12) is None


# ---------------------------------------------------------------------------
# Timeline
# ---------------------------------------------------------------------------

def test_timeline_spans_accumulate_and_drain():
    tl = StepTimeline()
    with tl.span("data_wait"):
        time.sleep(0.01)
    with tl.step_span(1):
        pass
    with tl.step_span(2):
        pass
    with tl.span("eval"):
        time.sleep(0.01)
    win = tl.drain()
    assert win["data_wait"] >= 0.01 and win["eval"] >= 0.01
    assert win["steps"] == 2 and "dispatch" in win
    assert tl.drain() == {"steps": 0}        # reset


def test_window_stats_excludes_non_step_time():
    """The satellite fix: sample/checkpoint/eval time inside the window
    must not deflate tok/s."""
    window = {"data_wait": 0.1, "dispatch": 0.2, "host_fetch": 0.1,
              "eval": 2.0, "sample": 1.0, "checkpoint": 1.0, "steps": 4}
    stats = window_stats(window, elapsed=6.0, tokens=8000)
    # 6s wall - 4s non-step = 2s of training
    assert stats["non_step_seconds"] == pytest.approx(4.0)
    assert stats["tok_s"] == pytest.approx(4000.0)
    assert stats["step_time_s"] == pytest.approx(0.5)
    naive = 8000 / 6.0
    assert stats["tok_s"] > 2 * naive


def test_trainer_throughput_excludes_sample_and_checkpoint_time(tmp_path):
    """Integration: with a deliberately slow sampler firing every 2 steps,
    the reported tok/s must track training time, not wall time."""
    datafile = tmp_path / "c.txt"
    datafile.write_text("a stitch in time saves nine, they say. " * 16)
    cfg = tiny_cfg()
    trainer = make_trainer(tmp_path, init_params(cfg, jax.random.PRNGKey(0)),
                           eval_freq=4, print_sample_iter=2)
    trainer.generate_and_print_sample = lambda *a, **kw: time.sleep(0.3)
    t0 = time.perf_counter()
    trainer.train_model([str(datafile)], n_epochs=1, start_context="a")
    wall = time.perf_counter() - t0
    assert trainer.global_step >= 8
    naive = trainer.tokens_seen / wall
    reported = np.mean(trainer.throughput_tokens_per_s)
    # ~0.15s/step of sample sleep vs ~ms-scale tiny-model steps: without
    # the exclusion `reported` would sit near `naive`; with it, far above
    assert reported > 2 * naive, (reported, naive)


# ---------------------------------------------------------------------------
# No new per-step host synchronization (acceptance)
# ---------------------------------------------------------------------------

def test_no_per_step_host_fetch_in_train_loop(tmp_path):
    """Device metric scalars must be fetched ONLY at cadence (the
    _flush_metrics discipline): wrap every step's lr in a guard that
    records the trainer step at which it is converted to a host value."""
    datafile = tmp_path / "c.txt"
    datafile.write_text("pack my box with five dozen liquor jugs. " * 12)
    cfg = tiny_cfg()
    trainer = make_trainer(tmp_path, init_params(cfg, jax.random.PRNGKey(0)),
                           eval_freq=4)
    fetch_steps = []

    class GuardedScalar:
        def __init__(self, val):
            self._val = val

        def copy_to_host_async(self):
            pass

        def __array__(self, dtype=None, copy=None):
            fetch_steps.append(trainer.global_step)
            out = np.asarray(self._val)
            return out.astype(dtype) if dtype is not None else out

    real_setup = trainer._setup

    def guarded_setup(total_steps):
        real_setup(total_steps)
        real_step = trainer.train_step

        def step(state, batch):
            state, metrics = real_step(state, batch)
            # guard the per-layer-group health arrays too: they ride the
            # same deferred-fetch discipline as lr (cadence-only)
            health = {k: GuardedScalar(v)
                      for k, v in metrics["health"].items()}
            return state, dict(metrics, lr=GuardedScalar(metrics["lr"]),
                               health=health)

        trainer.train_step = step

    trainer._setup = guarded_setup
    trainer.train_model([str(datafile)], n_epochs=1, start_context="a")
    assert trainer.global_step >= 8
    assert fetch_steps, "lr metrics were never flushed"
    allowed = {s for s in range(0, trainer.global_step + 1, 4)}
    allowed.add(trainer.global_step)         # final flush in `finally`
    assert set(fetch_steps) <= allowed, (
        f"host fetch outside cadence: {sorted(set(fetch_steps) - allowed)}")
    # and the lr trajectory still arrived intact
    assert len(trainer.track_lrs) == trainer.global_step
    # health made it to the host at cadence (not never-fetched)
    assert trainer._last_health is not None
    assert len(trainer._health_names) == len(
        np.asarray(trainer._last_health["grad_norm"]))


# ---------------------------------------------------------------------------
# Compile telemetry (obs/compile.py)
# ---------------------------------------------------------------------------

def _tiny_step_state_batch(bs=2):
    from building_llm_from_scratch_tpu.training import (
        build_optimizer,
        init_train_state,
        make_train_step,
    )

    cfg = tiny_cfg().replace(drop_rate=0.0)
    opt = build_optimizer(total_steps=10)
    state = init_train_state(init_params(cfg, jax.random.PRNGKey(0)), opt,
                             jax.random.PRNGKey(1))
    step = make_train_step(cfg, opt, lr_schedule=lambda s: 1e-3)
    rng = np.random.default_rng(0)
    T = cfg.context_length
    batch = {
        "inputs": rng.integers(0, cfg.vocab_size, (bs, T)).astype(np.int32),
        "targets": rng.integers(0, cfg.vocab_size, (bs, T)).astype(np.int32),
        "weights": np.ones((bs, T), np.float32),
    }
    return step, state, batch


def test_compile_watcher_captures_cost_and_memory(global_sink):
    """First call AOT-compiles and emits ONE compile event with nonzero
    compile seconds, HLO-counted FLOPs and the HBM breakdown; steady-state
    same-signature calls stay silent (no recompiles, no new events)."""
    from building_llm_from_scratch_tpu.obs import CompileWatcher

    _, path = global_sink
    step, state, batch = _tiny_step_state_batch()
    w = CompileWatcher(step, label="test_step")
    for _ in range(3):
        state, metrics = w(state, batch)
    assert w.n_compiles == 1 and w.n_recompiles == 0
    assert w.hlo_flops_per_step and w.hlo_flops_per_step > 0
    assert w.hlo_flops_per_token == pytest.approx(
        w.hlo_flops_per_step / batch["inputs"].size)
    compiles = [r for r in read_rows(path) if r.get("event") == "compile"]
    assert len(compiles) == 1
    ev = compiles[0]
    assert ev["label"] == "test_step"
    assert ev["compile_seconds"] > 0
    assert ev["flops"] > 0
    assert ev["tokens_per_step"] == batch["inputs"].size
    mem = ev["memory"]
    assert mem["args_bytes"] > 0 and mem["temp_bytes"] >= 0
    assert mem["total_bytes"] > 0
    assert not any(r.get("event") == "recompile" for r in read_rows(path))
    # the step result is the real one (executable actually ran)
    assert np.isfinite(float(metrics["loss"]))


def _stub_aot(monkeypatch, flops=1000.0):
    """Replace the real XLA compile with a stub so watcher-LOGIC tests
    (recompile keying, cache counting) don't pay ~5s of compile each —
    the end-to-end AOT path is covered once by
    test_compile_watcher_captures_cost_and_memory."""
    import building_llm_from_scratch_tpu.obs.compile as obs_compile

    def fake_aot(fn, state, batch):
        return (lambda s, b: (s, {"loss": np.float32(0.0)})), {
            "compile_seconds": 0.01, "lower_seconds": 0.005,
            "backend_compile_seconds": 0.005, "flops": flops,
            "executable_device_count": 1,
            "memory": {"args_bytes": 1, "temp_bytes": 2, "total_bytes": 3}}

    monkeypatch.setattr(obs_compile, "aot_compile", fake_aot)


def test_compile_watcher_detects_recompile_with_shape_diff(global_sink,
                                                           monkeypatch):
    """A changed batch signature fires a recompile event naming the exact
    leaf shape diff — the silent-TPU-perf-bug detector."""
    from building_llm_from_scratch_tpu.obs import CompileWatcher

    _, path = global_sink
    _stub_aot(monkeypatch)
    w = CompileWatcher(lambda s, b: None, label="test_step")
    state = {"x": np.zeros((3,), np.float32)}
    batch2 = {"inputs": np.zeros((2, 16), np.int32)}
    batch4 = {"inputs": np.zeros((4, 16), np.int32)}
    state, _ = w(state, batch2)
    state, _ = w(state, batch2)                  # steady state: silent
    state, _ = w(state, batch4)
    assert w.n_compiles == 2 and w.n_recompiles == 1
    rows = read_rows(path)
    rec = [r for r in rows if r.get("event") == "recompile"]
    assert len(rec) == 1
    leaves = {d["leaf"] for d in rec[0]["diff"]}
    assert "inputs" in leaves
    diff = next(d for d in rec[0]["diff"] if d["leaf"] == "inputs")
    assert diff["was"]["shape"][0] == 2 and diff["now"]["shape"][0] == 4
    assert len([r for r in rows if r.get("event") == "compile"]) == 2


def test_compile_watcher_cache_hit_miss_counting(global_sink, tmp_path,
                                                 monkeypatch):
    """--compile_cache_dir telemetry: a compile that writes no new cache
    entries into a warm dir reports a hit; an empty dir reports a miss."""
    from building_llm_from_scratch_tpu.obs import CompileWatcher

    _, path = global_sink
    _stub_aot(monkeypatch)
    batch = {"inputs": np.zeros((2, 16), np.int32)}
    warm = tmp_path / "warm_cache"
    warm.mkdir()
    (warm / "jit_step-abc123-cache").write_bytes(b"x")
    w = CompileWatcher(lambda s, b: None, cache_dir=str(warm))
    w({"x": np.zeros(2)}, batch)
    ev = [r for r in read_rows(path) if r.get("event") == "compile"][-1]
    assert ev["cache_dir"] == str(warm)
    assert ev["cache_entries"] == 1 and ev["cache_hit"] is True

    cold = tmp_path / "cold_cache"
    cold.mkdir()
    w2 = CompileWatcher(lambda s, b: None, cache_dir=str(cold))
    w2({"x": np.zeros(2)}, batch)
    ev2 = [r for r in read_rows(path) if r.get("event") == "compile"][-1]
    assert ev2["cache_hit"] is False


def test_compile_watcher_falls_back_on_unloweable_step(global_sink):
    """Telemetry must never take down the run: a step without .lower()
    (or whose AOT path raises) delegates to the wrapped callable and emits
    a compile_fallback event."""
    from building_llm_from_scratch_tpu.obs import CompileWatcher

    _, path = global_sink
    calls = []

    def plain_step(state, batch):                  # no .lower attribute
        calls.append(1)
        return state, {"loss": 0.0}

    w = CompileWatcher(plain_step, label="plain")
    state, m = w({"x": np.zeros(2)}, {"inputs": np.zeros((2, 4))})
    assert m["loss"] == 0.0 and len(calls) == 1
    assert w._disabled
    w(state, {"inputs": np.zeros((2, 4))})         # stays delegated
    assert len(calls) == 2
    events = [r.get("event") for r in read_rows(path)]
    assert "compile_fallback" in events
    assert "compile" not in events


def test_aot_cost_analysis_globalized_over_devices():
    """cost_analysis() reports the PER-DEVICE SPMD module; aot_compile must
    scale it by the executable's device span so mfu_hlo (global FLOPs /
    global tokens) is right on multi-chip runs, not just single-chip."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from building_llm_from_scratch_tpu.obs.compile import (
        aot_compile,
        executable_device_count,
    )

    a = jax.numpy.ones((64, 128))
    b = jax.numpy.ones((128, 32))
    c1, s1 = aot_compile(jax.jit(lambda a, b: a @ b), a, b)
    assert executable_device_count(c1) == 1
    assert s1["executable_device_count"] == 1
    assert "flops_per_device" not in s1

    n = len(jax.devices())
    assert n == 8, "conftest forces an 8-device CPU platform"
    mesh = Mesh(np.array(jax.devices()).reshape(n), ("data",))
    sharded = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())
    f8 = jax.jit(lambda a, b: a @ b, in_shardings=(sharded, rep),
                 out_shardings=sharded)
    c8, s8 = aot_compile(f8, jax.device_put(a, sharded),
                         jax.device_put(b, rep))
    assert s8["executable_device_count"] == n
    # per-device module counted 1/n of the work; stats carry the GLOBAL sum
    assert s8["flops_per_device"] == pytest.approx(s1["flops"] / n, rel=0.01)
    assert s8["flops"] == pytest.approx(s1["flops"], rel=0.01)


def test_signature_diff_names_changed_leaves():
    from building_llm_from_scratch_tpu.obs.compile import (
        signature_diff,
        tree_signature,
    )

    a = tree_signature({"x": np.zeros((2, 4), np.float32),
                        "y": np.zeros((3,), np.int32)})
    b = tree_signature({"x": np.zeros((8, 4), np.float32),
                        "y": np.zeros((3,), np.int32)})
    diff = signature_diff(a, b)
    assert len(diff) == 1 and diff[0]["leaf"] == "x"
    assert diff[0]["was"]["shape"] == [2, 4]
    assert diff[0]["now"]["shape"] == [8, 4]
    assert signature_diff(a, a) == []


def test_watchdog_halt_names_offending_layer(global_sink):
    """The trainer wires obs/health's digest as the watchdog context: the
    halt event + diagnostic name the first non-finite layer group."""
    from building_llm_from_scratch_tpu.training.resilience import (
        LossWatchdog,
        TrainingDivergedError,
    )

    _, path = global_sink
    wd = LossWatchdog(context_fn=lambda: {
        "first_nonfinite_group": "block_01",
        "top_grad_norm_groups": [{"group": "block_01", "grad_norm": 12.5}]})
    with pytest.raises(TrainingDivergedError, match="block_01"):
        wd.observe(7, float("nan"))
    halt = next(r for r in read_rows(path)
                if r.get("event") == "watchdog_halt")
    assert halt["first_nonfinite_group"] == "block_01"
    assert halt["top_grad_norm_groups"][0]["group"] == "block_01"
    # a broken context provider must not mask the halt itself
    wd2 = LossWatchdog(context_fn=lambda: 1 / 0)
    with pytest.raises(TrainingDivergedError):
        wd2.observe(8, float("inf"))
    # nor may a context key that collides with the event's own kwargs
    # (reason/recent/step) turn the halt into a TypeError
    wd3 = LossWatchdog(context_fn=lambda: {
        "reason": "shadow", "step": 0, "first_nonfinite_group": "head"})
    with pytest.raises(TrainingDivergedError):
        wd3.observe(9, float("nan"))
    halts = [r for r in read_rows(path) if r.get("event") == "watchdog_halt"]
    assert halts[-1]["reason"] == "non_finite"       # event kwarg wins
    assert halts[-1]["first_nonfinite_group"] == "head"


# ---------------------------------------------------------------------------
# utils/logging.py satellite: process-0 INFO gating + level semantics
# ---------------------------------------------------------------------------

def _capture_logger(name, **kw):
    import io
    import logging as pylogging

    from building_llm_from_scratch_tpu.utils.logging import setup_logger

    lg = setup_logger(name, **kw)
    stream = io.StringIO()
    # swap the stdout handler's stream so records (post-filter) are
    # observable; the coordinator filter lives on the handler
    lg.handlers[0].stream = stream
    return lg, stream


def test_logging_non_coordinator_gates_info(monkeypatch):
    """The docstring always promised process-0 INFO gating; now it exists:
    below-WARNING records drop on non-coordinator processes unless
    BLLM_LOG_ALL_HOSTS is set."""
    lg, stream = _capture_logger("test_obs.gating")
    monkeypatch.delenv("BLLM_LOG_ALL_HOSTS", raising=False)
    monkeypatch.setattr(jax.distributed, "is_initialized", lambda: True)
    monkeypatch.setattr(jax, "process_index", lambda: 3)
    lg.info("invisible info")
    lg.warning("visible warning")
    monkeypatch.setenv("BLLM_LOG_ALL_HOSTS", "1")
    lg.info("debug override info")
    out = stream.getvalue()
    assert "invisible info" not in out
    assert "visible warning" in out
    assert "debug override info" in out
    monkeypatch.setattr(jax, "process_index", lambda: 0)
    lg.info("coordinator info")
    assert "coordinator info" in stream.getvalue()


def test_logging_repeat_call_respects_level():
    import logging as pylogging

    from building_llm_from_scratch_tpu.utils.logging import setup_logger

    lg = setup_logger("test_obs.levels", level=pylogging.INFO)
    assert lg.level == pylogging.INFO
    # a repeat DEFAULT call must not clobber the explicit level...
    assert setup_logger("test_obs.levels").level == pylogging.INFO
    # ...but a repeat EXPLICIT call is respected
    assert setup_logger("test_obs.levels",
                        level=pylogging.ERROR).level == pylogging.ERROR
    # and a fresh logger still defaults to DEBUG
    assert setup_logger("test_obs.fresh").level == pylogging.DEBUG


# ---------------------------------------------------------------------------
# Stall detector
# ---------------------------------------------------------------------------

def test_stall_detector_fires_on_blocked_loop():
    import io
    import logging

    fired = threading.Event()
    det = StallDetector(timeout=0.3, poll_interval=0.05, first_grace=1.0,
                        on_stall=lambda e, t: fired.set())
    # obs loggers don't propagate (utils/logging.py), so attach a capture
    # handler directly instead of caplog
    stream = io.StringIO()
    handler = logging.StreamHandler(stream)
    stall_logger = logging.getLogger("building_llm_from_scratch_tpu.obs.stall")
    stall_logger.addHandler(handler)
    try:
        with det:
            det.notify_step()                # arm, then... nothing: "hang"
            assert fired.wait(3.0), "stall detector never fired"
    finally:
        stall_logger.removeHandler(handler)
    assert det.stall_count == 1
    text = stream.getvalue()
    assert "STALL" in text
    # the dump names THIS (blocked) thread's stack
    assert "test_stall_detector_fires_on_blocked_loop" in text
    assert "Device memory stats" in text


def test_stall_detector_fires_on_first_step_hang():
    """start() must arm the detector: a run that wedges in its very FIRST
    step (first collective / data pipeline / compile) still dumps, after
    first_grace x the threshold."""
    fired = threading.Event()
    det = StallDetector(timeout=0.2, poll_interval=0.05, first_grace=2.0,
                        on_stall=lambda e, t: fired.set())
    with det:                                # never notify_step
        assert det.threshold() == pytest.approx(0.4)   # grace applied
        assert fired.wait(3.0), "never fired on a first-step hang"
    assert det.stall_count == 1


def test_stall_detector_silent_on_healthy_loop():
    det = StallDetector(timeout=0.5, poll_interval=0.05, first_grace=1.0)
    with det:
        for _ in range(20):
            det.notify_step()
            time.sleep(0.02)
    assert det.stall_count == 0


def test_stall_detector_rearms_per_episode():
    """One dump per stall episode: no repeat dumps while still hung, a new
    dump after recovery + a second hang."""
    det = StallDetector(timeout=0.2, poll_interval=0.02, first_grace=1.0)
    with det:
        det.notify_step()
        time.sleep(0.6)                      # episode 1: several polls
        assert det.stall_count == 1
        det.notify_step()                    # recover
        time.sleep(0.6)                      # episode 2
    assert det.stall_count == 2


def test_stall_check_race_guard_keeps_detector_armed():
    """A heartbeat landing between _check's read and its fired-flag set
    must not mark the NEW gap as already-fired (that would permanently
    silence the detector for intermittent stalls)."""
    det = StallDetector(timeout=0.1, poll_interval=0.01, first_grace=1.0)
    det._last = time.monotonic() - 1.0       # wedged for 1s
    real_threshold = det.threshold

    def racy_threshold():
        det.notify_step()                    # stall ends mid-check
        return real_threshold()

    det.threshold = racy_threshold
    det._check()
    assert det.stall_count == 0              # stale gap: no dump...
    assert not det._fired_for_current_gap    # ...and the new gap is armed
    det.threshold = real_threshold
    det._last = time.monotonic() - 1.0       # wedges again
    det._check()
    assert det.stall_count == 1


def test_stall_threshold_median_adaptive_with_floor():
    """Fast steps tighten the threshold below a huge timeout, but never
    below the floor — one loop iteration legitimately stretches past
    10x the median step when cadence work (first-compile eval, checkpoint
    save) runs, and that must not read as a stall (seen live: a 2s first
    eval fired a 10 * 150ms threshold)."""
    det = StallDetector(timeout=600.0, factor=10.0, median_floor=30.0)
    det._last = 0.0
    det._intervals = [0.15] * 20             # 150ms steps
    assert det.threshold() == pytest.approx(30.0)   # floored, not 1.5s
    det._intervals = [5.0] * 20              # slow steps: adaptive wins
    assert det.threshold() == pytest.approx(50.0)
    det._intervals = [90.0] * 20             # timeout is still the cap
    assert det.threshold() == pytest.approx(600.0)
    det._intervals = []                      # pre-first-step: compile grace
    assert det.threshold() == pytest.approx(600.0 * det.first_grace)


def test_stall_detector_rejects_zero_timeout():
    with pytest.raises(ValueError, match="timeout"):
        StallDetector(timeout=0)


def test_stall_event_reaches_sink(global_sink, tmp_path):
    _, path = global_sink
    det = StallDetector(timeout=0.2, poll_interval=0.05, first_grace=1.0)
    with det:
        det.notify_step()
        deadline = time.monotonic() + 3.0
        while det.stall_count == 0 and time.monotonic() < deadline:
            time.sleep(0.05)
    events = [r for r in read_rows(path) if r["type"] == "event"]
    assert any(e["event"] == "stall" for e in events)


# ---------------------------------------------------------------------------
# CPU smoke run (acceptance): main() + --metrics_jsonl
# ---------------------------------------------------------------------------

def test_cli_smoke_metrics_jsonl(tmp_path):
    """A CPU-run main() with --metrics_jsonl produces a parseable JSONL:
    run-metadata header first, per-cadence loss/lr/tok-s/step-time/memory
    rows, and structured events (checkpoint_save, run_complete)."""
    from building_llm_from_scratch_tpu.args import get_args
    from building_llm_from_scratch_tpu.main import main

    d = tmp_path / "data"
    d.mkdir()
    (d / "corpus.txt").write_text(
        "Every effort moves you closer to mastery. " * 80)
    out = str(tmp_path / "out")
    jsonl = os.path.join(out, "metrics.jsonl")
    try:
        trainer = main(get_args([
            "--data_dir", str(d), "--output_dir", out, "--debug",
            "--byte_tokenizer", "--n_epochs", "1", "--batch_size", "8",
            "--eval_freq", "10", "--log_every", "5",
            "--print_sample_iter", "10000", "--save_ckpt_freq", "15",
            "--warmup_steps", "2", "--metrics_jsonl", jsonl]))
    finally:
        configure_metrics(None)              # detach the global sink
    assert trainer.global_step >= 15

    rows = read_rows(jsonl)                  # every line parses
    assert rows[0]["type"] == "header"
    header = rows[0]
    assert header["jax_version"] == jax.__version__
    assert header["device_count"] == len(jax.devices())
    assert header["model"]["name"] == "gpt2-124M"
    assert header["flags"]["batch_size"] == 8
    assert "argv" in header and "mesh_shape" in header

    metrics = [r for r in rows if r["type"] == "metrics"]
    assert metrics, "no metric rows"
    steps = [r["step"] for r in metrics]
    assert steps == sorted(steps)            # monotonically increasing
    # --log_every 5 decoupled from --eval_freq 10: rows at 5, 10, 15, ...
    assert 5 in steps and 10 in steps
    for r in metrics:
        assert r["lr"] is not None and r["tok_s"] > 0
        assert r["step_time_s"] is not None
        assert r["host_rss_bytes"] > 0
        # pre-clip grad norm + post-clip update norm (derived from the
        # health bundle) surface in every metrics row
        assert r["grad_norm"] > 0 and r["update_norm"] > 0
    # loss only on eval-cadence rows
    eval_rows = [r for r in metrics if r["step"] % 10 == 0]
    assert eval_rows and all(
        np.isfinite(r["train_loss"]) and np.isfinite(r["val_loss"])
        for r in eval_rows)
    log_only = [r for r in metrics if r["step"] % 10 and r["step"] % 5 == 0]
    assert log_only and all("train_loss" not in r for r in log_only)

    events = {r["event"] for r in rows if r["type"] == "event"}
    assert "checkpoint_save" in events
    assert "run_complete" in events
    ckpt = next(r for r in rows if r.get("event") == "checkpoint_save")
    assert ckpt["bytes"] > 0 and ckpt["seconds"] > 0

    # compile telemetry (acceptance): exactly ONE compile event — nonzero
    # compile seconds, HLO cost-analysis FLOPs, a memory breakdown — and
    # ZERO recompiles across the fixed-shape run
    compiles = [r for r in rows if r.get("event") == "compile"]
    assert len(compiles) == 1, [r.get("event") for r in rows
                                if r["type"] == "event"]
    ev = compiles[0]
    assert ev["compile_seconds"] > 0
    assert ev["flops"] > 0
    assert ev["memory"]["total_bytes"] > 0
    assert ev["tokens_per_step"] == 8 * trainer.cfg.context_length
    assert not [r for r in rows if r.get("event") == "recompile"]

    # health rows (acceptance): per-layer-group arrays at the log cadence
    health = [r for r in rows if r["type"] == "health"]
    assert health, "no health rows"
    groups = health[0]["groups"]
    assert [g for g in groups if g.startswith("block_")]
    for r in health:
        assert r["groups"] == groups
        for key in ("grad_norm", "param_norm", "update_norm",
                    "update_ratio"):
            assert len(r[key]) == len(groups)
            assert all(np.isfinite(v) for v in r[key])
        assert r["first_nonfinite"] is None
    hsteps = [r["step"] for r in health]
    assert hsteps == sorted(hsteps) and 5 in hsteps and 10 in hsteps
