"""Perf-observatory tests (obs/perf.py + scripts/perf_gate.py):
BenchResult schema round-trip, structural-fingerprint determinism, the
two gate modes (structural fires on injected recompiles / FLOP growth
with the offending program named; timing is silent across identical
reruns but fires on an injected 1.5x slowdown), the trajectory store,
the bench runner end-to-end, and the summarize_metrics --compare view the gate's diagnosis reuses."""

import io
import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

from building_llm_from_scratch_tpu.obs import CompileWatcher
from building_llm_from_scratch_tpu.obs import perf

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPTS = os.path.join(REPO_ROOT, "scripts")


def _capture_fingerprint(fn, *args, label="prog"):
    """Compile ``fn`` for ``args`` under a fresh CompileWatcher inside a
    fresh collector; returns the fingerprint."""
    watcher = CompileWatcher(jax.jit(fn), label=label)
    with perf.FingerprintCollector() as col:
        watcher(*args)
    return col.fingerprint()


# ---------------------------------------------------------------------------
# BenchResult schema
# ---------------------------------------------------------------------------

def test_bench_result_roundtrip():
    res = perf.BenchResult(name="toy", metric="toy tokens/sec", value=123.4,
                           unit="tokens/sec", detail={"arm": {"x": 1}},
                           time=1700000000.0)
    res.add_metric("mfu", 0.41, "fraction")
    res.repeats = perf.repeat_stats([120.0, 123.4, 125.0])
    res.env = perf.bench_env()
    row = json.loads(json.dumps(res.to_row()))
    assert perf.validate_row(row) == []
    back = perf.BenchResult.from_row(row)
    assert back.name == "toy" and back.value == 123.4
    assert back.metric_value("mfu") == 0.41
    assert back.repeats["n"] == 3
    assert back.env["jax_version"] == jax.__version__
    # the env block carries what the ISSUE demands of a comparable number
    for key in ("backend", "device_kind", "device_count", "argv", "mesh"):
        assert key in back.env, key


def test_validate_row_rejects_malformed():
    assert perf.validate_row({"type": "bench"})  # missing everything
    good = perf.BenchResult(name="t", metric="m", value=1.0).to_row()
    bad = dict(good, value="fast")
    assert any("value" in p for p in perf.validate_row(bad))
    bad = dict(good, metrics={"mfu": 0.4})        # not {value, unit}
    assert any("metrics" in p for p in perf.validate_row(bad))
    newer = dict(good, perf_schema_version=perf.PERF_SCHEMA_VERSION + 1)
    assert any("newer" in p for p in perf.validate_row(newer))
    with pytest.raises(ValueError):
        perf.BenchResult.from_row({"type": "bench"})


def test_repeat_stats_math():
    st = perf.repeat_stats([10.0, 30.0, 20.0])
    assert st["n"] == 3 and st["min"] == 10.0 and st["median"] == 20.0
    assert st["mean"] == 20.0 and st["stddev"] == 10.0
    assert perf.repeat_stats([5.0])["stddev"] == 0.0


def test_bench_result_event_is_schema_registered():
    from building_llm_from_scratch_tpu.obs.schema import validate_event

    assert validate_event("bench_result", {
        "name": "micro_train", "metric": "m", "value": 1.0,
        "unit": "tokens/sec", "n_repeats": 2, "quick": True,
        "fingerprint_sha": "ab" * 32}) == []


# ---------------------------------------------------------------------------
# Structural fingerprints
# ---------------------------------------------------------------------------

def test_fingerprint_byte_identical_across_identical_runs():
    x = jnp.ones((32, 32), jnp.float32)
    fp1 = _capture_fingerprint(lambda a: (a @ a).sum(), x)
    fp2 = _capture_fingerprint(lambda a: (a @ a).sum(), x)
    blob1 = json.dumps(perf.structural_part(fp1), sort_keys=True)
    blob2 = json.dumps(perf.structural_part(fp2), sort_keys=True)
    assert blob1 == blob2
    assert perf.fingerprint_digest(fp1) == perf.fingerprint_digest(fp2)
    assert fp1["n_programs"] == 1 and fp1["n_recompiles"] == 0
    prog = fp1["programs"][0]
    assert prog["label"] == "prog" and prog["flops"] > 0
    assert perf.compare_structural(fp1, fp2) == []


def test_structural_gate_fires_on_forced_recompile():
    """An injected recompile (second arg signature after the legitimate
    one) must fail the structural gate, not just log."""
    base = _capture_fingerprint(lambda a: (a @ a).sum(),
                                jnp.ones((32, 32), jnp.float32))
    watcher = CompileWatcher(jax.jit(lambda a: (a @ a).sum()), label="prog")
    with perf.FingerprintCollector() as col:
        watcher(jnp.ones((32, 32), jnp.float32))
        watcher(jnp.ones((16, 16), jnp.float32))   # forced recompile
    fresh = col.fingerprint()
    assert fresh["n_recompiles"] == 1
    findings = perf.compare_structural(base, fresh)
    kinds = {f["kind"] for f in findings}
    assert "recompiles" in kinds and "program_count" in kinds
    rec = next(f for f in findings if f["kind"] == "recompiles")
    assert "prog" in rec["detail"]           # the offending program named


def test_structural_gate_fires_on_flop_increase():
    """Same arg signature, more FLOPs (an extra matmul slipped into the
    step): the finding names the program and carries the delta."""
    x = jnp.ones((32, 32), jnp.float32)
    base = _capture_fingerprint(lambda a: (a @ a).sum(), x)
    fresh = _capture_fingerprint(lambda a: (a @ a @ a).sum(), x)
    findings = perf.compare_structural(base, fresh)
    flops = [f for f in findings if f["kind"] == "flops_delta"]
    assert flops, findings
    assert flops[0]["program"] == "prog"
    assert flops[0]["fresh"] > flops[0]["base"]
    assert "prog" in flops[0]["detail"]
    # and the clean direction still holds
    assert perf.compare_structural(base, base) == []


def test_structural_gate_reports_new_and_removed_programs():
    x = jnp.ones((8, 8), jnp.float32)
    one = _capture_fingerprint(lambda a: (a @ a).sum(), x, label="p1")
    watcher1 = CompileWatcher(jax.jit(lambda a: (a @ a).sum()), label="p1")
    watcher2 = CompileWatcher(jax.jit(lambda a: a.sum()), label="p2")
    with perf.FingerprintCollector() as col:
        watcher1(x)
        watcher2(x)
    both = col.fingerprint()
    kinds = {f["kind"]: f for f in perf.compare_structural(one, both)}
    assert "new_program" in kinds and kinds["new_program"]["program"] == "p2"
    kinds_rev = {f["kind"]: f
                 for f in perf.compare_structural(both, one)}
    assert kinds_rev["removed_program"]["program"] == "p2"


def test_bucket_leak_names_the_stray_variant():
    """A label that GROWS a signature variant while keeping the baselined
    ones (the prefill bucket-leak scenario) must name the stray variant,
    not collapse it into a bare program-count delta."""
    x8 = jnp.ones((8, 8), jnp.float32)
    x16 = jnp.ones((16, 16), jnp.float32)
    base = _capture_fingerprint(lambda a: (a @ a).sum(), x8,
                                label="prefill")
    watcher = CompileWatcher(jax.jit(lambda a: (a @ a).sum()),
                             label="prefill", multi_program=True)
    with perf.FingerprintCollector() as col:
        watcher(x8)
        watcher(x16)            # the leaked bucket
    fresh = col.fingerprint()
    findings = perf.compare_structural(base, fresh)
    leak = [f for f in findings if f["kind"] == "new_program_variant"]
    assert len(leak) == 1 and leak[0]["program"] == "prefill"
    assert "prefill" in leak[0]["detail"]
    # and the reverse direction: the lost variant is named too
    rev = perf.compare_structural(fresh, base)
    gone = [f for f in rev if f["kind"] == "removed_program_variant"]
    assert len(gone) == 1 and gone[0]["program"] == "prefill"


def test_signature_change_pairs_programs_and_reports_flops():
    x32 = jnp.ones((32, 32), jnp.float32)
    x64 = jnp.ones((64, 64), jnp.float32)
    base = _capture_fingerprint(lambda a: (a @ a).sum(), x32)
    fresh = _capture_fingerprint(lambda a: (a @ a).sum(), x64)
    findings = perf.compare_structural(base, fresh)
    sig = [f for f in findings if f["kind"] == "arg_signature_changed"]
    assert len(sig) == 1 and sig[0]["program"] == "prog"
    assert "flops" in sig[0]["detail"]       # the delta rides along


# ---------------------------------------------------------------------------
# Timing mode
# ---------------------------------------------------------------------------

def _timing_row(values):
    row = perf.BenchResult(name="t", metric="m",
                           value=values[-1], unit="tok/s").to_row()
    row["repeats"] = perf.repeat_stats(values)
    return row


def test_timing_gate_silent_across_identical_reruns():
    base = _timing_row([100.0, 101.0, 99.5])
    for _ in range(5):                       # k identical reruns: no fire
        fresh = _timing_row([100.2, 99.8, 100.9])
        assert perf.compare_timing(base, fresh) is None


def test_timing_gate_fires_on_injected_slowdown():
    base = _timing_row([100.0, 101.0, 99.5])
    slow = _timing_row([66.0, 67.0, 66.5])   # 1.5x slowdown
    finding = perf.compare_timing(base, slow)
    assert finding is not None
    assert finding["kind"] == "timing_regression"
    assert finding["ratio"] < 0.7
    assert "noise floor" in finding["detail"]
    # faster is never a regression
    fast = _timing_row([150.0, 151.0, 149.0])
    assert perf.compare_timing(base, fast) is None


def test_timing_noise_floor_scales_with_stddev():
    noisy_base = _timing_row([100.0, 140.0, 60.0])   # huge variance
    dip = _timing_row([80.0, 82.0, 81.0])
    # a 20% dip inside 4 sigma of a 40-stddev baseline must NOT fire
    assert perf.compare_timing(noisy_base, dip) is None


# ---------------------------------------------------------------------------
# Trajectory store
# ---------------------------------------------------------------------------

def test_trajectory_store_roundtrip(tmp_path):
    store = perf.TrajectoryStore(str(tmp_path / "perf"))
    res = perf.BenchResult(name="toy", metric="m", value=10.0,
                           time=1700000000.0)
    store.append(res)
    store.append(perf.BenchResult(name="toy", metric="m", value=12.0,
                                  time=1700000100.0))
    rows = store.load("toy")
    assert [r["value"] for r in rows] == [10.0, 12.0]
    assert store.names() == ["toy"]
    with pytest.raises(ValueError):
        store.append({"type": "bench", "name": "toy"})  # invalid row


def test_trajectory_tolerates_header_rows(tmp_path):
    """A trajectory file created via ``bench.py --json <file>.jsonl``
    starts with a header row; load() filters it and the report renders
    the bench rows instead of KeyErroring on the header."""
    store = perf.TrajectoryStore(str(tmp_path))
    os.makedirs(store.root, exist_ok=True)
    with open(store.path("toy"), "w") as f:
        f.write(json.dumps(perf.header_row()) + "\n")
        f.write(json.dumps(perf.BenchResult(
            name="toy", metric="m", value=5.0,
            time=1700000000.0).to_row()) + "\n")
    rows = store.load("toy")
    assert len(rows) == 1 and rows[0]["value"] == 5.0
    out = io.StringIO()
    assert perf.render_trajectory(store, out=out) == 1


def test_compare_structural_finding_iff_digest_differs():
    """The exact-match contract: zero findings iff the structural digests
    are equal — including the recompile-labels-drift edge where the
    counts match but the victims differ."""
    base = {"programs": [], "n_programs": 0, "n_recompiles": 1,
            "recompile_labels": ["decode"]}
    fresh = {"programs": [], "n_programs": 0, "n_recompiles": 1,
             "recompile_labels": ["prefill"]}
    assert perf.fingerprint_digest(base) != perf.fingerprint_digest(fresh)
    findings = perf.compare_structural(base, fresh)
    assert findings and any("decode" in f["detail"] for f in findings)
    assert perf.compare_structural(base, dict(base)) == []


# ---------------------------------------------------------------------------
# Bench runner end-to-end (micro bench on the debug model)
# ---------------------------------------------------------------------------

def test_run_bench_micro_train_schema_and_fingerprint():
    sys.path.insert(0, REPO_ROOT)
    try:
        import bench
    finally:
        sys.path.remove(REPO_ROOT)
    res = bench.run_bench("micro_train", repeats=2, quick=True)
    row = json.loads(json.dumps(res.to_row()))
    assert perf.validate_row(row) == []
    assert row["repeats"]["n"] == 2 and len(row["repeats"]["values"]) == 2
    assert row["env"]["jax_version"] == jax.__version__
    assert row["env"]["backend"] == "cpu"
    assert row["quick"] is True
    fp = row["fingerprint"]
    progs = [p for p in fp["programs"] if p["label"] == "bench_step"]
    assert progs and progs[0]["flops"] > 0
    assert fp["n_recompiles"] == 0
    assert fp["stable_across_repeats"] is True


def test_json_out_extensionless_path_is_a_directory(tmp_path):
    """``--json results/perf`` (no trailing slash, dir absent) must get
    the trajectory layout, not a FILE named like the trajectory dir."""
    sys.path.insert(0, REPO_ROOT)
    try:
        import bench
    finally:
        sys.path.remove(REPO_ROOT)
    target = str(tmp_path / "results" / "perf")      # extensionless
    f = bench._open_json_out(target, "toy")
    f.close()
    assert os.path.isdir(target)
    assert os.path.exists(os.path.join(target, "toy.jsonl"))
    file_target = str(tmp_path / "out.jsonl")        # explicit file
    f = bench._open_json_out(file_target, "toy")
    f.close()
    assert os.path.isfile(file_target)
    rows = [json.loads(line) for line in open(file_target)]
    assert rows and rows[0]["type"] == "header"


def test_perf_report_path_is_jax_free(tmp_path):
    """perf_gate --report/--backfill must run without importing jax (the
    stdlib-only promise obs/perf.py makes for the pure-compare paths)."""
    import subprocess

    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.argv = ['perf_gate.py', '--report']; "
         f"sys.path.insert(0, {SCRIPTS!r}); import perf_gate; "
         "perf_gate.main(['--report']); "
         "assert 'jax' not in sys.modules, 'jax imported'"],
        capture_output=True, text=True, cwd=REPO_ROOT)
    assert proc.returncode == 0, proc.stderr
    assert "perf trajectory" in proc.stdout


# ---------------------------------------------------------------------------
# The gate script itself (API-level, tmp baseline)
# ---------------------------------------------------------------------------

@pytest.fixture()
def perf_gate():
    sys.path.insert(0, SCRIPTS)
    sys.path.insert(0, REPO_ROOT)
    try:
        import perf_gate as pg
        yield pg
    finally:
        sys.path.remove(SCRIPTS)
        sys.path.remove(REPO_ROOT)


def test_perf_gate_end_to_end(perf_gate, tmp_path, monkeypatch, capsys):
    """--update-baseline (with a reason) -> structural gate passes; an
    injected per-program FLOP drift in the baseline -> rc 1 with the
    program named; --update-baseline without a reason refuses."""
    baseline = str(tmp_path / "PERF_BASELINE.json")
    monkeypatch.setattr(perf_gate, "BASELINE_JSONL_DIR",
                        str(tmp_path / "baseline_jsonl"))
    # no reason -> refusal before any bench runs
    assert perf_gate.main(["--update-baseline", "--baseline", baseline,
                           "--benches", "micro_train"]) == 2
    assert perf_gate.main(["--update-baseline", "--baseline", baseline,
                           "--benches", "micro_train",
                           "--reason", "test baseline"]) == 0
    data = json.load(open(baseline))
    assert data["updates"][-1]["reason"] == "test baseline"
    assert "micro_train" in data["benches"]
    assert data["benches"]["micro_train"]["fingerprint"]["programs"]
    # identical code -> structural gate green
    assert perf_gate.main(["--baseline", baseline]) == 0
    out = capsys.readouterr().out
    assert "perf gate ok: micro_train" in out
    # injected FLOP regression in the baseline -> gate fires, names it
    data["benches"]["micro_train"]["fingerprint"]["programs"][0][
        "flops"] *= 2
    with open(baseline, "w") as f:
        json.dump(data, f)
    assert perf_gate.main(["--baseline", baseline]) == 1
    out = capsys.readouterr().out
    assert "flops_delta" in out and "bench_step" in out
    # unknown bench name -> explicit refusal, not a KeyError
    assert perf_gate.main(["--baseline", baseline,
                           "--benches", "nope"]) == 2
    # a baseline entry whose bench no longer exists in bench.BENCHES
    # (renamed without re-baselining) -> clean rc-2 refusal, no KeyError
    data["benches"]["renamed_away"] = data["benches"].pop("micro_train")
    with open(baseline, "w") as f:
        json.dump(data, f)
    assert perf_gate.main(["--baseline", baseline]) == 2
    out = capsys.readouterr().out
    assert "renamed_away" in out and "re" in out.lower()


# ---------------------------------------------------------------------------
# summarize_metrics --compare (the gate's telemetry-diff view)
# ---------------------------------------------------------------------------

def test_compare_runs_on_fixture(capsys):
    sys.path.insert(0, SCRIPTS)
    try:
        import summarize_metrics
    finally:
        sys.path.remove(SCRIPTS)
    fixture = os.path.join(REPO_ROOT, "tests", "fixtures",
                           "metrics_fixture.jsonl")
    result = summarize_metrics.compare_runs(fixture, fixture)
    out = capsys.readouterr().out
    assert "A/B compare" in out
    assert "train step segments" in out
    # identical files -> identical stats, zero deltas
    a, b = result["a"], result["b"]
    assert a["train_segments_s_per_step"] == b["train_segments_s_per_step"]
    assert "+0.0%" in out
