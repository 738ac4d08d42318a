"""Perf gate: compare a fresh bench run against the checked-in
PERF_BASELINE.json — the CI tripwire that makes perf claims STAY proven.

Two modes:

  - **structural** (default; deterministic on the shared CPU container,
    so CI-safe): the fresh run's structural fingerprint — per-program HLO
    cost-analysis FLOPs, compiled-program count, argument signatures,
    recompile count, HBM breakdown — must match the baseline EXACTLY.
    Timing never enters the comparison, so a noisy neighbor can't flake
    the gate, but a forced recompile, a new compiled program, or FLOP
    growth in the step fails it with the offending program NAMED.
  - **timing** (``--timing``; opt-in, for humans on quiet machines):
    variance-aware comparison of the headline value — fires only when
    the fresh median falls past a noise floor derived from both arms'
    repeat stddev (obs/perf.compare_timing).

On failure the gate prints a differential diagnosis: per-program FLOP
deltas, new/removed programs, memory deltas, and — when both arms have
metrics JSONLs — the step-timeline / tick-phase / latency delta view from
``summarize_metrics.py --compare``. Exit status 1.

Baseline updates require a reason (mirroring analysis/baseline.json's
accepted-debt discipline): a perf baseline is a CLAIM about what the
code compiles to, and changing it is a reviewed decision, never a
silent refresh.

Usage:
  python scripts/perf_gate.py                     # structural gate (CI)
  python scripts/perf_gate.py --timing            # + timing comparison
  python scripts/perf_gate.py --benches micro_train,micro_serve
  python scripts/perf_gate.py --update-baseline --reason "why it changed"
  python scripts/perf_gate.py --report            # perf trajectory table
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

# The gate benches run IN-PROCESS, and micro_longctx needs a multi-
# device host for its seq mesh axis — force 8 CPU devices before any
# jax import (same count the tests and the fleet workers pin; the
# structural fingerprints are device-count-insensitive for the
# single-device benches, and the baseline env records 8).
if "--xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip()

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)
# summarize_metrics (the telemetry-diff view) lives next to this script;
# make it importable when perf_gate is imported as a module (tests)
sys.path.insert(1, os.path.dirname(os.path.abspath(__file__)))


def _load_perf(pure: bool = False):
    """Handle on obs/perf.py. ``pure=True`` loads it by FILE PATH —
    stdlib-only, skipping obs/__init__ and therefore jax (the
    analysis.base.load_schema_module pattern) — for the report
    path, which only reads JSONL. The gate paths import the
    package module instead: they run benches, whose BenchResult objects
    must share class identity with the module comparing them."""
    if pure:
        import importlib.util

        path = os.path.join(REPO_ROOT, "building_llm_from_scratch_tpu",
                            "obs", "perf.py")
        spec = importlib.util.spec_from_file_location("_bllm_perf_pure",
                                                      path)
        mod = importlib.util.module_from_spec(spec)
        # dataclass processing resolves the module through sys.modules
        # (PEP 563 string annotations) — register before exec
        sys.modules[spec.name] = mod
        spec.loader.exec_module(mod)
        return mod
    from building_llm_from_scratch_tpu.obs import perf

    return perf

BASELINE_PATH = os.path.join(REPO_ROOT, "PERF_BASELINE.json")
BASELINE_JSONL_DIR = os.path.join(REPO_ROOT, "results", "perf", "baseline")

#: The default gate benches: debug-size workloads that finish in seconds
#: on CPU (bench.py MICRO_BENCHES). One raw train step, one grad-accum
#: step, one continuous-batching engine run, one fused multi-LoRA step,
#: one speculative (k=4 verify) engine run, one fleet-router run —
#: together they fingerprint the train step builder, the serving
#: engine's whole program family (plain decode AND spec verify tiers),
#: the fused-finetune step, the router path's PER-REPLICA program
#: family (watch_compiles="first": replica-count invariant), and the
#: sequence-sharded ring-attention train step (micro_longctx — the
#: long-context tier, needing the forced 8-device host above).
GATE_BENCHES = ("micro_train", "micro_accum", "micro_serve",
                "micro_paged", "micro_lora_fusion", "micro_spec",
                "micro_router", "micro_longctx")

#: Env fields whose drift invalidates structural comparability (a
#: different XLA counts different FLOPs) — reported, not silently eaten.
ENV_COMPARE_KEYS = ("jax_version", "backend", "device_kind", "device_count")


def load_baseline(path):
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def run_fresh(names, repeats, jsonl_dir):
    """Run the gate benches in-process; returns {name: (BenchResult,
    metrics_jsonl_path)}. Each bench gets its own metrics JSONL so the
    failure diagnosis can diff telemetry against the baseline arm's."""
    import bench  # repo-root module (sys.path[0] is scripts/, [1] repo)

    from building_llm_from_scratch_tpu.obs.metrics import configure_metrics
    from building_llm_from_scratch_tpu.utils.seeding import (
        configure_default_prng,
    )

    configure_default_prng()
    out = {}
    for name in names:
        arm_jsonl = os.path.join(jsonl_dir, f"{name}.jsonl")
        configure_metrics(arm_jsonl, run_metadata={
            "bench": name, "perf_gate": True, "repeats": repeats})
        try:
            res = bench.run_bench(name, repeats=repeats, quick=True)
        finally:
            configure_metrics(None)
        out[name] = (res, arm_jsonl)
    return out


def env_drift(base_env, fresh_env):
    drift = []
    for key in ENV_COMPARE_KEYS:
        a, b = (base_env or {}).get(key), (fresh_env or {}).get(key)
        if a != b:
            drift.append(f"{key}: baseline {a!r} vs fresh {b!r}")
    return drift


def print_diagnosis(name, findings, base_entry, fresh_jsonl):
    print(f"\n!! perf gate FAILED: {name} — {len(findings)} structural/"
          "timing finding(s)")
    for f in findings:
        print(f"   [{f['kind']}] {f['detail']}")
    base_jsonl = base_entry.get("metrics_jsonl")
    if base_jsonl:
        base_jsonl = os.path.join(REPO_ROOT, base_jsonl)
    if base_jsonl and os.path.exists(base_jsonl) and fresh_jsonl \
            and os.path.exists(fresh_jsonl):
        # the A/B telemetry diff (summarize_metrics.py --compare): step-
        # timeline segments, engine tick phases, latency percentiles —
        # WHERE the regression lives, not just that it exists
        try:
            import summarize_metrics

            print(f"\n-- telemetry diff (A=baseline, B=fresh) for "
                  f"{name} --")
            summarize_metrics.compare_runs(base_jsonl, fresh_jsonl)
        except Exception as e:
            print(f"   (telemetry diff unavailable: {e})")
    print(f"\nIf this change is INTENDED, re-baseline with a reason:\n"
          f"  python scripts/perf_gate.py --update-baseline "
          f"--benches {name} --reason \"<why the structure changed>\"")


def _unknown_benches(names):
    """Names the baseline knows but bench.py no longer does (a renamed/
    removed bench without a re-baseline) — refuse cleanly, never
    KeyError mid-run."""
    import bench

    return [n for n in names if n not in bench.BENCHES]


def cmd_gate(args):
    perf = _load_perf()
    baseline = load_baseline(args.baseline)
    if baseline is None:
        print(f"no baseline at {args.baseline}; create one with "
              "--update-baseline --reason \"initial baseline\"")
        return 2
    names = (args.benches.split(",") if args.benches
             else sorted(baseline.get("benches", {})))
    missing = [n for n in names if n not in baseline.get("benches", {})]
    if missing:
        print(f"bench(es) {missing} not in the baseline "
              f"({sorted(baseline.get('benches', {}))}); re-baseline them "
              "first")
        return 2
    unknown = _unknown_benches(names)
    if unknown:
        print(f"bench(es) {unknown} are in the baseline but not in "
              "bench.BENCHES — a renamed/removed bench needs its "
              "baseline entry updated (--update-baseline --reason …) "
              "or pruned")
        return 2
    jsonl_dir = tempfile.mkdtemp(prefix="perf_gate_")
    try:
        return _gate_over(args, perf, baseline, names, jsonl_dir)
    finally:
        # keep the fresh arms' telemetry ONLY when the gate failed (the
        # diagnosis prints their paths); green runs must not leak a
        # /tmp/perf_gate_* dir per invocation
        if os.path.isdir(jsonl_dir) and not getattr(
                args, "_gate_failed", False):
            shutil.rmtree(jsonl_dir, ignore_errors=True)


def _gate_over(args, perf, baseline, names, jsonl_dir):
    fresh = run_fresh(names, args.repeats, jsonl_dir)
    fresh_env = perf.bench_env()
    rc = 0
    for name in names:
        res, arm_jsonl = fresh[name]
        entry = baseline["benches"][name]
        # env recorded PER BENCH (a --benches subset re-baseline must
        # not claim a new environment for entries measured in the old)
        drift = env_drift(entry.get("env") or baseline.get("env"),
                          fresh_env)
        if drift:
            print(f"note: environment drift vs the '{name}' baseline — "
                  "structural mismatches may be environmental, not "
                  "regressions:")
            for d in drift:
                print(f"   {d}")
        findings = perf.compare_structural(entry.get("fingerprint"),
                                           res.fingerprint)
        if args.timing:
            t = perf.compare_timing(entry.get("timing", {}), res.to_row(),
                                    sigma=args.sigma,
                                    floor_frac=args.floor_frac)
            if t:
                findings.append(t)
        if findings:
            rc = 1
            args._gate_failed = True      # cmd_gate keeps jsonl_dir
            print_diagnosis(name, findings, entry, arm_jsonl)
        else:
            fp = res.fingerprint or {}
            print(f"perf gate ok: {name} — {fp.get('n_programs', 0)} "
                  f"program(s), {fp.get('n_recompiles', 0)} recompiles, "
                  f"structural fingerprint matches"
                  + (f"; median {res.repeats['median']:.1f} {res.unit} "
                     f"(baseline {entry.get('timing', {}).get('value')})"
                     if args.timing and res.repeats else ""))
        if args.record:
            store = perf.TrajectoryStore(
                os.path.join(REPO_ROOT, "results", "perf"))
            store.append(res)
    return rc


def cmd_update_baseline(args):
    perf = _load_perf()
    if not args.reason or not args.reason.strip():
        print("refusing to update the baseline without --reason: the perf "
              "baseline is a reviewed claim (analysis/baseline.json "
              "discipline), not a snapshot")
        return 2
    names = (args.benches.split(",") if args.benches else list(GATE_BENCHES))
    unknown = _unknown_benches(names)
    if unknown:
        print(f"bench(es) {unknown} not in bench.BENCHES "
              "(nothing to measure)")
        return 2
    baseline = load_baseline(args.baseline) or {
        "comment": "Perf-observatory baseline (scripts/perf_gate.py): "
                   "structural HLO fingerprints + timing medians for the "
                   "gate benches. Every update carries a reason — "
                   "changing what the code compiles to is a reviewed "
                   "decision.",
        "benches": {}, "updates": []}
    os.makedirs(BASELINE_JSONL_DIR, exist_ok=True)
    jsonl_dir = tempfile.mkdtemp(prefix="perf_baseline_")
    try:
        fresh = run_fresh(names, max(args.repeats, 2), jsonl_dir)
    except Exception:
        shutil.rmtree(jsonl_dir, ignore_errors=True)
        raise
    env = perf.bench_env()
    for name in names:
        res, arm_jsonl = fresh[name]
        # through BASELINE_JSONL_DIR, never a hardcoded repo path: the
        # test suite monkeypatches the dir at a tmp location, and the
        # hardcoded join made its --update-baseline e2e rewrite the
        # COMMITTED arm files on every test run
        dst = os.path.join(BASELINE_JSONL_DIR, f"{name}.jsonl")
        rel_jsonl = os.path.relpath(dst, REPO_ROOT)
        shutil.copyfile(arm_jsonl, dst)
        baseline["benches"][name] = {
            "metric": res.metric,
            "fingerprint": perf.structural_part(res.fingerprint),
            "timing": {"value": round(res.value, 4), "unit": res.unit,
                       "repeats": res.repeats},
            "metrics_jsonl": rel_jsonl,
            # per-bench env: a --benches subset update must not claim a
            # new environment for the entries it did NOT re-measure
            "env": env,
        }
        fp = res.fingerprint or {}
        print(f"baselined {name}: {fp.get('n_programs', 0)} program(s), "
              f"median {res.repeats['median']:.1f} {res.unit}")
    baseline["env"] = env
    baseline["updates"] = (baseline.get("updates") or []) + [{
        "time": time.strftime("%Y-%m-%d %H:%M:%S"),
        "reason": args.reason.strip(),
        "benches": names,
        "git_sha": env.get("git_sha"),
    }]
    with open(args.baseline, "w") as f:
        json.dump(baseline, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"baseline written to {args.baseline} "
          f"(reason: {args.reason.strip()})")
    shutil.rmtree(jsonl_dir, ignore_errors=True)   # arms already copied
    return 0


def cmd_report(args):
    # pure file-path load: --report only reads JSONL and must work
    # (fast) without jax or the accelerator stack
    perf = _load_perf(pure=True)
    perf.render_trajectory(
        perf.TrajectoryStore(os.path.join(REPO_ROOT, "results", "perf")))
    return 0


def main(argv=None):
    p = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--baseline", default=BASELINE_PATH,
                   help="baseline JSON path (default: PERF_BASELINE.json)")
    p.add_argument("--benches", default=None,
                   help="comma-separated bench subset (default: every "
                        "bench in the baseline; for --update-baseline: "
                        f"{','.join(GATE_BENCHES)})")
    p.add_argument("--repeats", type=int, default=1,
                   help="repeats per bench (timing mode wants >=2 for a "
                        "real stddev; --update-baseline enforces >=2)")
    p.add_argument("--timing", action="store_true",
                   help="ALSO compare the headline value against the "
                        "baseline median (variance-aware; off in CI — "
                        "the shared container's clock is noise)")
    p.add_argument("--sigma", type=float, default=4.0,
                   help="timing noise floor: sigma * combined stddev")
    p.add_argument("--floor-frac", type=float, default=0.10,
                   help="timing noise floor: at least this fraction of "
                        "the baseline median")
    p.add_argument("--record", action="store_true",
                   help="append fresh results to results/perf/*.jsonl "
                        "(the trajectory store)")
    p.add_argument("--update-baseline", action="store_true",
                   help="re-measure and rewrite the baseline (REQUIRES "
                        "--reason)")
    p.add_argument("--reason", default=None,
                   help="why the baseline legitimately changed")
    p.add_argument("--report", action="store_true",
                   help="print the perf trajectory table "
                        "(results/perf/*.jsonl) and exit")
    args = p.parse_args(argv)
    if args.report:
        return cmd_report(args)
    if args.update_baseline:
        return cmd_update_baseline(args)
    return cmd_gate(args)


if __name__ == "__main__":
    sys.exit(main())
