"""The perf observatory: schema'd benchmark results, structural HLO
fingerprints, and the machine-readable perf trajectory.

Before this module, the repo's perf methodology was weaker than its
telemetry: ``bench.py`` printed loose single-metric JSON lines with no
environment capture, no repeat/variance discipline, and no baseline gate.
ROADMAP mandates that perf work prove itself via CPU A/Bs, HLO cost
analysis and zero-recompile invariants — this module is where those proofs
become ARTIFACTS:

  - ``BenchResult`` — the one schema every ``bench.py`` entry returns:
    headline value + unit, named extra metrics (each with a unit), repeat
    stats (min/median/mean/stddev over ``--repeats k``), an ``env`` block
    (jax version, backend, device kind/count, mesh, git sha, argv) and a
    **structural fingerprint** of everything XLA compiled during the run.

  - ``FingerprintCollector`` — a context manager that registers with
    ``obs/compile.py``: every ``CompileWatcher`` capture (the trainer step,
    the serving engine's prefill/decode programs) reports its label, arg
    signature, HLO cost-analysis FLOPs and memory breakdown here. The
    resulting fingerprint is TIMING-FREE and deterministic on CPU — two
    identical runs produce byte-identical structural parts — which is what
    lets ``scripts/perf_gate.py`` gate perf regressions in CI without
    trusting a shared container's wall clock.

  - ``compare_structural`` / ``compare_timing`` — the two gate modes.
    Structural: FLOPs / program count / arg signatures / recompile count /
    HBM breakdown must match the baseline EXACTLY; any drift yields a
    per-program differential finding (the offending program is NAMED).
    Timing: variance-aware; fires only when the fresh median falls past a
    noise floor derived from both arms' repeat stddev.

  - ``TrajectoryStore`` — reads/writes ``results/perf/*.jsonl``: one JSONL
    per bench name, one ``BenchResult`` row per measurement, so the perf
    history is machine-readable.

Stdlib-only at import time (jax is imported lazily inside ``bench_env``),
so the gate's pure-compare paths (``--report``, baseline diffs) run
without touching the accelerator stack.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import math
import os
import re
import statistics
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional

#: Version of the BenchResult row schema. Bump when a field changes
#: meaning; rows carry it so the gate can refuse to compare across
#: incompatible schemas instead of mis-diagnosing.
PERF_SCHEMA_VERSION = 1

#: Row discriminators in a bench/trajectory JSONL.
PERF_ROW_TYPES = ("header", "bench")

#: Structural fingerprint keys compared by the gate (everything else in a
#: fingerprint — timing, stability flags — is informational).
STRUCTURAL_KEYS = ("programs", "n_programs", "n_recompiles",
                   "recompile_labels")

#: Per-program structural fields (exact-match in the gate). ``memory`` is
#: the HBM breakdown dict; ``tokens_per_step`` is shape-derived.
PROGRAM_STRUCTURAL_FIELDS = ("label", "arg_sig", "flops", "transcendentals",
                             "bytes_accessed", "memory", "tokens_per_step")


# ---------------------------------------------------------------------------
# Environment capture
# ---------------------------------------------------------------------------

def git_info(root: Optional[str] = None) -> Dict[str, Any]:
    """{"git_sha": ..., "git_dirty": bool} for ``root`` (default: this
    file's repo), or {} when git is unavailable — env capture must never
    fail a bench run."""
    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=root, capture_output=True,
            text=True, timeout=10)
        if sha.returncode != 0:
            return {}
        dirty = subprocess.run(
            ["git", "status", "--porcelain"], cwd=root,
            capture_output=True, text=True, timeout=10)
        return {"git_sha": sha.stdout.strip(),
                "git_dirty": bool(dirty.stdout.strip())
                if dirty.returncode == 0 else None}
    except (OSError, subprocess.SubprocessError):
        return {}


def bench_env(mesh: Optional[Dict[str, int]] = None) -> Dict[str, Any]:
    """The ``env`` block every BenchResult carries: jax version, backend,
    device kind/count, mesh, git sha, argv. A number without this block is
    not comparable to anything — the Gemma-on-TPU comparison discipline
    (PAPERS.md): fixed workloads need recorded environments."""
    env: Dict[str, Any] = {
        "python": sys.version.split()[0],
        "argv": list(sys.argv),
        "mesh": mesh,
    }
    env.update(git_info())
    try:
        import jax

        devices = jax.devices()
        env.update({
            "jax_version": jax.__version__,
            "backend": jax.default_backend(),
            "device_kind": devices[0].device_kind if devices else "unknown",
            "device_count": len(devices),
            "local_device_count": jax.local_device_count(),
            "process_count": jax.process_count(),
        })
    except Exception:                      # pragma: no cover - env capture
        env.setdefault("jax_version", None)
    return env


# ---------------------------------------------------------------------------
# Structural fingerprint capture (via obs/compile.py's CompileWatcher)
# ---------------------------------------------------------------------------

def _sig_digest(sig: Any) -> str:
    """Stable short digest of one program's argument signature: a tuple
    of per-argument ``tree_signature`` tuples, each a sequence of
    (path, shape, dtype, sharding) leaf entries. Shardings are rendered
    through their spec/str like the recompile diff does, so the digest
    is deterministic across identical runs."""
    rendered = []
    for arg_sig in sig or ():
        arg = []
        for entry in arg_sig or ():
            path, shape, dtype = entry[0], entry[1], entry[2]
            sharding = entry[3] if len(entry) > 3 else None
            if sharding is not None:
                spec = getattr(sharding, "spec", None)
                sharding = str(spec if spec is not None else sharding)
            arg.append([str(path), list(shape), str(dtype), sharding])
        rendered.append(arg)
    blob = json.dumps(rendered, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:16]


class FingerprintCollector:
    """Collects every CompileWatcher capture/recompile while installed.

    Use as a context manager around one bench run::

        with FingerprintCollector() as col:
            result = bench_fn()
        result.fingerprint = col.fingerprint()

    Thread-safe: serving-engine programs may compile from engine threads.
    """

    def __init__(self):
        import threading

        self._lock = threading.Lock()
        self._programs: List[Dict[str, Any]] = []    # guarded-by: _lock
        self._recompiles: List[Dict[str, Any]] = []  # guarded-by: _lock
        self._compile_seconds = 0.0                  # guarded-by: _lock

    # -- CompileWatcher callbacks (obs/compile.py) -----------------------

    def on_compile(self, label: str, sig: Any, stats: Dict[str, Any],
                   n_tokens: Optional[int] = None) -> None:
        prog: Dict[str, Any] = {"label": label, "arg_sig": _sig_digest(sig)}
        for key in ("flops", "transcendentals", "bytes_accessed"):
            if isinstance(stats.get(key), (int, float)):
                prog[key] = stats[key]
        mem = stats.get("memory")
        if isinstance(mem, dict) and mem:
            prog["memory"] = dict(mem)
        if n_tokens:
            prog["tokens_per_step"] = int(n_tokens)
        with self._lock:
            self._programs.append(prog)
            self._compile_seconds += float(
                stats.get("compile_seconds") or 0.0)

    def on_recompile(self, label: str, diff: List[Dict[str, Any]]) -> None:
        with self._lock:
            self._recompiles.append(
                {"label": label, "n_changed_leaves": len(diff),
                 "leaves": [d.get("leaf") for d in diff[:8]]})

    # -- lifecycle -------------------------------------------------------

    def __enter__(self) -> "FingerprintCollector":
        from building_llm_from_scratch_tpu.obs import compile as _compile

        _compile.add_collector(self)
        return self

    def __exit__(self, *exc) -> None:
        from building_llm_from_scratch_tpu.obs import compile as _compile

        _compile.remove_collector(self)

    # -- the fingerprint -------------------------------------------------

    def fingerprint(self) -> Dict[str, Any]:
        """Structural fingerprint + timing info for everything compiled
        while installed. The structural part (``structural_part`` strips
        the rest) is deterministic across identical runs; ``timing`` is
        informational (the trajectory tracks compile seconds, the gate
        never compares them structurally)."""
        with self._lock:
            programs = [dict(p) for p in self._programs]
            recompiles = [dict(r) for r in self._recompiles]
            compile_s = self._compile_seconds
        # chronologically-last capture kept aside (non-structural): the
        # sorted programs list loses which program was compiled LAST,
        # which is what the legacy stdout line's HLO fields report
        last = dict(programs[-1]) if programs else None
        programs.sort(key=lambda p: (p["label"], p["arg_sig"]))
        return {
            "programs": programs,
            "n_programs": len(programs),
            "n_recompiles": len(recompiles),
            "recompile_labels": sorted({r["label"] for r in recompiles}),
            "recompile_diffs": recompiles,
            "last_program": last,
            "timing": {"compile_seconds_total": round(compile_s, 4)},
        }


def structural_part(fingerprint: Optional[Dict[str, Any]]
                    ) -> Dict[str, Any]:
    """The timing-free slice of a fingerprint the gate compares: per-
    program FLOPs/signatures/memory, program count, recompile count."""
    fingerprint = fingerprint or {}
    out: Dict[str, Any] = {}
    for key in STRUCTURAL_KEYS:
        if key == "programs":
            out["programs"] = [
                {f: p[f] for f in PROGRAM_STRUCTURAL_FIELDS if f in p}
                for p in fingerprint.get("programs", ())]
        else:
            out[key] = fingerprint.get(key, 0 if key != "recompile_labels"
                                       else [])
    return out


def fingerprint_digest(fingerprint: Optional[Dict[str, Any]]) -> str:
    """sha256 of the canonical-JSON structural part — the byte-identity
    the determinism tests pin."""
    blob = json.dumps(structural_part(fingerprint), sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()


# ---------------------------------------------------------------------------
# BenchResult: the one schema every bench returns
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class BenchResult:
    """One benchmark measurement, self-describing.

    ``value``/``unit`` is the headline metric (what the trajectory plots
    and the timing gate compares); ``metrics`` holds named extra numbers,
    each ``{"value": v, "unit": u}``; ``detail`` is the bench's free-form
    arm breakdown (the dicts the serve benches print). The runner
    (``bench.run_bench``) fills ``repeats``/``env``/``fingerprint``.
    """

    name: str
    metric: str
    value: float
    unit: str = "tokens/sec/chip"
    metrics: Dict[str, Dict[str, Any]] = dataclasses.field(
        default_factory=dict)
    detail: Optional[Dict[str, Any]] = None
    repeats: Optional[Dict[str, Any]] = None
    env: Optional[Dict[str, Any]] = None
    fingerprint: Optional[Dict[str, Any]] = None
    quick: bool = False
    time: Optional[float] = None

    def add_metric(self, key: str, value: float, unit: str) -> None:
        self.metrics[key] = {"value": value, "unit": unit}

    def metric_value(self, key: str) -> Optional[float]:
        entry = self.metrics.get(key)
        return entry.get("value") if isinstance(entry, dict) else None

    def to_row(self) -> Dict[str, Any]:
        row: Dict[str, Any] = {"type": "bench",
                               "perf_schema_version": PERF_SCHEMA_VERSION,
                               "name": self.name, "metric": self.metric,
                               "value": self.value, "unit": self.unit}
        for key in ("metrics", "detail", "repeats", "env", "fingerprint",
                    "time"):
            val = getattr(self, key)
            if val is not None and val != {}:
                row[key] = val
        if self.quick:
            row["quick"] = True
        return row

    @classmethod
    def from_row(cls, row: Dict[str, Any]) -> "BenchResult":
        problems = validate_row(row)
        if problems:
            raise ValueError("invalid BenchResult row: "
                             + "; ".join(problems))
        kw = {f.name: row[f.name] for f in dataclasses.fields(cls)
              if f.name in row}
        return cls(**kw)


def validate_row(row: Dict[str, Any]) -> List[str]:
    """Schema-check one bench row; returns problems (empty = valid)."""
    problems = []
    if row.get("type") != "bench":
        problems.append(f"type must be 'bench', got {row.get('type')!r}")
    if not isinstance(row.get("name"), str) or not row.get("name"):
        problems.append("missing/empty 'name'")
    if not isinstance(row.get("metric"), str) or not row.get("metric"):
        problems.append("missing/empty 'metric'")
    if not isinstance(row.get("value"), (int, float)):
        problems.append("'value' must be a number")
    if not isinstance(row.get("unit"), str):
        problems.append("'unit' must be a string")
    ver = row.get("perf_schema_version")
    if not isinstance(ver, int):
        problems.append("missing 'perf_schema_version'")
    elif ver > PERF_SCHEMA_VERSION:
        problems.append(f"perf_schema_version {ver} is newer than this "
                        f"reader ({PERF_SCHEMA_VERSION})")
    metrics = row.get("metrics")
    if metrics is not None:
        if not isinstance(metrics, dict):
            problems.append("'metrics' must be a dict")
        else:
            for key, entry in metrics.items():
                if (not isinstance(entry, dict) or "value" not in entry
                        or "unit" not in entry):
                    problems.append(
                        f"metrics[{key!r}] must be {{value, unit}}")
    reps = row.get("repeats")
    if reps is not None and not (
            isinstance(reps, dict) and isinstance(reps.get("n"), int)):
        problems.append("'repeats' must carry an integer 'n'")
    env = row.get("env")
    if env is not None and not isinstance(env, dict):
        problems.append("'env' must be a dict")
    return problems


def repeat_stats(values: List[float]) -> Dict[str, Any]:
    """min/median/mean/stddev over a bench's repeated headline values —
    the variance discipline the timing gate's noise floor is derived
    from. ``stddev`` is the sample stddev (0.0 for n=1)."""
    vals = [float(v) for v in values]
    return {
        "n": len(vals),
        "values": [round(v, 4) for v in vals],
        "min": round(min(vals), 4),
        "median": round(statistics.median(vals), 4),
        "mean": round(statistics.fmean(vals), 4),
        "stddev": round(statistics.stdev(vals), 4) if len(vals) > 1 else 0.0,
    }


def header_row(**extra: Any) -> Dict[str, Any]:
    """The run-metadata header row (one per bench stdout stream / --json
    file): schema version + the env block. One constructor, so the two
    sinks can never diverge on what a header carries."""
    row: Dict[str, Any] = {"type": "header",
                           "perf_schema_version": PERF_SCHEMA_VERSION,
                           "time": time.time()}
    row.update(bench_env())
    row.update(extra)
    return row


def emit_bench_result(result: "BenchResult") -> None:
    """One ``bench_result`` event into the configured metrics JSONL, so a
    bench arm's telemetry file is self-describing about what it measured
    (the gate's differential diagnosis joins on it)."""
    from building_llm_from_scratch_tpu.obs.metrics import get_metrics

    get_metrics().event(
        "bench_result", name=result.name, metric=result.metric,
        value=round(float(result.value), 4), unit=result.unit,
        n_repeats=(result.repeats or {}).get("n"),
        quick=bool(result.quick),
        fingerprint_sha=fingerprint_digest(result.fingerprint))


# ---------------------------------------------------------------------------
# Gate comparisons
# ---------------------------------------------------------------------------

def _fmt_delta(base: float, fresh: float) -> str:
    if base:
        return f"{fresh - base:+.4g} ({100.0 * (fresh - base) / base:+.2f}%)"
    return f"{fresh - base:+.4g}"


def compare_structural(base_fp: Optional[Dict[str, Any]],
                       fresh_fp: Optional[Dict[str, Any]]
                       ) -> List[Dict[str, Any]]:
    """Timing-free differential between two structural fingerprints.

    Returns findings (empty = identical). Exact-match discipline: on the
    shared CPU container the fingerprint is deterministic, so ANY drift —
    per-program FLOPs, a new/removed program, an arg-signature change, a
    recompile, an HBM-breakdown byte — is a real structural change in
    what XLA was asked to build, and the finding NAMES the program."""
    base = structural_part(base_fp)
    fresh = structural_part(fresh_fp)
    findings: List[Dict[str, Any]] = []
    if base == fresh:
        return findings

    def field_diffs(label, sig, b, f):
        for field in ("flops", "transcendentals", "bytes_accessed",
                      "tokens_per_step"):
            if b.get(field) != f.get(field):
                findings.append({
                    "kind": f"{field}_delta", "program": label,
                    "arg_sig": sig, "base": b.get(field),
                    "fresh": f.get(field),
                    "detail": f"program '{label}' {field}: "
                              f"{b.get(field)} -> {f.get(field)} "
                              + (_fmt_delta(b[field], f[field])
                                 if isinstance(b.get(field), (int, float))
                                 and isinstance(f.get(field), (int, float))
                                 else "")})
        bm, fm = b.get("memory") or {}, f.get("memory") or {}
        if bm != fm:
            deltas = {k: (bm.get(k), fm.get(k))
                      for k in sorted(set(bm) | set(fm))
                      if bm.get(k) != fm.get(k)}
            findings.append({
                "kind": "memory_delta", "program": label,
                "arg_sig": sig, "base": bm, "fresh": fm,
                "detail": f"program '{label}' HBM breakdown changed: "
                          + ", ".join(f"{k} {v[0]} -> {v[1]}"
                                      for k, v in deltas.items())})

    base_progs = base.get("programs", [])
    fresh_progs = fresh.get("programs", [])
    labels = sorted({p["label"] for p in base_progs}
                    | {p["label"] for p in fresh_progs})
    for label in labels:
        b_by_sig = {p["arg_sig"]: p for p in base_progs
                    if p["label"] == label}
        f_by_sig = {p["arg_sig"]: p for p in fresh_progs
                    if p["label"] == label}
        for sig in sorted(set(b_by_sig) & set(f_by_sig)):
            field_diffs(label, sig, b_by_sig[sig], f_by_sig[sig])
        b_only = sorted(set(b_by_sig) - set(f_by_sig))
        f_only = sorted(set(f_by_sig) - set(b_by_sig))
        if not b_by_sig:
            for sig in f_only:
                findings.append({
                    "kind": "new_program", "program": label,
                    "arg_sig": sig, "base": None, "fresh": f_by_sig[sig],
                    "detail": f"NEW program '{label}' (sig {sig}, flops "
                              f"{f_by_sig[sig].get('flops')})"})
        elif not f_by_sig:
            for sig in b_only:
                findings.append({
                    "kind": "removed_program", "program": label,
                    "arg_sig": sig, "base": b_by_sig[sig], "fresh": None,
                    "detail": f"program '{label}' (sig {sig}) is no "
                              "longer compiled"})
        elif len(b_only) == 1 and len(f_only) == 1:
            # 1:1 signature change — pair them so the finding carries the
            # FLOP drift that usually rides along with a shape change
            b, f = b_by_sig[b_only[0]], f_by_sig[f_only[0]]
            extra = ""
            if isinstance(b.get("flops"), (int, float)) and isinstance(
                    f.get("flops"), (int, float)) \
                    and b["flops"] != f["flops"]:
                extra = ", flops " + _fmt_delta(b["flops"], f["flops"])
            findings.append({
                "kind": "arg_signature_changed", "program": label,
                "arg_sig": b_only[0], "base": b, "fresh": f,
                "detail": f"program '{label}' changed its argument "
                          f"signature ({b_only[0]} -> {f_only[0]}"
                          f"{extra})"})
        else:
            # the label survives with shared variants but grew and/or
            # lost some — the bucket-leak shape: every stray variant is
            # NAMED, never collapsed into a bare program-count delta
            for sig in f_only:
                findings.append({
                    "kind": "new_program_variant", "program": label,
                    "arg_sig": sig, "base": None, "fresh": f_by_sig[sig],
                    "detail": f"NEW variant of program '{label}' "
                              f"(sig {sig}, flops "
                              f"{f_by_sig[sig].get('flops')}) — a "
                              "signature outside the baselined set"})
            for sig in b_only:
                findings.append({
                    "kind": "removed_program_variant", "program": label,
                    "arg_sig": sig, "base": b_by_sig[sig], "fresh": None,
                    "detail": f"variant of program '{label}' (sig {sig}) "
                              "is no longer compiled"})

    if base.get("n_programs") != fresh.get("n_programs"):
        findings.append({
            "kind": "program_count", "program": None,
            "base": base.get("n_programs"), "fresh": fresh.get("n_programs"),
            "detail": f"compiled-program count {base.get('n_programs')} -> "
                      f"{fresh.get('n_programs')}"})
    if base.get("n_recompiles") != fresh.get("n_recompiles"):
        findings.append({
            "kind": "recompiles", "program": None,
            "base": base.get("n_recompiles"),
            "fresh": fresh.get("n_recompiles"),
            "detail": f"recompile count {base.get('n_recompiles')} -> "
                      f"{fresh.get('n_recompiles')} "
                      f"(labels: {fresh.get('recompile_labels')})"})
    elif base.get("recompile_labels") != fresh.get("recompile_labels"):
        # same count, different victims (reachable when an AOT capture
        # fails and the program set stays unchanged)
        findings.append({
            "kind": "recompiles", "program": None,
            "base": base.get("recompile_labels"),
            "fresh": fresh.get("recompile_labels"),
            "detail": "recompiled programs changed: "
                      f"{base.get('recompile_labels')} -> "
                      f"{fresh.get('recompile_labels')}"})
    if not findings:
        # safety net for the exact-match contract: base != fresh was
        # established above, so ANY drift the specific rules missed
        # still fails the gate (with the digests to chase)
        findings.append({
            "kind": "structural_drift", "program": None,
            "base": fingerprint_digest(base_fp),
            "fresh": fingerprint_digest(fresh_fp),
            "detail": "structural fingerprints differ "
                      f"({fingerprint_digest(base_fp)[:12]} -> "
                      f"{fingerprint_digest(fresh_fp)[:12]}) outside the "
                      "itemized fields — diff the baseline's fingerprint "
                      "JSON against a fresh bench row's"})
    return findings


def compare_timing(base_row: Dict[str, Any], fresh_row: Dict[str, Any],
                   sigma: float = 4.0, floor_frac: float = 0.10
                   ) -> Optional[Dict[str, Any]]:
    """Variance-aware timing comparison of two BenchResult rows (higher
    value = better, the bench convention). Fires ONLY when the fresh
    median falls below the baseline median by more than the noise floor:

        noise = max(sigma * sqrt(base_std^2 + fresh_std^2),
                    floor_frac * base_median)

    so k identical reruns (stddev ~0, delta 0) never fire, and a genuine
    1.5x slowdown always does. Returns a finding dict or None."""
    def med_std(row):
        reps = row.get("repeats") or {}
        med = reps.get("median", row.get("value"))
        std = reps.get("stddev", 0.0) or 0.0
        return float(med), float(std)

    base_med, base_std = med_std(base_row)
    fresh_med, fresh_std = med_std(fresh_row)
    noise = max(sigma * math.sqrt(base_std ** 2 + fresh_std ** 2),
                floor_frac * abs(base_med))
    delta = fresh_med - base_med
    if delta >= -noise:
        return None
    return {
        "kind": "timing_regression",
        "base": round(base_med, 4), "fresh": round(fresh_med, 4),
        "ratio": round(fresh_med / base_med, 4) if base_med else None,
        "noise_floor": round(noise, 4),
        "detail": (f"median {base_med:.4g} -> {fresh_med:.4g} "
                   f"{row_unit(base_row)} "
                   f"({100 * delta / base_med:+.1f}%), past the "
                   f"noise floor of {noise:.4g} "
                   f"(sigma={sigma}, base std {base_std:.4g}, "
                   f"fresh std {fresh_std:.4g})"),
    }


def row_unit(row: Dict[str, Any]) -> str:
    return row.get("unit", "")


# ---------------------------------------------------------------------------
# Trajectory store: results/perf/*.jsonl
# ---------------------------------------------------------------------------

def default_trajectory_root() -> str:
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(repo, "results", "perf")


class TrajectoryStore:
    """One JSONL per bench name under ``root`` (``results/perf/`` by
    default); each line is a ``BenchResult`` row. Appending validates;
    loading skips unparseable lines loudly rather than dying — the
    trajectory must survive a half-written row from a killed run."""

    def __init__(self, root: Optional[str] = None):
        self.root = root or default_trajectory_root()

    def path(self, name: str) -> str:
        safe = re.sub(r"[^A-Za-z0-9_.-]", "_", name)
        return os.path.join(self.root, f"{safe}.jsonl")

    def names(self) -> List[str]:
        if not os.path.isdir(self.root):
            return []
        return sorted(n[:-6] for n in os.listdir(self.root)
                      if n.endswith(".jsonl"))

    def append(self, result) -> str:
        row = result.to_row() if isinstance(result, BenchResult) else result
        problems = validate_row(row)
        if problems:
            raise ValueError("refusing to store invalid row: "
                             + "; ".join(problems))
        os.makedirs(self.root, exist_ok=True)
        path = self.path(row["name"])
        with open(path, "a") as f:
            f.write(json.dumps(row, sort_keys=True) + "\n")
        return path

    def load(self, name: str) -> List[Dict[str, Any]]:
        """Bench rows only: a file fed through ``bench.py --json
        <file>.jsonl`` carries a header row too — the trajectory
        consumers (report table, backfill dedup) never want it."""
        path = self.path(name)
        if not os.path.exists(path):
            return []
        rows = []
        with open(path) as f:
            for i, line in enumerate(f):
                line = line.strip()
                if not line:
                    continue
                try:
                    row = json.loads(line)
                except json.JSONDecodeError:
                    print(f"warning: {path}:{i + 1} unparseable; skipped",
                          file=sys.stderr)
                    continue
                if row.get("type") == "bench":
                    rows.append(row)
        return rows


# ---------------------------------------------------------------------------
# Trajectory rendering
# ---------------------------------------------------------------------------

def render_trajectory(store: Optional[TrajectoryStore] = None,
                      names: Optional[List[str]] = None,
                      out=None) -> int:
    """Print the tok/s + MFU + compile-seconds trajectory table per bench
    from the one-file-per-bench store. Returns the number of rows
    rendered."""
    store = store or TrajectoryStore()
    write = (out or sys.stdout).write
    names = names or store.names()
    n_rows = 0
    for name in names:
        rows = store.load(name)
        if not rows:
            continue
        rows.sort(key=lambda r: (r.get("time") or 0))
        write(f"\n== perf trajectory: {name} ==\n")
        write(f"{'when':<17}{'run':<8}{'value':>12} "
              f"{'unit':<18}{'mfu':>7}{'compile_s':>11}\n")
        for r in rows:
            when = (time.strftime("%Y-%m-%d %H:%M",
                                  time.localtime(r["time"]))
                    if isinstance(r.get("time"), (int, float)) else "?")
            metrics = r.get("metrics") or {}

            def mval(key):
                entry = metrics.get(key)
                return entry.get("value") if isinstance(entry, dict) \
                    else None

            mfu = mval("mfu")
            compile_s = mval("compile_seconds")
            if compile_s is None:
                compile_s = ((r.get("fingerprint") or {}).get("timing")
                             or {}).get("compile_seconds_total")
            mode = "quick" if r.get("quick") else "full"
            write(f"{when:<17}{mode:<8}{r['value']:>12.1f} "
                  f"{r.get('unit', ''):<18}"
                  f"{mfu if mfu is not None else '-':>7}"
                  f"{compile_s if compile_s is not None else '-':>11}\n")
            n_rows += 1
    if n_rows == 0:
        write("no trajectory rows under "
              f"{store.root} (run bench.py <name> --json "
              "results/perf/)\n")
    return n_rows
