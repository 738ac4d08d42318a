"""Observability subsystem: structured metrics (JSONL), step timeline +
trace annotations, MFU accounting, per-layer-group training health, XLA
compile telemetry, and the per-host stall detector.

Entry points:
  - ``MetricLogger`` / ``configure_metrics`` / ``get_metrics`` /
    ``emit_event`` — counters, gauges, timings, typed events, JSONL sink
    (obs/metrics.py);
  - ``StepTimeline`` / ``annotate`` / ``window_stats`` — per-step
    wall-clock breakdown + jax.profiler trace annotation (obs/timeline.py);
  - ``SetupTimeline`` / ``books_init`` / ``emit_setup_record`` — the books
    of an engine's or a trainer's set-up: every span kept, drained once
    into one ``setup`` record (obs/timeline.py);
  - ``flops_per_token`` / ``compute_mfu`` / ``mfu_from_flops`` /
    ``format_mfu`` / ``device_specs`` — analytic FLOPs and MFU against
    chip peak, one device-spec table (obs/mfu.py);
  - ``group_health`` / ``group_names`` / ``describe_health`` — in-graph
    per-layer-group gradient/param/update norms + non-finite localization
    (obs/health.py);
  - ``CompileWatcher`` / ``aot_compile`` / ``configure_compile_cache`` —
    AOT compile capture, HLO cost/memory analysis, recompile detection,
    persistent-cache wiring (obs/compile.py); ``program_table``: one
    ``program`` record a program the process builds, watched or not
    (trace, lower, cache load or compile, the cache's verdict);
  - ``StallDetector`` — opt-in hung-step flight recorder (obs/stall.py);
  - ``Histogram`` / ``RollingRatio`` / ``render_prometheus`` — serving
    aggregation: fixed-bucket latency histograms, rolling SLO burn-rate
    window, Prometheus text exposition (obs/metrics.py);
  - ``chrome_trace`` / ``export_chrome_trace`` / ``TICK_PHASES`` —
    metrics-JSONL -> Chrome trace-event JSON for Perfetto (obs/trace.py);
  - ``BenchResult`` / ``FingerprintCollector`` / ``TrajectoryStore`` /
    ``compare_structural`` / ``compare_timing`` — the perf observatory:
    schema'd bench results with env + structural HLO fingerprints, the
    results/perf trajectory store, and the two perf-gate comparison
    modes (obs/perf.py; gated by scripts/perf_gate.py).
"""

from building_llm_from_scratch_tpu.obs.compile import (
    CompileWatcher,
    aot_compile,
    configure_compile_cache,
    program_table,
)
from building_llm_from_scratch_tpu.obs.health import (
    describe_health,
    first_nonfinite_group,
    group_health,
    group_names,
    health_summary_line,
)
from building_llm_from_scratch_tpu.obs.metrics import (
    LATENCY_BUCKETS_S,
    Histogram,
    MetricLogger,
    RollingRatio,
    configure_metrics,
    emit_event,
    get_metrics,
    render_prometheus,
    run_metadata,
)
from building_llm_from_scratch_tpu.obs.trace import (
    TICK_PHASES,
    chrome_trace,
    export_chrome_trace,
)
from building_llm_from_scratch_tpu.obs.mfu import (
    compute_mfu,
    device_peak_flops,
    device_specs,
    flops_per_token,
    format_mfu,
    mfu_from_flops,
)
from building_llm_from_scratch_tpu.obs.perf import (
    BenchResult,
    FingerprintCollector,
    TrajectoryStore,
    bench_env,
    compare_structural,
    compare_timing,
    fingerprint_digest,
)
from building_llm_from_scratch_tpu.obs.stall import StallDetector
from building_llm_from_scratch_tpu.obs.timeline import (
    NON_STEP_SEGMENTS,
    SetupTimeline,
    StepTimeline,
    annotate,
    books_init,
    emit_setup_record,
    setup_line,
    window_stats,
)

__all__ = [
    "MetricLogger",
    "Histogram",
    "RollingRatio",
    "LATENCY_BUCKETS_S",
    "render_prometheus",
    "configure_metrics",
    "emit_event",
    "get_metrics",
    "run_metadata",
    "TICK_PHASES",
    "chrome_trace",
    "export_chrome_trace",
    "compute_mfu",
    "device_peak_flops",
    "device_specs",
    "flops_per_token",
    "format_mfu",
    "mfu_from_flops",
    "CompileWatcher",
    "aot_compile",
    "configure_compile_cache",
    "program_table",
    "describe_health",
    "first_nonfinite_group",
    "group_health",
    "group_names",
    "health_summary_line",
    "BenchResult",
    "FingerprintCollector",
    "TrajectoryStore",
    "bench_env",
    "compare_structural",
    "compare_timing",
    "fingerprint_digest",
    "StallDetector",
    "NON_STEP_SEGMENTS",
    "SetupTimeline",
    "StepTimeline",
    "annotate",
    "books_init",
    "emit_setup_record",
    "setup_line",
    "window_stats",
]
