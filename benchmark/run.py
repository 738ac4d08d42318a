"""One run of one cell: load, warm up, measure, check, print one line, exit.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``main`` looks for the chips the cell asks for and refuses to run without
them; ``run_cell`` is the rest of a run and is what the tests under
``benchmark/tests`` drive on the CPU at a tiny size. The last line of standard
output is the result object; earlier lines itemise set-up, sample counts,
medians and what the generator did. The numbers ``correct`` rests on are the
last lines of standard error, each beside its limit.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse          # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import sys               # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

from benchmark import spec  # noqa: E402


class Clock:
    """Set-up, itemised: seconds from the start of the process to each mark."""

    def __init__(self, t0: float = T_PROCESS):
        self.t0 = self.t_last = t0
        self.items: List[List[Any]] = []

    def mark(self, name: str) -> None:
        now = time.perf_counter()
        self.items.append([name, round(now - self.t_last, 3)])
        self.t_last = now



class CompileCounter:
    """Counts every program JAX compiles or loads from its cache, whoever
    asked for it: the count over the window has to be 0."""

    _instance = None

    def __init__(self):
        import jax.monitoring

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name: str, secs: float, **_) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance


def say(**fields) -> None:
    """An earlier line of the output: one JSON object, flushed."""
    print(json.dumps(fields), flush=True)


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             peaks: Optional[Dict[str, float]], clock: Optional[Clock] = None,
             control: str = "") -> Dict[str, Any]:
    """Everything after the look for a chip. Returns the result object.
    ``control`` (tests only) also reads the control: the reference in that
    lower precision, put in the program's place."""
    import jax

    clock = clock or Clock(time.perf_counter())
    compiles = CompileCounter.get()
    mode = spec.load_module("modes", cell.mode)
    out = mode.run(cell, seed=seed, seconds=seconds, trace=trace,
                   peaks=peaks, clock=clock, say=say, compiles=compiles,
                   **({"control": control} if control else {}))
    say(setup_items=clock.items, setup_s=out["setup_s"],
        programs_compiled_or_loaded=compiles.n)
    say(window=out["summary"])
    for name, value, limit in out["compared"]:
        print(f"compared {cell.config['name']} {name}: value {value!r} "
              f"limit {limit!r} -> {'ok' if value <= limit else 'NOT OK'}",
              file=sys.stderr, flush=True)
    correct = bool(out["compared"]) and all(
        v <= lim for _, v, lim in out["compared"]) and out["sound"]
    print(f"correct {correct} (compilations in window: "
          f"{out['compiles_in_window']}, must be 0)", file=sys.stderr,
          flush=True)
    dev = jax.local_devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell.chips,
              "memory_peak_bytes": out["memory_peak_bytes"]}
    if trace:
        metrics = {}
        ctx = {"cell": cell, "peaks": peaks, "trace": out["trace"],
               "window": out["window"]}
        for metric in cell.per_layer:
            reader = spec.load_module("readers", metric["reader"])
            value = reader.read(metric, ctx)
            if value is not None:
                metrics[metric["name"]] = {"value": value,
                                           "unit": metric["unit"]}
        if out["trace"] is not None:
            device["busy_s"] = out["trace"]["busy_s"]
            device["window_s"] = out["trace"]["window_s"]
    else:
        metrics = {m["name"]: {"value": out["end_to_end"][m["name"]],
                               "unit": m["unit"]} for m in cell.end_to_end}
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if trace and out["trace"] is not None:
        result["breakdown"] = {"device_ops": out["trace"]["device_ops"],
                               "idle_gaps": out["trace"]["idle_gaps"]}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    clock = Clock()
    clock.mark("start")

    import jax

    from benchmark import peaks as peak_table
    from building_llm_from_scratch_tpu.obs.compile import (
        configure_compile_cache,
    )

    # the program's own rule: JAX_COMPILATION_CACHE_DIR if set, else this
    # fixed directory inside the checkout
    configure_compile_cache(os.path.join(spec.ROOT, ".jax_cache"))
    devices = jax.local_devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"cell {cell.name} needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} x {devices[0].platform}", file=sys.stderr)
        return 3
    peaks = peak_table.peaks_for(devices[0].device_kind)
    clock.mark("import")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), peaks,
                      clock)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
