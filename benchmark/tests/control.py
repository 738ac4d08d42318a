"""Reads what the limits of ``correct`` are set from, on the chip, at the
cell's own size: for each seed one run of the cell (``--seconds`` may be
short) with the numbers compared, and beside them the control's: the plain
reference computed in the precision below the configuration's
(``precision.below`` in its file) and put in the program's place.

    python3 -m benchmark.tests.control --workload <cell> --seeds 1,2,3 --seconds 20

Not part of a benchmark run. One process, so one set-up of the chip; each
seed still builds its own weights, state or engine.
"""

import argparse
import json
import sys

from benchmark import run, spec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--control", type=int, default=1)
    ap.add_argument("--tiny", type=int, default=0,
                    help="the CPU-sized override of benchmark/tests/tiny.py")
    args = ap.parse_args(argv)
    import jax

    if args.tiny:
        from benchmark.tests.tiny import tiny_cell

        cell, peaks = tiny_cell(args.workload), None
    else:
        from benchmark import peaks as peak_table
        from building_llm_from_scratch_tpu.obs.compile import (
            configure_compile_cache,
        )

        cell = spec.load_cell(args.workload)
        configure_compile_cache()
        peaks = peak_table.peaks_for(jax.local_devices()[0].device_kind)
    below = cell.config["precision"]["below"] if args.control else ""
    for seed in (int(s) for s in args.seeds.split(",")):
        result = run.run_cell(cell, seed, args.seconds, False, peaks,
                              control=below)
        print(json.dumps({"seed": seed, **result}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
