"""The on-chip benchmark: cells named in ``BENCHMARK.json``, found by name.

Run as ``python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` from the root of a checkout. Everything that decides a number
lives here (traffic, reduction, peaks, references, the verdict ``correct``);
from the program the benchmark takes only the system under test.
"""
