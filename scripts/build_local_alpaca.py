"""Synthesize an offline Alpaca-FORMAT instruction dataset.

Zero network egress means the real tatsu-lab alpaca_data.json
(datasets/alpaca.py) cannot download, so an offline SFT convergence run
uses deterministic string-manipulation tasks in the exact
Alpaca schema ({"instruction", "input", "output"}). The tasks are chosen
so a byte-level model can visibly LEARN them (reverse/uppercase/repeat):
before-SFT samples are garbage, after-SFT samples follow the instruction —
the observable the reference's own SFT runs produce.

  python scripts/build_local_alpaca.py [out.json] [n_examples]
"""

import json
import os
import random
import sys

WORDS = [
    "tensor", "kernel", "gradient", "shard", "lattice", "vector", "matrix",
    "python", "compile", "buffer", "stream", "socket", "thread", "object",
    "module", "string", "number", "window", "branch", "commit", "memory",
    "device", "driver", "packet", "signal", "record", "column", "schema",
]

TASKS = [
    ("Reverse the given word.", lambda w: w[::-1]),
    ("Convert the given word to uppercase.", lambda w: w.upper()),
    ("Repeat the given word twice, separated by a space.",
     lambda w: f"{w} {w}"),
    ("Output the first three letters of the given word.", lambda w: w[:3]),
]


def main(argv):
    out_path = argv[1] if len(argv) > 1 else "data_local/alpaca/alpaca_local.json"
    n = int(argv[2]) if len(argv) > 2 else 2000
    rng = random.Random(0)
    data = []
    for _ in range(n):
        instr, fn = rng.choice(TASKS)
        w = rng.choice(WORDS)
        data.append({"instruction": instr, "input": w, "output": fn(w)})
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(data, f, indent=1)
    print(f"wrote {len(data)} examples to {out_path}")


if __name__ == "__main__":
    main(sys.argv)
