"""Assemble a real offline training corpus from text available on disk.

This environment has zero network egress, so the Gutenberg download
(datasets/gutenberg.py `download_archive`) cannot run; the packing side of
that pipeline is reused verbatim here over the ~500MB of English prose and
source text shipped with the Python installation — a genuine (if unusual)
corpus for offline convergence runs.

  python scripts/build_local_corpus.py [out_dir] [max_mb]
"""

import os
import sys

from building_llm_from_scratch_tpu.datasets.gutenberg import (
    is_english,
    pack_files,
)

ROOTS = [
    "/opt/venv/lib/python3.12/site-packages",
    "/usr/local/lib/python3.12",
]
EXTS = (".py", ".md", ".rst", ".txt")


def collect(max_bytes: int):
    out, total = [], 0
    for root in ROOTS:
        for dirpath, dirs, files in os.walk(root):
            if "__pycache__" in dirpath:
                continue
            for f in sorted(files):
                if not f.endswith(EXTS):
                    continue
                p = os.path.join(dirpath, f)
                try:
                    size = os.path.getsize(p)
                except OSError:
                    continue
                if size < 512:
                    continue
                out.append(p)
                total += size
                if total >= max_bytes:
                    return out, total
    return out, total


def main(argv):
    out_dir = argv[1] if len(argv) > 1 else "data_local/corpus"
    max_mb = int(argv[2]) if len(argv) > 2 else 400
    files, total = collect(max_mb * 1_000_000)
    print(f"collected {len(files)} files, {total / 1e6:.0f} MB")
    # pack through the Gutenberg pipeline (ASCII-ratio English filter +
    # <|endoftext|>-joined <=500MB shards, datasets/gutenberg.py)
    os.makedirs(out_dir, exist_ok=True)
    n = pack_files(files, out_dir, max_size_mb=100)
    for i in range(1, n + 1):
        p = os.path.join(out_dir, f"combined_{i}.txt")
        print("wrote", p, f"{os.path.getsize(p) / 1e6:.0f} MB")


if __name__ == "__main__":
    main(sys.argv)
