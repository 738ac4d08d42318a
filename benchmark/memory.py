"""What the chip holds: the allocator's counters and what the runtime reserves.

On this TPU runtime ``memory_stats()["peak_bytes_in_use"]`` counts arrays only.
A loaded program's temporaries are reserved apart and show as
``bytes_reserved`` (PERF.md, PR 24: 4.09 GB in use beside 6.12 GB reserved for
a train step whose ``memory_analysis`` gives 6.17 GB of temporaries). The peak
on a chip is the sum of the two peaks; live arrays plus the largest program's
temporaries are printed beside it as a cross-check.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Iterable

KEYS = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
        "largest_alloc_size", "bytes_reserved", "peak_bytes_reserved")


def read_peak(say: Callable[..., None], live_arrays: Any,
              executables: Iterable[Any]) -> int:
    """Prints the counters (an earlier line of the output) and returns the
    peak bytes on the fullest chip."""
    import jax

    stats = [dict(d.memory_stats() or {}) for d in jax.local_devices()]
    live = sum(x.nbytes for x in jax.tree_util.tree_leaves(live_arrays))
    temp = 0
    for exe in executables:
        analysis = exe.memory_analysis()
        if analysis is not None:
            temp = max(temp, int(analysis.temp_size_in_bytes))
    say(memory={"memory_stats": [{k: s.get(k) for k in KEYS} for s in stats],
                "live_array_bytes": int(live),
                "largest_program_temp_bytes": temp})
    return max(int(s.get("peak_bytes_in_use", 0))
               + int(s.get("peak_bytes_reserved", 0)) for s in stats)
