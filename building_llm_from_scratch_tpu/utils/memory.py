"""Parameter counting and device-memory accounting.

Reference equivalents:
  - count_params / static 4N-Adam estimate  (utils.py:112-129)
  - dynamic param+grad+buffer estimate      (utils.py:131-144)
  - CUDA peak-memory tracking               (utils.py:149-166)

On TPU the peak-stat source is ``device.memory_stats()`` (HBM view); on CPU
test runs stats may be unavailable and we degrade gracefully.
"""

from __future__ import annotations

import sys
from typing import Any, Dict, Optional

import jax
import numpy as np

from building_llm_from_scratch_tpu.configs import DTYPE_BYTES


def count_params(params: Any) -> int:
    """Total number of scalar parameters in a pytree."""
    leaves = jax.tree_util.tree_leaves(params)
    return int(sum(np.prod(l.shape) if hasattr(l, "shape") else 1 for l in leaves))


def estimate_memory_static(n_params: int, dtype: str = "fp32",
                           optimizer: str = "adamw") -> float:
    """Static memory estimate in GB using the 4N Adam rule
    (params + grads + Adam m/v), reference utils.py:112-129."""
    mult = 4 if optimizer == "adamw" else 2
    return mult * n_params * DTYPE_BYTES[dtype] / 1024**3


def estimate_memory_dynamic(n_params: int, n_trainable: int,
                            dtype: str = "fp32") -> float:
    """Dynamic params+grads estimate in GB (reference utils.py:131-144:
    parameters + gradients-for-trainables + buffers; this framework keeps
    no torch-style buffers — RoPE/mask constants live in the jit program)."""
    return (n_params + n_trainable) * DTYPE_BYTES[dtype] / 1024**3


def host_rss_bytes() -> Optional[int]:
    """This process's resident set size in bytes, or None when
    undeterminable. Host-RAM growth (data pipeline buffers, checkpoint
    staging, metric accumulation) is invisible to ``device.memory_stats``
    — a leaking input pipeline OOMs the HOST first. Reads /proc (Linux,
    the TPU VM case) and falls back to getrusage peak-RSS elsewhere."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        pass
    try:
        import resource

        peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        # linux reports KiB, macOS bytes
        return peak * 1024 if sys.platform != "darwin" else peak
    except Exception:
        return None


def device_memory_stats(device: Optional[jax.Device] = None) -> Dict[str, int]:
    """Best-effort HBM stats for one device (bytes)."""
    device = device or jax.local_devices()[0]
    try:
        stats = device.memory_stats()
    except Exception:
        return {}
    return {k: v for k, v in (stats or {}).items() if isinstance(v, int)}


def log_device_memory(logger, prefix: str = "") -> None:
    """Log peak/in-use HBM per local device (reference utils.py:158-166)."""
    for d in jax.local_devices():
        stats = device_memory_stats(d)
        if not stats:
            if d.platform == "tpu":
                raise RuntimeError(
                    f"{d} reports no memory_stats(): a TPU always does, so "
                    f"something is wrong with the runtime — refusing to "
                    f"print a guess as a peak")
            logger.info("%s%s: no memory_stats() on platform '%s' "
                        "(not measured)", prefix, d, d.platform)
            continue
        in_use = stats.get("bytes_in_use", 0) / 1024**3
        peak = stats.get("peak_bytes_in_use", 0) / 1024**3
        limit = stats.get("bytes_limit", 0) / 1024**3
        logger.info("%s%s: in_use=%.2fGB peak=%.2fGB limit=%.2fGB",
                    prefix, d, in_use, peak, limit)
