"""Per-module console loggers (reference: logger.py:4-42).

Same behavior: named loggers, DEBUG level, timestamped format,
duplicate-handler guard, no propagation. Additionally process-index aware:
on multi-host TPU runs only process 0 emits below-WARNING records by
default (replacing the reference's ``rank == 0`` gating scattered through
train.py) — N hosts otherwise print N interleaved copies of every INFO
line. Set ``BLLM_LOG_ALL_HOSTS=1`` to see every host (debugging a single
wedged worker).

The gating is a lazy handler filter, NOT an import-time ``process_index``
call: these loggers are created at module import, long before
``jax.distributed.initialize``, and asking jax for a process index would
initialize the backend prematurely. The filter only consults distributed
state that already exists; with none, it assumes single-process (where
process 0 is everyone).
"""

from __future__ import annotations

import logging
import os
import sys

_FORMAT = "%(asctime)s - %(name)s - %(levelname)s - %(message)s"


def _coordinator_if_known() -> bool:
    """True unless this process is provably a non-coordinator. Never
    initializes jax (see module docstring)."""
    jax = sys.modules.get("jax")
    if jax is None or not jax.distributed.is_initialized():
        return True         # one process, or not a multi-host job (yet)
    # after jax.distributed.initialize() the backend is about to come up
    # anyway; before it, the branch above never touches it
    return jax.process_index() == 0


class _CoordinatorFilter(logging.Filter):
    """Drop below-WARNING records on non-coordinator processes (the
    process-0 INFO gating the module docstring always promised).
    ``BLLM_LOG_ALL_HOSTS=1`` disables the gate for debugging."""

    def filter(self, record: logging.LogRecord) -> bool:
        if record.levelno >= logging.WARNING:
            return True
        if os.environ.get("BLLM_LOG_ALL_HOSTS"):
            return True
        return _coordinator_if_known()


def setup_logger(name: str, level: int | None = None) -> logging.Logger:
    """Get/create a named logger.

    ``level`` is applied whenever passed explicitly; when omitted, the
    DEBUG default applies only to a logger that has no level yet — a
    repeat default call no longer clobbers a level an earlier explicit
    call chose.
    """
    logger = logging.getLogger(name)
    if level is not None:
        logger.setLevel(level)
    elif logger.level == logging.NOTSET:
        logger.setLevel(logging.DEBUG)
    if not logger.handlers:
        handler = logging.StreamHandler(sys.stdout)
        handler.setFormatter(logging.Formatter(_FORMAT))
        handler.addFilter(_CoordinatorFilter())
        logger.addHandler(handler)
    logger.propagate = False
    return logger


def is_coordinator() -> bool:
    """True on the process that should do host-side IO (rank-0 analog)."""
    import jax

    return jax.process_index() == 0
