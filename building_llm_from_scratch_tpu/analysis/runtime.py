"""Runtime sanitizers: the dynamic twins of the GL01x/GL03x static rules.

**Transfer sentry** (``no_implicit_device_to_host``): proves a code
region performs ZERO implicit device->host transfers. Two layers, both
armed together:

  - ``jax.transfer_guard_device_to_host("disallow")`` — the real C++
    guard. On TPU/GPU it rejects every implicit d->h transfer while
    letting explicit ``jax.device_get`` through. On the CPU backend the
    device buffer *is* host memory, so this guard never fires there —
    which is why the second layer exists;
  - a Python-level sentry that patches the jax array type's implicit
    conversion dunders (``__float__``/``__int__``/``__bool__``/
    ``__index__``/``item``) and wraps ``numpy.asarray``/``numpy.array``
    to reject jax arrays. These are exactly the idioms GL01x flags
    statically, intercepted portably on every backend.
    ``jax.device_get`` does not route through any of them (verified),
    so the sanctioned explicit fetch stays legal.

The sentry is test-harness machinery: patching a type's dunders is
process-global, so enter the context in exactly one test at a time
(tests are the only caller; the tier-1 gate runs them single-process).

**LockOrderSanitizer**: wraps real locks, records each thread's
acquisition stack, and flags (a) order inversions — lock B acquired
under A somewhere, A under B elsewhere: the deadlock pattern GL032
detects statically, here observed on live schedules — and (b) holds
longer than ``hold_threshold_s`` (the PR 6 wedge class). ``instrument``
swaps sanitized wrappers into an object's lock attributes so a real
engine can tick under observation.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple


class ImplicitTransferError(RuntimeError):
    """An implicit device->host transfer happened inside the sentry."""


_SENTRY_DUNDERS = ("__float__", "__int__", "__bool__", "__index__",
                   "item", "tolist")


@contextmanager
def no_implicit_device_to_host(allow: Tuple[str, ...] = ()):
    """Context manager rejecting implicit d->h transfers inside it.

    ``allow`` names dunders to leave unpatched (escape hatch for
    diagnosing a failure one idiom at a time). Explicit fetches must go
    through ``jax.device_get`` — the engine tick and the trainer's
    cadence flush already do (graft-lint GL01x keeps it that way)."""
    import jax
    import numpy as _np

    # the concrete class behind jax.Array (its dunders are what convert)
    array_cls = type(jax.numpy.zeros(()))
    saved: Dict[str, object] = {}

    def _make_trap(name: str, orig):
        def trap(self, *args, **kwargs):
            # tracers and committed arrays share the type's dunders only
            # for concrete arrays; anything reaching here is a real
            # host conversion of device-backed data
            raise ImplicitTransferError(
                f"implicit device->host transfer via jax.Array.{name} — "
                f"hot paths must fetch explicitly with jax.device_get "
                f"(graft-lint GL01x)")
        trap.__name__ = name
        return trap

    real_asarray, real_array = _np.asarray, _np.array

    def _guard_np(fn, label):
        def wrapped(obj, *args, **kwargs):
            if isinstance(obj, jax.Array):
                raise ImplicitTransferError(
                    f"implicit device->host transfer via np.{label}() on "
                    f"a jax.Array — use jax.device_get (graft-lint GL012)")
            return fn(obj, *args, **kwargs)
        return wrapped

    with jax.transfer_guard_device_to_host("disallow"):
        try:
            for name in _SENTRY_DUNDERS:
                if name in allow or not hasattr(array_cls, name):
                    continue
                saved[name] = getattr(array_cls, name)
                setattr(array_cls, name, _make_trap(name, saved[name]))
            _np.asarray = _guard_np(real_asarray, "asarray")
            _np.array = _guard_np(real_array, "array")
            yield
        finally:
            _np.asarray, _np.array = real_asarray, real_array
            for name, orig in saved.items():
                setattr(array_cls, name, orig)


# ---------------------------------------------------------------------------
# Lock-order sanitizer
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class LockOrderViolation:
    kind: str                 # "inversion" | "hold_time"
    lock: str
    other: Optional[str]
    thread: str
    detail: str


class _SanitizedLock:
    """Context-manager/acquire-release wrapper over a real lock. Reentrant
    acquisitions of the same wrapper (RLock semantics) are recorded once —
    re-entry cannot invert an order."""

    def __init__(self, sanitizer: "LockOrderSanitizer", name: str, inner):
        self._san = sanitizer
        self.name = name
        self._inner = inner

    def acquire(self, blocking: bool = True, timeout: float = -1):
        got = (self._inner.acquire(blocking, timeout)
               if timeout != -1 else self._inner.acquire(blocking))
        if got:
            try:
                self._san._on_acquire(self)
            except BaseException:
                # raise_on_violation mode: don't leak the inner lock when
                # the sanitizer aborts the acquisition
                self._inner.release()
                raise
        return got

    def release(self):
        self._san._on_release(self)
        self._inner.release()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()
        return False

    # Condition(lock) compatibility passthroughs
    def _is_owned(self):
        owned = getattr(self._inner, "_is_owned", None)
        return owned() if owned else False

    def __repr__(self):
        return f"<sanitized {self.name} over {self._inner!r}>"


class LockOrderSanitizer:
    """Records per-thread lock-acquisition order across wrapped locks.

    - ``wrap(lock, name)`` returns a drop-in wrapper feeding the
      sanitizer; ``instrument(obj, attrs)`` swaps wrappers into an
      object's lock attributes in place.
    - an acquisition of B while holding A registers order A->B; if B->A
      was ever registered (any thread), an **inversion** violation is
      recorded — the runtime twin of graft-lint GL032.
    - releasing a lock held longer than ``hold_threshold_s`` records a
      **hold_time** violation — wedge-class behavior (PR 6) that static
      analysis cannot see.

    Violations are collected, not raised (``raise_on_violation=True``
    flips that for tests that want the stack at the exact site).
    """

    def __init__(self, hold_threshold_s: float = 0.0,
                 raise_on_violation: bool = False):
        self.hold_threshold_s = float(hold_threshold_s)
        self.raise_on_violation = raise_on_violation
        self.violations: List[LockOrderViolation] = []
        self._mu = threading.Lock()
        self._orders: Dict[Tuple[str, str], str] = {}
        self._held = threading.local()

    # -- wiring -----------------------------------------------------------

    def wrap(self, lock, name: str) -> _SanitizedLock:
        return _SanitizedLock(self, name, lock)

    def instrument(self, obj, attrs: Tuple[str, ...],
                   prefix: str = "") -> List[str]:
        """Replace ``obj.<attr>`` locks with sanitized wrappers; returns
        the wrapped names. Attributes that are absent are skipped."""
        wrapped = []
        label = prefix or type(obj).__name__
        for attr in attrs:
            inner = getattr(obj, attr, None)
            if inner is None:
                continue
            name = f"{label}.{attr}"
            setattr(obj, attr, self.wrap(inner, name))
            wrapped.append(name)
        return wrapped

    # -- event sinks ------------------------------------------------------

    def _stack(self) -> List[Tuple["_SanitizedLock", float, int]]:
        if not hasattr(self._held, "stack"):
            self._held.stack = []
        return self._held.stack

    def _record(self, violation: LockOrderViolation) -> None:
        with self._mu:
            self.violations.append(violation)
        if self.raise_on_violation:
            raise RuntimeError(f"lock sanitizer: {violation}")

    def _on_acquire(self, lock: _SanitizedLock) -> None:
        stack = self._stack()
        thread = threading.current_thread().name
        for held, _t0, _n in stack:
            if held is lock:
                # reentrant re-acquire: bump the depth marker, no edge
                for i, (lk, t0, n) in enumerate(stack):
                    if lk is lock:
                        stack[i] = (lk, t0, n + 1)
                return
        for held, _t0, _n in stack:
            edge = (held.name, lock.name)
            inverse = (lock.name, held.name)
            with self._mu:
                first = self._orders.setdefault(edge, thread)
                inverted = inverse in self._orders
            if inverted:
                self._record(LockOrderViolation(
                    kind="inversion", lock=lock.name, other=held.name,
                    thread=thread,
                    detail=f"{held.name} -> {lock.name} here, but "
                           f"{lock.name} -> {held.name} was taken by "
                           f"thread '{self._orders[inverse]}'"))
            del first
        stack.append((lock, time.monotonic(), 1))

    def _on_release(self, lock: _SanitizedLock) -> None:
        stack = self._stack()
        for i in range(len(stack) - 1, -1, -1):
            lk, t0, n = stack[i]
            if lk is lock:
                if n > 1:
                    stack[i] = (lk, t0, n - 1)
                    return
                held_for = time.monotonic() - t0
                del stack[i]
                if (self.hold_threshold_s > 0
                        and held_for > self.hold_threshold_s):
                    self._record(LockOrderViolation(
                        kind="hold_time", lock=lock.name, other=None,
                        thread=threading.current_thread().name,
                        detail=f"held {held_for:.3f}s > threshold "
                               f"{self.hold_threshold_s:.3f}s"))
                return

    # -- reporting --------------------------------------------------------

    def inversions(self) -> List[LockOrderViolation]:
        return [v for v in self.violations if v.kind == "inversion"]

    def report(self) -> str:
        if not self.violations:
            return "lock sanitizer: no violations"
        lines = [f"lock sanitizer: {len(self.violations)} violation(s)"]
        for v in self.violations:
            lines.append(f"  [{v.kind}] {v.lock} (thread {v.thread}): "
                         f"{v.detail}")
        return "\n".join(lines)


__all__ = [
    "ImplicitTransferError",
    "LockOrderSanitizer",
    "LockOrderViolation",
    "no_implicit_device_to_host",
]
