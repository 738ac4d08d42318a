"""Request lifecycle for the serving engine.

A ``Request`` is one generation job: prompt tokens + ``SamplingParams`` in,
a stream of generated tokens out. The object doubles as the caller's
handle — ``result()`` blocks until completion, ``stream()`` yields
detokenized text pieces as the engine produces them — and carries the
timestamps the serving telemetry is built from (queue wait, TTFT, TPOT).
"""

from __future__ import annotations

import dataclasses
import queue as _stdqueue
import threading
import time
from typing import Any, Callable, Iterator, List, Optional

#: request states
QUEUED = "queued"
RUNNING = "running"      # admitted to a slot (prefill or decode)
FINISHED = "finished"
REJECTED = "rejected"

#: finish reasons
FINISH_EOS = "eos"       # sampled the request's eos (token dropped)
FINISH_LENGTH = "length"  # hit max_new_tokens
FINISH_ERROR = "error"   # engine failure (req.error holds the message)
FINISH_EXPIRED = "expired"      # deadline passed while queued (shed)
FINISH_PREEMPTED = "preempted"  # drain timeout hit before it finished
FINISH_CANCELLED = "cancelled"  # client gave up (timeout/disconnect)
FINISH_SHED = "shed"            # SLO-rejected at submit (predicted miss)
FINISH_REJECTED = "rejected"    # bounded queue at capacity at submit


class RequestExpiredError(RuntimeError):
    """The request's deadline passed before it reached a slot — the engine
    shed it at an admission boundary instead of burning decode time on a
    result nobody is waiting for (``result()`` raises this; the HTTP
    frontend maps it to 504)."""


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request decode controls (all co-batchable in one compiled
    program — serving/engine.py samples with per-slot dynamic values).

    ``seed`` pins the request's PRNG: token i is drawn with
    ``generate.token_rng(PRNGKey(seed), i)`` regardless of slot placement
    or co-batched traffic, so identical (prompt, seed, params) requests
    reproduce — and match one-shot ``generate(rng=PRNGKey(seed))``.

    ``eos_id=None`` means the engine's model default; ``ignore_eos=True``
    disables eos stopping entirely (decode runs to the token budget).

    ``deadline_s`` is the client's patience in seconds from submission:
    past it the request is useless to whoever sent it, so the engine sheds
    it from the queue instead of decoding into the void (and rejects at
    submit time when the queue is already predicted to blow the deadline).
    ``None`` = no deadline (the engine may apply its default).

    ``adapter`` names a LoRA adapter in the engine's ``AdapterRegistry``
    (serving/adapters.py): the request decodes through base weights + that
    adapter's delta, co-batched with any other adapters' traffic in the
    same compiled program. ``None`` = the base model. Unknown names are
    rejected at submit (HTTP 400).

    ``spec`` opts this request out of speculative decoding
    (``--serve_spec_k`` engines) when False: its rows commit exactly one
    token per tick. Tokens are bit-identical either way (the accept rule
    is exact) — the opt-out exists for workloads whose acceptance rate is
    too low to be worth the drafting, e.g. high-entropy sampling. No-op
    on spec-off engines.
    """

    max_new_tokens: int = 128
    temperature: float = 0.0
    top_k: Optional[int] = None
    seed: int = 0
    eos_id: Optional[int] = None
    ignore_eos: bool = False
    deadline_s: Optional[float] = None
    adapter: Optional[str] = None
    spec: bool = True


class Request:
    """One generation request + its result handle."""

    def __init__(self, req_id: int, prompt_ids, params: SamplingParams,
                 on_token: Optional[Callable[["Request", int, str], None]]
                 = None):
        self.id = req_id
        self.prompt_ids = prompt_ids            # np.int32 (Tp,)
        self.params = params
        self.on_token = on_token
        self.state = QUEUED
        self.finish_reason: Optional[str] = None
        self.output_ids: List[int] = []
        self.text = ""
        self._detok_start = 0    # first output_ids index not yet in text
        self.slot: Optional[int] = None
        self.error: Optional[str] = None
        self._cancelled = False  # client gave up; retired at next boundary
        # router dispatch record (serving/router.py): {"replica": i,
        # "affinity": "adapter"|"prefix"|None, "route_s": seconds} — set
        # by the engine at submit (the decision precedes the Request's
        # existence), updated on a drain re-dispatch. None outside a
        # router: single-engine requests are unchanged.
        self.route: Optional[dict] = None
        # speculative-decoding ledger (spec engines only): drafted = k per
        # decode tick; accepted = the in-graph accepted-draft count
        self.spec_drafted = 0
        self.spec_accepted = 0
        # memory-ledger fields (obs/memory.py): peak slot-KV bytes this
        # request occupied (set at retirement, before the slot is freed)
        # and the KV bytes prefix-cache hits spared it from recomputing
        self.kv_bytes_peak = 0
        self.prefix_bytes_saved = 0
        # long-context tier: prompt longer than one device's prefill pane
        # (set at submit by a --serve_sp engine; the long-vs-short TTFT
        # split in summarize_metrics keys on it)
        self.long_prompt = False
        # timestamps (time.monotonic): submit -> admit (queue wait) ->
        # first token (TTFT) -> finish (TPOT over the decode tail).
        # wall_submit anchors the monotonic timeline to unix time so the
        # request's trace spans land on the same clock as every other
        # JSONL row (obs/trace.py joins them into one timeline)
        self.t_submit = time.monotonic()
        self.wall_submit = time.time()
        self.t_deadline: Optional[float] = (
            self.t_submit + params.deadline_s
            if params.deadline_s is not None else None)
        self.t_admit: Optional[float] = None
        self.t_first_token: Optional[float] = None
        self.t_finish: Optional[float] = None
        self._done = threading.Event()
        self._stream: "_stdqueue.Queue[Optional[str]]" = _stdqueue.Queue()

    # -- caller-side handle ----------------------------------------------

    def result(self, timeout: Optional[float] = None) -> "Request":
        """Block until the request finishes; returns self. Raises
        ``RequestExpiredError`` when the deadline shed it in the queue,
        ``RuntimeError`` for any other engine-side failure (fault
        isolation, restart, preemption, cancellation)."""
        if not self._done.wait(timeout):
            raise TimeoutError(f"request {self.id} not finished "
                               f"within {timeout}s")
        if self.finish_reason == FINISH_EXPIRED:
            raise RequestExpiredError(
                f"request {self.id} expired: {self.error}")
        if self.error is not None:
            raise RuntimeError(
                f"request {self.id} failed: {self.error}")
        return self

    def expired(self, now: Optional[float] = None) -> bool:
        """True when the request's deadline has passed (False without
        one). The engine checks this at admission boundaries."""
        if self.t_deadline is None:
            return False
        return (time.monotonic() if now is None else now) > self.t_deadline

    def stream(self, timeout: Optional[float] = None) -> Iterator[str]:
        """Yield detokenized text pieces as they are generated (ends when
        the request finishes). Raises ``TimeoutError`` — same as
        ``result()`` — when no piece arrives within ``timeout``."""
        while True:
            try:
                piece = self._stream.get(timeout=timeout)
            except _stdqueue.Empty:
                raise TimeoutError(
                    f"request {self.id}: no stream piece within "
                    f"{timeout}s") from None
            if piece is None:
                return
            yield piece

    @property
    def done(self) -> bool:
        return self._done.is_set()

    # -- engine-side metrics ---------------------------------------------

    def queue_wait_s(self) -> Optional[float]:
        if self.t_admit is None:
            return None
        return self.t_admit - self.t_submit

    def ttft_s(self) -> Optional[float]:
        if self.t_first_token is None:
            return None
        return self.t_first_token - self.t_submit

    def tpot_s(self) -> Optional[float]:
        """Mean time per output token AFTER the first (None with < 2)."""
        if (self.t_first_token is None or self.t_finish is None
                or len(self.output_ids) < 2):
            return None
        return ((self.t_finish - self.t_first_token)
                / (len(self.output_ids) - 1))

    def e2e_s(self) -> Optional[float]:
        if self.t_finish is None:
            return None
        return self.t_finish - self.t_submit

    def summary(self) -> dict:
        """The ``request_done`` telemetry payload."""
        out: dict = {
            "request_id": self.id,
            "n_prompt_tokens": int(len(self.prompt_ids)),
            "n_tokens": len(self.output_ids),
            "finish_reason": self.finish_reason,
            "slot": self.slot,
        }
        if self.params.deadline_s is not None:
            out["deadline_s"] = self.params.deadline_s
        if self.params.adapter is not None:
            out["adapter"] = self.params.adapter
        if self.route is not None:
            out["replica"] = self.route.get("replica")
        if self.spec_drafted:
            # acceptance telemetry (ISSUE 14): how much of this request's
            # decode the drafter paid for
            out["spec_drafted"] = self.spec_drafted
            out["spec_accepted"] = self.spec_accepted
        if self.kv_bytes_peak:
            out["kv_bytes_peak"] = self.kv_bytes_peak
        if self.prefix_bytes_saved:
            out["prefix_bytes_saved"] = self.prefix_bytes_saved
        if self.long_prompt:
            out["long_prompt"] = True
        for name, fn in (("queue_wait_s", self.queue_wait_s),
                         ("ttft_s", self.ttft_s), ("tpot_s", self.tpot_s),
                         ("e2e_s", self.e2e_s)):
            v = fn()
            if v is not None:
                out[name] = round(v, 6)
        return out

    # -- tracing ----------------------------------------------------------

    def _wall(self, t_mono: Optional[float]) -> Optional[float]:
        """Monotonic timestamp -> unix wall time via the submit anchor."""
        if t_mono is None:
            return None
        return self.wall_submit + (t_mono - self.t_submit)

    def outcome(self) -> str:
        """Terminal label for the span row: the finish reason, or the
        state for requests that never got one (rejected at submit)."""
        return self.finish_reason or self.state

    def trace_row(self) -> dict:
        """The request's ``span`` row (obs/metrics.log_span kwargs): one
        root ``request`` span [submit, terminal] with ``queued`` /
        ``prefill`` / ``decode`` children for every phase the request
        actually reached. Emitted ONCE, at the terminal transition — so
        a trace join on ``request_id`` sees exactly one closed tree per
        request, whatever its outcome."""
        t_end = self.t_finish if self.t_finish is not None else (
            time.monotonic())
        children = []
        if self.route is not None:
            # the router hop: the dispatch decision's wall time, pinned
            # at the root's start (the decision strictly precedes the
            # Request, so its duration is data on the route record)
            children.append({"name": "router", "t0": self.wall_submit,
                             "dur_s": max(float(
                                 self.route.get("route_s") or 0.0), 0.0)})
        children.append({"name": "queued", "t0": self.wall_submit,
                         "dur_s": (self.t_admit if self.t_admit is not None
                                   else t_end) - self.t_submit})
        if self.t_admit is not None:
            t_ft = (self.t_first_token if self.t_first_token is not None
                    else min(t_end, self.t_admit))
            children.append({"name": "prefill",
                             "t0": self._wall(self.t_admit),
                             "dur_s": max(t_ft - self.t_admit, 0.0)})
            if self.t_first_token is not None:
                children.append({"name": "decode",
                                 "t0": self._wall(self.t_first_token),
                                 "dur_s": max(t_end - self.t_first_token,
                                              0.0)})
        row = {
            "name": "request", "cat": "request",
            "t0": self.wall_submit,
            "dur_s": max(t_end - self.t_submit, 0.0),
            "children": children,
            "request_id": self.id,
            # the submit stamp on the monotonic clock itself (the tick
            # records' clock: obs/schema.TICK_RECORD_FIELDS), so a reader
            # can lay a request beside the ticks that served it
            "t_submit": self.t_submit,
            "outcome": self.outcome(),
            "n_prompt_tokens": int(len(self.prompt_ids)),
            "n_tokens": len(self.output_ids),
        }
        if self.slot is not None:
            row["slot"] = self.slot
        if self.params.adapter is not None:
            row["adapter"] = self.params.adapter
        if self.route is not None:
            row["replica"] = self.route.get("replica")
            if self.route.get("affinity"):
                row["affinity"] = self.route["affinity"]
        if self.error is not None:
            row["error"] = self.error
        return row

    # -- engine internals -------------------------------------------------

    def _push_piece(self, piece: str) -> None:
        self._stream.put(piece)

    def _mark_done(self) -> None:
        self._stream.put(None)
        self._done.set()


def resolve_eos(params: SamplingParams, default_eos: Optional[int]
                ) -> Optional[int]:
    """The eos id this request actually stops on (None = never)."""
    if params.ignore_eos:
        return None
    return params.eos_id if params.eos_id is not None else default_eos


_COUNTER = threading.Lock()
_next_id = [0]


def next_request_id() -> int:
    with _COUNTER:
        _next_id[0] += 1
        return _next_id[0]


def seed_request_ids(start: int) -> None:
    """Move the id counter to ``start`` (next id = start + 1). Fleet
    worker processes seed a disjoint per-(replica, incarnation) range so
    their LOCAL request ids can never collide with the supervisor's
    fleet-wide ids in merged telemetry — a trace join on ``request_id``
    must mean one request, whichever process stamped the row. Only moves
    forward: a late seed never re-issues ids already handed out."""
    with _COUNTER:
        _next_id[0] = max(_next_id[0], int(start))


__all__: List[Any] = [
    "QUEUED", "RUNNING", "FINISHED", "REJECTED",
    "FINISH_EOS", "FINISH_LENGTH", "FINISH_ERROR",
    "FINISH_EXPIRED", "FINISH_PREEMPTED", "FINISH_CANCELLED",
    "FINISH_SHED", "FINISH_REJECTED",
    "RequestExpiredError",
    "SamplingParams", "Request", "resolve_eos", "next_request_id",
    "seed_request_ids",
]
