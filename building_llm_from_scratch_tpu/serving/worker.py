"""The fleet's worker: one replica engine behind a process boundary.

Three layers, shared by production serving (``serving/fleet.py``), the
bench harness (``scripts/bench_fleet_worker.py``), and the tests:

  - ``EngineSpec`` + ``apply_host_env`` + ``build_engine`` — a
    JSON-serializable recipe for rebuilding the SAME engine in another
    process. Params are reconstructed, not shipped: ``init_params(cfg,
    PRNGKey(seed))`` is deterministic, and ``init_params_from`` loads an
    exported checkpoint — either way every worker holds identical
    weights, which is what makes prefix-pane keys (config-fingerprinted)
    portable across the fleet.
  - ``FakeEngine`` — a jax-free engine stand-in with the same
    worker-facing surface (bounded queue, slot concurrency, typed
    admission errors, drain semantics, optionally a REAL ``PrefixStore``
    over deterministic numpy panes). Fault-injection tests exercise the
    whole transport/supervisor/kill-9/handoff machinery in milliseconds
    instead of compile-seconds.
  - ``WorkerServer`` + ``main`` — the subprocess entrypoint: an
    ``RpcServer`` on a unix socket (submit/adopt/cancel/steal_queue/
    drain/healthz/export_panes/import_panes/...), an event-push channel
    (heartbeats + per-request admitted/piece/done/failed), its own
    metrics JSONL, and a clean SIGTERM drain. Stdout carries exactly one
    ready line and then stays open: the supervisor reads EOF on it as a
    death signal no heartbeat timeout can beat.

Import-light on purpose: jax is imported only inside ``build_engine``,
so the supervisor (and fake-mode workers) never pay for — or depend
on — an accelerator runtime.
"""

from __future__ import annotations

import argparse
import base64
import dataclasses
import json
import os
import queue as _stdqueue
import signal
import sys
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from building_llm_from_scratch_tpu.obs.metrics import (
    configure_metrics,
    get_metrics,
)
from building_llm_from_scratch_tpu.serving.kvcache import (
    KVCachePolicy,
    PrefixStore,
    cache_nbytes,
)
from building_llm_from_scratch_tpu.serving.queue import (
    EngineDrainingError,
    RequestQueue,
    SLOShedError,
)
from building_llm_from_scratch_tpu.serving.request import (
    FINISH_CANCELLED,
    FINISH_EXPIRED,
    FINISH_LENGTH,
    FINISH_PREEMPTED,
    FINISHED,
    RUNNING,
    Request,
    SamplingParams,
    next_request_id,
    seed_request_ids,
)
from building_llm_from_scratch_tpu.serving.transport import (
    DETACH,
    RpcServer,
    RpcStats,
    TransportError,
    send_frame,
)
from building_llm_from_scratch_tpu.utils.logging import setup_logger

logger = setup_logger(__name__)


# ---------------------------------------------------------------------------
# the engine recipe
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class EngineSpec:
    """Everything a worker process needs to rebuild its replica engine.

    ``engine`` holds ``DecodeEngine`` keyword arguments (n_slots,
    max_len, max_queue, ...); ``kv_policy`` holds ``KVCachePolicy``
    fields; ``fake`` non-None selects the jax-free ``FakeEngine`` (its
    constructor kwargs). The whole spec round-trips through JSON — it IS
    the worker's command line.
    """

    model: str = "GPT2"
    size: str = "124M"
    dtype: str = "auto"              # "auto" = bf16 on tpu else fp32
    debug: bool = False
    seed: int = 0
    init_params_from: Optional[str] = None
    tokenizer: str = "none"          # "byte" | "none"
    devices: int = 1                 # forced-host CPU device count
    tp: int = 1
    engine: Dict[str, Any] = dataclasses.field(default_factory=dict)
    kv_policy: Optional[Dict[str, Any]] = None
    adapters: Optional[Dict[str, str]] = None     # name -> npz path
    spec_k: int = 0
    fake: Optional[Dict[str, Any]] = None

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))

    @classmethod
    def from_json(cls, s: str) -> "EngineSpec":
        return cls(**json.loads(s))


def apply_host_env(devices: int) -> None:
    """Forced-host device count, BEFORE jax imports: each worker process
    pins its own (the flag only shapes the CPU platform; the parent's jax
    — if any — is untouched). The PLATFORM is never chosen here: a
    worker runs where its environment says (``JAX_PLATFORMS`` from
    whoever spawned it), else on jax's default — never on the CPU by
    default on a host that has a chip."""
    if devices > 1:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={devices}").strip()


def build_engine(spec: EngineSpec, replica: Optional[int] = None):
    """Rebuild the replica engine a spec describes (jax imported here)."""
    if spec.fake is not None:
        return FakeEngine(**spec.fake)

    import jax

    from building_llm_from_scratch_tpu.configs import get_config
    from building_llm_from_scratch_tpu.models import init_params
    from building_llm_from_scratch_tpu.serving.engine import DecodeEngine

    dtype = spec.dtype
    if dtype == "auto":
        dtype = "bf16" if jax.default_backend() == "tpu" else "fp32"
    cfg = get_config(spec.model, spec.size, dtype=dtype, debug=spec.debug)
    params = init_params(cfg, jax.random.PRNGKey(spec.seed))
    if spec.init_params_from:
        from building_llm_from_scratch_tpu.training.checkpoint import (
            load_exported_params,
        )

        params = load_exported_params(spec.init_params_from, params)

    tokenizer = None
    if spec.tokenizer == "byte":
        from building_llm_from_scratch_tpu.data.tokenizers import (
            ByteTokenizer,
        )

        tokenizer = ByteTokenizer()

    mesh_plan = None
    if spec.tp > 1:
        from building_llm_from_scratch_tpu.parallel import build_mesh_plan

        mesh_plan = build_mesh_plan("tp", tp=spec.tp)

    adapters = None
    if spec.adapters:
        from building_llm_from_scratch_tpu.serving.adapters import (
            AdapterRegistry,
        )

        adapters = AdapterRegistry(cfg, params)
        for name, path in spec.adapters.items():
            adapters.load(name, path)

    kv_policy = (KVCachePolicy(**spec.kv_policy)
                 if spec.kv_policy else None)
    return DecodeEngine(cfg, params, tokenizer,
                        adapters=adapters, kv_policy=kv_policy,
                        spec_k=spec.spec_k, mesh_plan=mesh_plan,
                        replica=replica, **spec.engine)


# ---------------------------------------------------------------------------
# pane serialization (prefix handoff)
# ---------------------------------------------------------------------------

def encode_panes(panes: Any) -> Any:
    """Pane pytree -> JSON-able tree (arrays as base64 + dtype + shape).

    ``np.asarray`` pulls device arrays to host; byte-exactness is the
    contract the handoff test asserts."""
    if isinstance(panes, dict):
        return {k: encode_panes(v) for k, v in panes.items()}
    arr = np.asarray(panes)
    return {"__nd__": base64.b64encode(
                np.ascontiguousarray(arr).tobytes()).decode("ascii"),
            "dtype": arr.dtype.str, "shape": list(arr.shape)}


def decode_panes(tree: Any) -> Any:
    if isinstance(tree, dict) and "__nd__" in tree:
        arr = np.frombuffer(
            base64.b64decode(tree["__nd__"]),
            dtype=np.dtype(tree["dtype"])).reshape(tree["shape"])
        return arr.copy()                      # writable, owns its bytes
    return {k: decode_panes(v) for k, v in tree.items()}


# ---------------------------------------------------------------------------
# the jax-free engine stand-in
# ---------------------------------------------------------------------------

class FakeEngine:
    """A decode engine with the physics removed.

    Same worker-facing surface and admission semantics as
    ``DecodeEngine`` (bounded queue -> ``QueueFullError``, drain ->
    ``EngineDrainingError``, slot-limited concurrency, per-token
    ``on_token`` callbacks, terminal finish reasons) but tokens are a
    deterministic function of the prompt and each costs ``tpot_s`` of
    wall time. With ``prefix_chunk > 0`` it runs a REAL ``PrefixStore``
    whose panes are a pure function of the prefix tokens — so pane
    handoff is byte-checkable without a model.
    """

    def __init__(self, *, n_slots: int = 2, max_queue: int = 16,
                 tpot_s: float = 0.01, default_max_new_tokens: int = 16,
                 prefix_chunk: int = 0,
                 prefix_budget_bytes: int = 8 * 1024 * 1024,
                 vocab_size: int = 96):
        self.n_slots = int(n_slots)
        self.queue = RequestQueue(max_queue)
        self.tpot_s = float(tpot_s)
        self.default_max_new_tokens = int(default_max_new_tokens)
        self.vocab_size = int(vocab_size)
        self.warmed_up = True
        self.n_recompiles = 0
        self.n_restarts = 0
        self._draining = False
        self._dead: Optional[str] = None
        self._t0 = time.monotonic()
        self._lock = threading.Lock()
        self._active: List[Request] = []               # guarded-by: _lock
        self._finished = 0                             # guarded-by: _lock
        self._failed = 0                               # guarded-by: _lock
        self._ticks = 0                                # guarded-by: _lock
        self.prefix_store = (PrefixStore(
            "fake-engine", chunk_tokens=prefix_chunk,
            budget_bytes=prefix_budget_bytes,
            pane_tokens=4 * prefix_chunk)
            if prefix_chunk > 0 else None)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- lifecycle ---------------------------------------------------------

    def warmup(self) -> None:
        pass

    def start(self) -> None:
        if self._thread is None:
            self._thread = threading.Thread(target=self._loop,
                                            name="fake-decode", daemon=True)
            self._thread.start()

    def shutdown(self, drain: bool = True) -> None:
        if drain and not self._draining:
            self.drain(timeout=5.0)
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)

    def drain(self, timeout: float = 30.0) -> dict:
        self._draining = True
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._lock:
                idle = not self._active and len(self.queue) == 0
            if idle:
                break
            time.sleep(0.002)
        preempted = 0
        while True:                       # whatever is left gets failed
            req = self.queue.get_nowait()
            if req is None:
                break
            self._finish(req, FINISH_PREEMPTED, error="drain timeout")
            preempted += 1
        with self._lock:
            leftovers = list(self._active)
        for req in leftovers:
            self._finish(req, FINISH_PREEMPTED, error="drain timeout")
            preempted += 1
        return {"preempted": preempted}

    def run_until_idle(self) -> None:
        while True:
            with self._lock:
                if not self._active and len(self.queue) == 0:
                    return
            time.sleep(0.002)

    # -- admission ---------------------------------------------------------

    def submit(self, prompt, params: Optional[SamplingParams] = None,
               block: bool = False, timeout: Optional[float] = None,
               on_token=None, route=None) -> Request:
        if self._draining:
            raise EngineDrainingError("engine is draining",
                                      retry_after_s=1.0)
        params = params or SamplingParams(
            max_new_tokens=self.default_max_new_tokens)
        if params.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if params.deadline_s is not None:
            # deadline-aware admission, FakeEngine style: the decode cost
            # is exactly max_new_tokens ticks of tpot_s, so a deadline
            # below that is a predicted miss — shed now (mirrors
            # DecodeEngine's TPOT-EWMA estimate, deterministic here)
            est = params.max_new_tokens * self.tpot_s
            if params.deadline_s < est:
                raise SLOShedError(
                    f"deadline {params.deadline_s:.3f}s < estimated "
                    f"decode {est:.3f}s", retry_after_s=est)
        prompt_ids = np.asarray(prompt, np.int32).reshape(-1)
        req = Request(next_request_id(), prompt_ids, params, on_token)
        req.route = route
        self.queue.put(req, block=block, timeout=timeout)
        return req

    def adopt(self, req: Request, timeout: float = 5.0) -> None:
        if self._dead is not None:
            raise RuntimeError(f"engine is dead: {self._dead}")
        if self._draining:
            raise EngineDrainingError("engine is draining: "
                                      "admission closed")
        self.queue.put(req, block=True, timeout=timeout)

    def cancel(self, req: Request) -> bool:
        if self.queue.remove(req):
            self._finish(req, FINISH_CANCELLED, error="cancelled")
            return True
        with self._lock:
            if req in self._active:
                req._cancelled = True
                return True
        return False

    # -- the "decode" loop -------------------------------------------------

    def _loop(self) -> None:
        while not self._stop.is_set():
            expired: List[Request] = []
            with self._lock:
                while len(self._active) < self.n_slots:
                    req = self.queue.get_nowait()
                    if req is None:
                        break
                    if req.expired():
                        # queue-TTL shed at the admission boundary —
                        # finishing outside the lock (_finish re-takes it)
                        expired.append(req)
                        continue
                    self._admit_locked(req)
                active = list(self._active)
            for req in expired:
                self._finish(req, FINISH_EXPIRED,
                             error="deadline expired in queue")
            if not active:
                time.sleep(0.002)
                continue
            time.sleep(self.tpot_s)
            with self._lock:
                self._ticks += 1
            for req in active:
                self._step(req)

    # holds: _lock
    def _admit_locked(self, req: Request) -> None:
        req.t_admit = time.monotonic()
        req.state = RUNNING
        req.slot = len(self._active)
        self._active.append(req)
        if self.prefix_store is not None:
            self._prefix_probe(req)

    def _prefix_probe(self, req: Request) -> None:
        """Real PrefixStore traffic over deterministic panes: a hit
        reuses the stored pane (and counts), a miss computes + inserts —
        the handoff test's donor/adoptee behavior without a model."""
        store = self.prefix_store
        span = store.storable_span(len(req.prompt_ids))
        if span <= 0:
            return
        tag = req.params.adapter or ""
        hit_span, entry = store.match(req.prompt_ids, tag)
        if entry is not None:
            get_metrics().event("prefix_hit", request_id=req.id,
                                span_tokens=hit_span,
                                prompt_tokens=int(len(req.prompt_ids)))
            store.release(entry)
            return
        get_metrics().event("prefix_miss", request_id=req.id,
                            prompt_tokens=int(len(req.prompt_ids)))
        store.insert(req.prompt_ids[:span], tag,
                     self._panes_for(req.prompt_ids[:span]))

    @staticmethod
    def _panes_for(token_ids) -> Dict[str, np.ndarray]:
        """Byte-deterministic pane tree: a pure function of the tokens,
        so donor-computed and locally-computed panes are identical."""
        ids = np.asarray(token_ids, np.float32)
        return {"k": (ids * 0.5 + 1.0).reshape(1, 1, -1, 1),
                "v": (ids * 0.25 - 2.0).reshape(1, 1, -1, 1)}

    def _step(self, req: Request) -> None:
        if req.done:
            return
        if req._cancelled:
            self._finish(req, FINISH_CANCELLED, error="cancelled")
            return
        tok = int((int(req.prompt_ids[-1]) + len(req.output_ids))
                  % self.vocab_size)
        if req.t_first_token is None:
            req.t_first_token = time.monotonic()
        req.output_ids.append(tok)
        piece = chr(0x20 + tok % 94)
        req.text += piece
        if req.on_token is not None:
            req.on_token(req, tok, piece)
        if len(req.output_ids) >= req.params.max_new_tokens:
            self._finish(req, FINISH_LENGTH)

    def _finish(self, req: Request, reason: str,
                error: Optional[str] = None) -> None:
        with self._lock:
            if req.state == FINISHED:
                return
            req.state = FINISHED
            req.finish_reason = reason
            req.error = error
            req.t_finish = time.monotonic()
            if req in self._active:
                self._active.remove(req)
            if error is None:
                self._finished += 1
            else:
                self._failed += 1
        req._mark_done()

    # -- introspection (the engine-shaped surface) -------------------------

    @property
    def draining(self) -> bool:
        return self._draining

    def queue_capacity(self) -> int:
        return self.queue.max_size

    def estimate_queue_clear_s(self) -> Optional[float]:
        return None

    def service_snapshot(self) -> dict:
        with self._lock:
            n_active = len(self._active)
        return {"queue_depth": len(self.queue),
                "queue_capacity": self.queue.max_size,
                "n_active": n_active, "n_slots": self.n_slots,
                "tpot_ewma": self.tpot_s, "tokens_ewma": None,
                "draining": self._draining, "dead": self._dead is not None}

    def stats(self) -> dict:
        with self._lock:
            out = {"requests_finished": self._finished,
                   "requests_failed": self._failed,
                   "n_ticks": self._ticks,
                   "n_recompiles": self.n_recompiles,
                   "n_restarts": self.n_restarts,
                   "draining": self._draining}
        if self.prefix_store is not None:
            out["prefix_store"] = self.prefix_store.stats()
        return out

    def healthz_payload(self) -> dict:
        snap = self.service_snapshot()
        with self._lock:
            ticks, finished, failed = (self._ticks, self._finished,
                                       self._failed)
        status = "serving"
        if self._dead is not None:
            status = "dead"
        elif self._draining:
            status = "draining"
        return {"status": status, "slots": self.n_slots,
                "active": snap["n_active"],
                "queue_depth": snap["queue_depth"],
                "queue_capacity": snap["queue_capacity"],
                "warmed_up": True, "draining": self._draining,
                "restarts": 0,
                "uptime_s": round(time.monotonic() - self._t0, 3),
                "n_ticks": ticks,
                "occupancy": round(snap["n_active"]
                                   / max(self.n_slots, 1), 3),
                "counters": {"requests_finished": finished,
                             "requests_failed": failed}}

    def metrics_snapshot(self):
        with self._lock:
            counters = {"serve_requests_finished_total": self._finished,
                        "serve_requests_failed_total": self._failed}
            gauges = {"serve_active_slots": float(len(self._active)),
                      "serve_queue_depth": float(len(self.queue))}
        return counters, gauges, {}


# ---------------------------------------------------------------------------
# the worker RPC server
# ---------------------------------------------------------------------------

class _WEntry:
    __slots__ = ("client_id", "req", "stolen")

    def __init__(self, client_id: int, req: Request):
        self.client_id = client_id
        self.req = req
        self.stolen = False


class WorkerServer:
    """RPC facade over one replica engine inside the worker process.

    Control methods run on transport connection threads; request
    progress (admitted/piece/done/failed) and heartbeats push over the
    subscribed event channel. ``client_id`` — the SUPERVISOR's request
    id — is the cross-process request identity: piece callbacks close
    over it, so no map lookup can race the engine admitting a request
    before ``submit`` returns.
    """

    def __init__(self, engine, socket_path: str, *,
                 replica: int = 0, heartbeat_s: float = 0.5,
                 max_frame_bytes: Optional[int] = None,
                 incarnation: int = 0):
        self.engine = engine
        self.replica = replica
        self.incarnation = incarnation
        self.heartbeat_s = heartbeat_s
        self.rpc_stats = RpcStats()
        kw = {}
        if max_frame_bytes:
            kw["max_frame_bytes"] = max_frame_bytes
        self.server = RpcServer(socket_path, self._handle,
                                stats=self.rpc_stats,
                                span_hook=self._rpc_span, **kw)
        self._lock = threading.Lock()
        self._entries: Dict[int, _WEntry] = {}         # guarded-by: _lock
        self._events: "_stdqueue.Queue[Optional[dict]]" = _stdqueue.Queue()
        self._stop = threading.Event()
        self._hb_thread: Optional[threading.Thread] = None

    def start(self) -> None:
        self.server.start()
        self._hb_thread = threading.Thread(target=self._heartbeat_loop,
                                           name="worker-heartbeat",
                                           daemon=True)
        self._hb_thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._events.put(None)
        self.server.stop()

    # -- event channel -----------------------------------------------------

    def _push(self, ev: dict) -> None:
        self._events.put(ev)

    def _heartbeat_loop(self) -> None:
        while not self._stop.wait(self.heartbeat_s):
            # paired (wall, mono) stamps: the supervisor's between-RPC
            # clock-offset signal, and the honest base for heartbeat-age
            # math (receipt time includes pipe latency; this doesn't)
            self._push({"ev": "heartbeat", "pid": os.getpid(),
                        "wall": time.time(), "mono": time.monotonic(),
                        "incarnation": self.incarnation,
                        "snapshot": self.engine.service_snapshot()})

    # -- observability -----------------------------------------------------

    def _rpc_span(self, method: str, trace: dict, t0_wall: float,
                  dur_s: float, ok: bool) -> None:
        """RpcServer span hook: one ``rpc`` span row per traced frame —
        the server-handle half of the hop (the client logs its
        send→reply wait as an ``rpc:<method>`` child on the request
        tree; the gap between the two IS the transport)."""
        get_metrics().log_span(
            "rpc", t0_wall, dur_s, cat="rpc", method=method,
            request_id=trace.get("request_id"), replica=self.replica,
            pid=os.getpid(), incarnation=self.incarnation, ok=ok)

    def _event_sender(self, sock) -> None:
        """Drains the event queue onto the subscribed connection. Peer
        gone = the supervisor died; the worker keeps serving (SIGTERM or
        a new supervisor will claim it)."""
        while not self._stop.is_set():
            ev = self._events.get()
            if ev is None:
                return
            try:
                send_frame(sock, ev)
            except TransportError:
                logger.warning("Event peer gone; event channel closed.")
                return

    # -- request watchers --------------------------------------------------

    def _watch(self, entry: _WEntry) -> None:
        """Per-request lifecycle reporter: polls admission (cheap attr
        read), then blocks on the done event and pushes the terminal
        frame — authoritative token ids + text, so streamed pieces are
        pure latency optimization."""
        req = entry.req
        admitted_sent = False
        while not req._done.wait(0.01):
            if not admitted_sent and req.t_admit is not None:
                self._push({"ev": "admitted", "client_id": entry.client_id})
                admitted_sent = True
        with self._lock:
            self._entries.pop(entry.client_id, None)
            if entry.stolen:
                return              # handle now lives on another worker
        self._emit_worker_span(entry)
        if req.error is None and req.finish_reason is not None \
                and req.finish_reason not in ("error",):
            self._push({"ev": "done", "client_id": entry.client_id,
                        "token_ids": [int(t) for t in req.output_ids],
                        "text": req.text,
                        "finish_reason": req.finish_reason,
                        "n_prompt_tokens": int(len(req.prompt_ids)),
                        "queue_wait_s": req.queue_wait_s(),
                        "ttft_s": req.ttft_s(), "tpot_s": req.tpot_s()})
        else:
            self._push({"ev": "failed", "client_id": entry.client_id,
                        "reason": req.finish_reason or "error",
                        "error": req.error or "engine failure"})

    def _on_piece(self, client_id: int, req: Request, tok: int,
                  piece: str) -> None:
        self._push({"ev": "piece", "client_id": client_id,
                    "token": int(tok), "piece": piece})

    def _emit_worker_span(self, entry: _WEntry) -> None:
        """The worker-process half of the request's span tree: the same
        queued/prefill/decode shape as the engine's ``request`` root,
        renamed ``worker_request``, keyed by the SUPERVISOR's request id
        (the cross-process identity) and stamped with pid/incarnation —
        the merged timeline joins it to the fleet's ``request`` root on
        ``request_id``. Telemetry only: failures are swallowed."""
        try:
            row = entry.req.trace_row()
            row["name"] = "worker_request"
            row["local_request_id"] = row.get("request_id")
            row["request_id"] = entry.client_id
            row["replica"] = self.replica
            row["pid"] = os.getpid()
            row["incarnation"] = self.incarnation
            get_metrics().log_span(**row)
        except Exception:
            logger.exception("worker_request span emit failed (ignored)")

    # -- control methods ---------------------------------------------------

    def _handle(self, method: str, args: dict, sock):
        if method == "subscribe":
            t = threading.Thread(target=self._event_sender, args=(sock,),
                                 name="worker-events", daemon=True)
            t.start()
            return (DETACH, {"ok": True, "pid": os.getpid()})
        if method == "ping":
            return {"ok": True, "pid": os.getpid()}
        if method in ("submit", "adopt"):
            return self._rpc_submit(args, adopt=(method == "adopt"))
        if method == "cancel":
            return self._rpc_cancel(args)
        if method == "steal_queue":
            return self._rpc_steal_queue()
        if method == "drain":
            return self.engine.drain(
                timeout=float(args.get("timeout", 30.0)))
        if method == "healthz":
            out = dict(self.engine.healthz_payload())
            out["pid"] = os.getpid()
            return out
        if method == "snapshot":
            return self.engine.service_snapshot()
        if method == "stats":
            return _jsonable(self.engine.stats())
        if method == "metrics":
            counters, gauges, hists = self.engine.metrics_snapshot()
            out = {"counters": dict(counters), "gauges": dict(gauges),
                   "hists": {k: h.snapshot() for k, h in hists.items()}}
            # server-side transport telemetry rides the same scrape: the
            # fleet re-labels every series with worker/incarnation
            for m, e in self.rpc_stats.snapshot().items():
                lab = f'{{method="{m}"}}'
                out["counters"][f"rpc_server_calls{lab}"] = e["calls"]
                out["counters"][f"rpc_server_errors{lab}"] = e["errors"]
                out["counters"][
                    f"rpc_server_frame_bytes_received{lab}"] = \
                    e["bytes_received"]
                out["counters"][f"rpc_server_frame_bytes_sent{lab}"] = \
                    e["bytes_sent"]
                out["hists"][f"rpc_server_handle_seconds{lab}"] = \
                    e["latency"]
            return out
        if method == "export_panes":
            return self._rpc_export_panes()
        if method == "import_panes":
            return self._rpc_import_panes(args)
        raise ValueError(f"unknown method '{method}'")

    def _rpc_submit(self, args: dict, adopt: bool) -> dict:
        client_id = int(args["client_id"])
        prompt_ids = np.asarray(args["prompt_ids"], np.int32)
        params = SamplingParams(**(args.get("params") or {}))
        on_token = (lambda req, tok, piece, cid=client_id:
                    self._on_piece(cid, req, tok, piece))
        if adopt:
            # re-dispatched work was admitted fleet-wide already: skip
            # submit-time shedding, mirror EngineRouter._redispatch
            req = Request(next_request_id(), prompt_ids, params, on_token)
            req.route = args.get("route")
            self.engine.adopt(req, timeout=float(args.get("timeout", 5.0)))
        else:
            req = self.engine.submit(prompt_ids, params, block=False,
                                     on_token=on_token,
                                     route=args.get("route"))
        entry = _WEntry(client_id, req)
        with self._lock:
            self._entries[client_id] = entry
        threading.Thread(target=self._watch, args=(entry,),
                         name=f"watch-{client_id}", daemon=True).start()
        return {"request_id": req.id}

    def _rpc_cancel(self, args: dict) -> dict:
        with self._lock:
            entry = self._entries.get(int(args["client_id"]))
        if entry is None:
            return {"cancelled": False}
        return {"cancelled": bool(self.engine.cancel(entry.req))}

    def _rpc_steal_queue(self) -> dict:
        """Pop every still-QUEUED request (the supervisor re-dispatches
        them under the same client ids — ``drain_replica`` semantics
        across the process boundary)."""
        stolen: List[int] = []
        while True:
            req = self.engine.queue.get_nowait()
            if req is None:
                break
            with self._lock:
                entry = next((e for e in self._entries.values()
                              if e.req is req), None)
                if entry is not None:
                    entry.stolen = True
                    stolen.append(entry.client_id)
            # unblock the watcher; `stolen` suppresses its terminal frame
            req._mark_done()
        return {"client_ids": stolen}

    def _rpc_export_panes(self) -> dict:
        store = getattr(self.engine, "prefix_store", None)
        if store is None:
            return {"entries": []}
        entries = [{"key": k, "span": span, "panes": encode_panes(panes),
                    "nbytes": cache_nbytes(panes)}
                   for k, span, panes in store.export_entries()]
        return {"entries": entries}

    def _rpc_import_panes(self, args: dict) -> dict:
        store = getattr(self.engine, "prefix_store", None)
        if store is None:
            return {"imported": 0, "bytes": 0}
        imported = total = 0
        for ent in args.get("entries", []):
            n = store.import_entry(ent["key"],
                                   decode_panes(ent["panes"]),
                                   int(ent["span"]))
            if n > 0:
                imported += 1
                total += n
        return {"imported": imported, "bytes": total}


def _jsonable(obj: Any) -> Any:
    """Best-effort JSON coercion for stats payloads (numpy scalars)."""
    if isinstance(obj, dict):
        return {k: _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if isinstance(obj, (np.integer,)):
        return int(obj)
    if isinstance(obj, (np.floating,)):
        return float(obj)
    return obj


# ---------------------------------------------------------------------------
# subprocess entrypoint
# ---------------------------------------------------------------------------

def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="fleet worker: one replica engine behind a unix-socket "
                    "RPC boundary")
    ap.add_argument("--socket", required=True)
    ap.add_argument("--spec", required=True,
                    help="EngineSpec JSON (inline or @/path/to/file)")
    ap.add_argument("--replica", type=int, default=0)
    ap.add_argument("--incarnation", type=int, default=0,
                    help="restart generation of this worker process "
                         "(the supervisor's restart count); stamps "
                         "telemetry + seeds a disjoint request-id range")
    ap.add_argument("--metrics_jsonl", default=None)
    ap.add_argument("--heartbeat_s", type=float, default=0.5)
    ap.add_argument("--drain_timeout", type=float, default=30.0)
    args = ap.parse_args(argv)

    spec_json = args.spec
    if spec_json.startswith("@"):
        with open(spec_json[1:]) as f:
            spec_json = f.read()
    spec = EngineSpec.from_json(spec_json)

    if spec.fake is None:
        apply_host_env(spec.devices)
    if args.metrics_jsonl:
        # append mode: a restarted incarnation stacks its rows (own
        # header first) onto the same per-replica file, so the victim's
        # last rows and its successor's live in one artifact
        configure_metrics(args.metrics_jsonl,
                          run_metadata={"role": "fleet_worker",
                                        "replica": args.replica,
                                        "incarnation": args.incarnation,
                                        "pid": os.getpid()},
                          append=True)
    # worker-LOCAL request ids must never collide with the supervisor's
    # fleet-wide ids (or another worker's) in merged telemetry: seed a
    # disjoint per-(replica, incarnation) range
    seed_request_ids((args.replica * 1000 + args.incarnation + 1)
                     * 1_000_000)

    from building_llm_from_scratch_tpu.obs import configure_compile_cache

    configure_compile_cache()
    engine = build_engine(spec, replica=args.replica)
    engine.warmup()
    engine.start()

    server = WorkerServer(engine, args.socket, replica=args.replica,
                          heartbeat_s=args.heartbeat_s,
                          incarnation=args.incarnation)
    server.start()

    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    signal.signal(signal.SIGINT, lambda *_: stop.set())

    # exactly ONE stdout line, then the pipe stays open: the supervisor
    # parses this for readiness and reads EOF on it as process death
    print(json.dumps({"ready": True, "pid": os.getpid(),
                      "replica": args.replica, "socket": args.socket}),
          flush=True)
    logger.info("Worker %d serving on %s (pid %d).",
                args.replica, args.socket, os.getpid())

    stop.wait()
    logger.info("Worker %d: SIGTERM — draining (%.1fs budget).",
                args.replica, args.drain_timeout)
    try:
        engine.drain(timeout=args.drain_timeout)
    finally:
        engine.shutdown(drain=False)
        server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
