"""Fleet tier: N ``DecodeEngine`` replicas behind one dispatch surface.

The single engine tops out at one model replica on one device (or one
tensor-parallel device group). This router is the "millions of users"
layer above it: an in-process replica set with one
``submit()/result()/stream()`` surface and one HTTP frontend, where

  - each replica is a full ``DecodeEngine`` on its OWN ``MeshPlan``
    (``parallel/sharding.serve_mesh_plan``): ``tp=1`` pins a replica to
    its own device, ``tp>1`` runs it tensor-parallel over a disjoint
    device slice — replicas execute concurrently, so aggregate
    throughput scales with the replica count (``bench.py serve_fleet``);
  - dispatch is deadline-aware: each replica's live TPOT/queue-depth
    EWMAs (``DecodeEngine.service_snapshot``) feed the same completion
    estimate the single-engine SLO shed uses, generalized fleet-wide —
    a request is only refused when EVERY replica predicts a miss, and
    the 429 carries the best replica's Retry-After;
  - adapter-affinity: a tenant's traffic prefers replicas whose
    ``AdapterRegistry`` already holds its adapter row (residency is a
    lock-free ``lookup``), with load-spill past an overloaded resident
    and a routed HOT-LOAD on fleet-wide miss (the router knows the
    artifact paths);
  - prefix-affinity: requests sharing a prompt prefix hash to the same
    replica, so ``PrefixStore`` hits concentrate instead of every
    replica paying the same cold prefill;
  - drain/restart of ONE replica never drops a request: its queued work
    is re-dispatched onto live replicas (the SAME ``Request`` handles —
    clients never notice), in-flight work finishes within the drain
    timeout, and a ``restart_replica`` brings a fresh engine back into
    dispatch.

Telemetry: every engine event carries ``replica=<i>`` (the engines label
their own rows), the router adds ``replica_drain`` / ``replica_restart``
/ ``router_redispatch`` events plus fleet counters, and ``/metrics``
re-exports each replica's series with a ``{replica="i"}`` label next to
fleet-level gauges (replicas_up, fleet occupancy, affinity ratio). Each
routed request still closes exactly ONE span tree — the router hop rides
as a ``router`` child span on the request's root.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from building_llm_from_scratch_tpu.obs.metrics import (
    get_metrics,
    render_prometheus,
)
from building_llm_from_scratch_tpu.serving.engine import DecodeEngine
from building_llm_from_scratch_tpu.serving.queue import (
    EngineDrainingError,
    QueueFullError,
    SLOShedError,
)
from building_llm_from_scratch_tpu.serving.request import (
    Request,
    SamplingParams,
)
from building_llm_from_scratch_tpu.utils.logging import setup_logger

logger = setup_logger(__name__)

#: prompt-prefix window the prefix-affinity hash reads: long enough to
#: distinguish system prompts, short enough that requests sharing one
#: land on the same replica even when their suffixes diverge
PREFIX_AFFINITY_TOKENS = 64


def _labeled(key: str, replica: int) -> str:
    """Merge ``replica="i"`` into a metric key's (possibly existing)
    label set: ``adapter_tokens{adapter="x"}`` ->
    ``adapter_tokens{adapter="x",replica="i"}``."""
    base, sep, labels = key.partition("{")
    if not sep:
        return f'{base}{{replica="{replica}"}}'
    return f'{base}{{{labels[:-1]},replica="{replica}"}}'


class EngineRouter:
    """N ``DecodeEngine`` replicas behind one engine-shaped surface.

    Construct from live engines (tests) or via ``build()`` (the CLI
    path), then use it exactly like a ``DecodeEngine``: ``warmup()``,
    ``start()``, ``submit()`` (returns the replica's ``Request`` handle
    — ``result()``/``stream()`` ride it unchanged), ``drain()``,
    ``shutdown()``. The HTTP frontend binds either without caring.
    """

    def __init__(self, engines: Sequence[DecodeEngine], *,
                 adapter_paths: Optional[Dict[str, str]] = None,
                 factory: Optional[Callable[[int], DecodeEngine]] = None,
                 prefix_affinity: bool = True):
        if not engines:
            raise ValueError("EngineRouter needs at least one engine")
        self.engines: List[DecodeEngine] = list(engines)
        for i, eng in enumerate(self.engines):
            if eng.replica is None:
                eng.replica = i
        #: adapter name -> artifact path, for routed hot-load on a
        #: fleet-wide residency miss (and for drain re-dispatch of
        #: tenant traffic onto a replica that never saw the tenant)
        self._adapter_paths = dict(adapter_paths or {})
        self._factory = factory
        self.prefix_affinity = bool(prefix_affinity)
        self._lock = threading.Lock()
        #: replicas the router stopped dispatching to (drain/restart)
        self._out: set = set()              # guarded-by: _lock [writes]
        self.routed_total = 0               # guarded-by: _lock
        self.routed_affinity = 0            # guarded-by: _lock
        self.routed_spill = 0               # guarded-by: _lock
        self.hot_loads = 0                  # guarded-by: _lock
        self.redispatched = 0               # guarded-by: _lock
        self._t_start = time.monotonic()

    # -- construction ------------------------------------------------------

    @classmethod
    def build(cls, cfg, params, tokenizer=None, *, n_replicas: int,
              tp: int = 1, sp: int = 1, devices=None,
              adapter_specs: Optional[Dict[str, str]] = None,
              adapter_capacity: int = 0,
              kv_policy=None, watch_compiles: str = "all",
              prefix_affinity: bool = True,
              **engine_kwargs) -> "EngineRouter":
        """Build ``n_replicas`` engines over partitioned devices.

        Each replica gets its own ``serve_mesh_plan`` (``tp`` devices,
        disjoint slices when the pool is big enough — see
        ``parallel.partition_serve_devices``) and its OWN
        ``AdapterRegistry``. Adapters are placed round-robin across
        replicas (affinity routing makes the placement sticky; misses
        hot-load), every registry sized to hold the full set so a drain
        can consolidate tenants onto the survivors.

        ``watch_compiles``: "all" (default) wraps every replica's
        programs in CompileWatchers; "first" watches only replica 0 —
        the perf-gate mode, whose fingerprint is then replica-count
        invariant by construction; "none" disables watching.
        """
        from building_llm_from_scratch_tpu.parallel.sharding import (
            partition_serve_devices,
            serve_mesh_plan,
        )
        from building_llm_from_scratch_tpu.serving.adapters import (
            AdapterRegistry,
        )

        if n_replicas < 1:
            raise ValueError("n_replicas must be >= 1")
        if watch_compiles not in ("all", "first", "none"):
            raise ValueError("watch_compiles must be all|first|none")
        t0 = time.monotonic()
        dev_slices = partition_serve_devices(n_replicas, tp, sp,
                                             devices=devices)
        specs = dict(adapter_specs or {})
        names = sorted(specs)
        if not adapter_capacity:
            adapter_capacity = max(2, len(names) + 1)

        def make_engine(i: int) -> DecodeEngine:
            plan = serve_mesh_plan(tp, sp, devices=dev_slices[i])
            registry = None
            if adapter_specs is not None:
                # an EMPTY spec dict still builds (empty) registries:
                # the router can then hot-load artifacts it learns about
                # (adapter_paths) onto any replica
                mine = {nm: specs[nm] for k, nm in enumerate(names)
                        if k % n_replicas == i}
                registry = AdapterRegistry.from_artifacts(
                    cfg, params, mine, capacity=adapter_capacity) \
                    if mine else AdapterRegistry(
                        cfg, params, capacity=adapter_capacity)
            watch = (watch_compiles == "all"
                     or (watch_compiles == "first" and i == 0))
            return DecodeEngine(cfg, params, tokenizer,
                                mesh_plan=plan, replica=i,
                                adapters=registry, kv_policy=kv_policy,
                                watch_compiles=watch, **engine_kwargs)

        engines = [make_engine(i) for i in range(n_replicas)]
        router = cls(engines, adapter_paths=specs, factory=make_engine,
                     prefix_affinity=prefix_affinity)
        disjoint = (len({d for sl in dev_slices for d in sl})
                    == n_replicas * tp * sp)
        get_metrics().event(
            "serve_fleet", phase="build", n_replicas=n_replicas, tp=tp,
            sp=sp, disjoint_devices=disjoint, n_adapters=len(names),
            seconds=round(time.monotonic() - t0, 3))
        logger.info(
            "Fleet: %d replica(s) x tp=%d x sp=%d (%s device slices), %d "
            "adapter(s) round-robin.", n_replicas, tp, sp,
            "disjoint" if disjoint else "OVERLAPPING", len(names))
        return router

    # -- engine-shaped lifecycle ------------------------------------------

    def warmup(self) -> None:
        """Warm every replica CONCURRENTLY (each compiles its own program
        family; XLA compiles release the GIL, so a fleet warms in roughly
        one replica's wall time). Worker exceptions re-raise here."""
        errs: List[BaseException] = []

        def warm(eng):
            try:
                eng.warmup()
            except BaseException as e:          # noqa: BLE001 — re-raised
                errs.append(e)

        threads = [threading.Thread(target=warm, args=(eng,),
                                    name=f"warmup-r{i}", daemon=True)
                   for i, eng in enumerate(self.engines)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errs:
            raise errs[0]

    def start(self) -> None:
        for eng in self.engines:
            eng.start()

    def shutdown(self, drain: bool = True) -> None:
        for eng in self.engines:
            eng.shutdown(drain=drain)
        get_metrics().event("serve_fleet", phase="end",
                            n_replicas=len(self.engines),
                            seconds=round(time.monotonic()
                                          - self._t_start, 3))

    def run_until_idle(self) -> None:
        """Manual mode (tests): tick every replica until the whole fleet
        is idle."""
        while any(eng.step() for eng in self.engines):
            pass

    # -- dispatch ----------------------------------------------------------

    def _live(self) -> List[int]:
        with self._lock:
            out = set(self._out)
        return [i for i, eng in enumerate(self.engines)
                if i not in out and eng._dead is None
                and not eng.draining]

    @staticmethod
    def _estimate(snap: dict, max_new: int) -> Optional[float]:
        """The single-engine SLO completion estimate, computed from a
        replica's snapshot — THE shared ``engine.service_estimate``
        formula, so fleet admission and per-engine shed agree on what
        "predicted miss" means."""
        from building_llm_from_scratch_tpu.serving.engine import (
            service_estimate,
        )

        return service_estimate(snap["queue_depth"], snap["n_active"],
                                snap["n_slots"], snap["tpot_ewma"],
                                snap["tokens_ewma"], max_new)

    def _prefix_hash_pick(self, prompt, candidates: List[int]
                          ) -> Optional[int]:
        """Stable prompt-prefix -> replica mapping among the candidates
        whose prefix cache is on: shared-system-prompt traffic lands on
        one replica, so its ``PrefixStore`` actually accumulates hits.
        The hashed window is CHUNK-aligned (the tail partial chunk is
        dropped, mirroring ``PrefixStore.storable_span``): requests
        sharing a system prompt but differing in their last few suffix
        tokens still hash together."""
        capable = [i for i in candidates
                   if self.engines[i].prefix_store is not None]
        if not capable:
            return None
        try:
            import numpy as np

            chunk = max(
                self.engines[capable[0]].kv_policy.prefill_chunk, 1)
            if isinstance(prompt, str):
                ids = np.frombuffer(
                    prompt.encode()[: PREFIX_AFFINITY_TOKENS * 4],
                    dtype=np.uint8)
            else:
                ids = np.asarray(prompt).reshape(-1)
            span = min((ids.size // chunk) * chunk,
                       PREFIX_AFFINITY_TOKENS)
            if span <= 0:
                return None
            key = ids[:span].tobytes()
        except Exception:       # noqa: BLE001 — affinity is best-effort
            return None
        import zlib

        return capable[zlib.crc32(key) % len(capable)]

    def _route_order(self, prompt, params: SamplingParams
                     ) -> List[Tuple[int, Optional[str]]]:
        """The dispatch plan: (replica, affinity-label) candidates in
        preference order. Affinity targets (adapter residency, prefix
        hash) come first sorted by predicted completion; deadline-aware
        spill moves candidates predicted to MISS the request's deadline
        behind every candidate predicted to make it."""
        live = self._live()
        if not live:
            return []
        snaps = {i: self.engines[i].service_snapshot() for i in live}
        est = {i: self._estimate(snaps[i], params.max_new_tokens)
               for i in live}
        aff: List[int] = []
        label: Optional[str] = None

        def sort_key(i):
            return (est[i] if est[i] is not None else 0.0,
                    snaps[i]["queue_depth"], i)

        if params.adapter is not None:
            # adapter traffic can ONLY go where the adapter is resident
            # (a non-resident replica would 400 it): candidates are the
            # residents, spill is a routed hot-load (here on full miss;
            # in submit() when every resident refuses)
            aff = [i for i in live
                   if self.engines[i].adapters is not None
                   and self.engines[i].adapters.lookup(params.adapter)
                   is not None]
            label = "adapter"
            if not aff:
                target = self._hot_load(params.adapter, live, est)
                if target is not None:
                    aff = [target]
            order = [(i, label) for i in sorted(aff, key=sort_key)]
            if params.deadline_s is not None:
                ok = [c for c in order if est[c[0]] is None
                      or est[c[0]] <= params.deadline_s]
                order = ok + [c for c in order if c not in ok]
            return order
        if self.prefix_affinity:
            target = self._prefix_hash_pick(prompt, live)
            if target is not None:
                aff = [target]
                label = "prefix"
        rest = sorted((i for i in live if i not in aff), key=sort_key)
        order = [(i, label) for i in sorted(aff, key=sort_key)]
        order += [(i, None) for i in rest]
        if params.deadline_s is not None:
            # load-spill: an affinity target predicted to blow the
            # deadline yields to ANY replica predicted to make it (the
            # per-engine shed would 429 there; a colder replica serves)
            ok = [c for c in order if est[c[0]] is None
                  or est[c[0]] <= params.deadline_s]
            miss = [c for c in order if c not in ok]
            order = ok + miss
        return order

    def _hot_load(self, adapter: str, live: List[int],
                  est: Dict[int, Optional[float]]) -> Optional[int]:
        """Fleet-wide residency miss: load the tenant's artifact into
        the least-loaded live replica's registry. Returns the replica,
        or None when the router has no path / no registry / the load
        fails (the chosen engine's own submit then rejects the unknown
        adapter exactly as a single engine would)."""
        path = self._adapter_paths.get(adapter)
        if path is None:
            return None
        for i in sorted(live, key=lambda j: (est[j] or 0.0, j)):
            reg = self.engines[i].adapters
            if reg is None:
                continue
            try:
                reg.load(adapter, path)
            except Exception as e:  # noqa: BLE001 — registry full, race
                logger.warning("Hot-load of '%s' on replica %d failed: "
                               "%s", adapter, i, e)
                continue
            with self._lock:
                self.hot_loads += 1
            logger.info("Adapter '%s' hot-loaded onto replica %d "
                        "(routed miss).", adapter, i)
            return i
        return None

    def submit(self, prompt, params: Optional[SamplingParams] = None,
               block: bool = False, timeout: Optional[float] = None,
               on_token=None) -> Request:
        """Dispatch one request to the best replica; returns that
        replica's ``Request`` handle (``result()``/``stream()`` ride it
        unchanged). Raises only when EVERY live replica refuses:
        ``SLOShedError``/``QueueFullError``/``EngineDrainingError`` with
        the best replica's Retry-After — fleet-wide admission."""
        params = params or SamplingParams()
        t0 = time.perf_counter()
        order = self._route_order(prompt, params)
        route_s = round(time.perf_counter() - t0, 6)
        if not order:
            if params.adapter is not None and self._live():
                # live replicas exist but none holds (or could load) the
                # adapter — the single-engine unknown-adapter 400
                raise ValueError(
                    f"adapter '{params.adapter}' is not loaded on any "
                    "replica (and no artifact path is known to the "
                    "router)")
            raise RuntimeError("no live replicas")
        last: Optional[BaseException] = None
        for rank, (i, affinity) in enumerate(order):
            eng = self.engines[i]
            route = {"replica": i, "affinity": affinity,
                     "route_s": route_s, "spill": rank > 0}
            try:
                req = eng.submit(prompt, params, block=False,
                                 timeout=timeout, on_token=on_token,
                                 route=route)
            except (EngineDrainingError, QueueFullError,
                    SLOShedError) as e:
                # keep the FIRST refusal: candidates are best-first, so
                # its Retry-After is the soonest the fleet has room —
                # raising a worse replica's would over-back-off clients
                last = last or e
                continue
            except RuntimeError as e:           # replica died under us
                last = last or e
                continue
            self._count_route(affinity, rank)
            return req
        if params.adapter is not None:
            # load-spill for tenant traffic: every RESIDENT refused
            # (full/draining/shed) — hot-load the artifact onto a live
            # non-resident and serve there instead of bouncing
            tried = {i for i, _ in order}
            spill_live = [i for i in self._live() if i not in tried]
            if spill_live:
                est = {i: self._estimate(
                    self.engines[i].service_snapshot(),
                    params.max_new_tokens) for i in spill_live}
                target = self._hot_load(params.adapter, spill_live, est)
                if target is not None:
                    try:
                        req = self.engines[target].submit(
                            prompt, params, block=False, timeout=timeout,
                            on_token=on_token,
                            route={"replica": target,
                                   "affinity": "adapter",
                                   "route_s": route_s, "spill": True})
                        self._count_route("adapter", 1)
                        return req
                    except (EngineDrainingError, QueueFullError,
                            SLOShedError, RuntimeError) as e:
                        last = last or e
        if block and order:
            # every replica refused non-blocking; honor backpressure on
            # the best candidate instead of bouncing the caller
            i, affinity = order[0]
            req = self.engines[i].submit(
                prompt, params, block=True, timeout=timeout,
                on_token=on_token,
                route={"replica": i, "affinity": affinity,
                       "route_s": route_s, "spill": False})
            self._count_route(affinity, 0)
            return req
        assert last is not None
        raise last

    def _count_route(self, affinity: Optional[str], rank: int) -> None:
        with self._lock:
            self.routed_total += 1
            if affinity is not None and rank == 0:
                self.routed_affinity += 1
            if rank > 0:
                self.routed_spill += 1

    def cancel(self, req: Request) -> bool:
        """Client gave up: cancel on the owning replica (the route
        record tracks ownership across re-dispatch)."""
        i = (req.route or {}).get("replica")
        if i is not None and 0 <= i < len(self.engines):
            return self.engines[i].cancel(req)
        for eng in self.engines:            # ownership unknown: flag all
            if req.done:
                return False
            eng.cancel(req)
        return not req.done

    # -- drain / restart ---------------------------------------------------

    def drain_replica(self, i: int, timeout: float = 30.0,
                      redispatch: bool = True) -> dict:
        """Drain ONE replica without dropping fleet work: it leaves
        dispatch, its QUEUED requests move to live replicas (same
        ``Request`` handles — ``router_redispatch`` events record each
        hop), and its in-flight requests finish within ``timeout``."""
        eng = self.engines[i]
        with self._lock:
            self._out.add(i)
        snap = eng.service_snapshot()
        get_metrics().event("replica_drain", replica=i, phase="start",
                            timeout_s=timeout,
                            n_active=snap["n_active"],
                            queue_depth=snap["queue_depth"])
        moved = 0
        if redispatch:
            while True:
                req = eng.queue.get_nowait()
                if req is None:
                    break
                if self._redispatch(req, i):
                    moved += 1
                else:
                    # no live target took it: hand it back so the
                    # drain below finishes it (or preempts it loudly)
                    # rather than leaving a stolen handle unfinished
                    self._return_to_queue(eng, req)
                    break
        summary = eng.drain(timeout=timeout)
        get_metrics().event("replica_drain", replica=i, phase="end",
                            n_redispatched=moved,
                            n_preempted=summary.get("n_preempted"),
                            seconds=summary.get("seconds"))
        logger.warning("Replica %d drained: %d queued re-dispatched, "
                       "%s preempted.", i, moved,
                       summary.get("n_preempted"))
        return summary

    @staticmethod
    def _return_to_queue(eng: DecodeEngine, req: Request) -> None:
        """Hand a stolen-but-unplaceable request back to its source
        replica. The source may have refilled meanwhile (get_nowait
        woke a blocked submitter), so wait briefly for space; if it
        stays full, fail the request LOUDLY instead of letting it
        propagate out of the drain with the handle enqueued nowhere
        (a client blocked in result() forever)."""
        from building_llm_from_scratch_tpu.serving.request import (
            FINISH_PREEMPTED,
        )

        try:
            eng.queue.put(req, block=True, timeout=5.0)
            return
        except QueueFullError:
            pass
        # mirrors DecodeEngine.cancel's timed-acquire discipline: the
        # fail path mutates engine counters under the engine lock, but a
        # wedged tick must not hang the drain — we own the request (it
        # is in no queue), so the lock-free fallback cannot race a commit
        lock = eng._lock
        locked = lock.acquire(timeout=2.0)
        try:
            eng._fail_request(
                None, req,
                "drain re-dispatch found no live target and the source "
                "queue refilled", reason="preempted",
                finish=FINISH_PREEMPTED)
        finally:
            if locked:
                lock.release()

    def _redispatch(self, req: Request, from_i: int) -> bool:
        """Move one stolen QUEUED request onto a live replica. Prefers
        adapter residents; hot-loads the tenant's artifact when no
        resident survives; falls through targets on backpressure."""
        live = self._live()
        if not live:
            return False
        snaps = {j: self.engines[j].service_snapshot() for j in live}
        est = {j: self._estimate(snaps[j], req.params.max_new_tokens)
               for j in live}
        order = sorted(live, key=lambda j: (est[j] or 0.0,
                                            snaps[j]["queue_depth"], j))
        if req.params.adapter is not None:
            # tenant work can ONLY move where its adapter is resident
            # (or hot-loadable): adopt() bypasses submit-time adapter
            # validation, so a non-resident target would fail the
            # request at admission — returning False instead hands it
            # back to the draining replica, where the adapter IS
            # resident and the drain finishes it
            res = [j for j in order
                   if self.engines[j].adapters is not None
                   and self.engines[j].adapters.lookup(req.params.adapter)
                   is not None]
            if not res:
                target = self._hot_load(req.params.adapter, live, est)
                res = [target] if target is not None else []
            order = res
        for j in order:
            try:
                self.engines[j].adopt(req)
            except (EngineDrainingError, QueueFullError, RuntimeError):
                continue
            req.route = {**(req.route or {}), "replica": j,
                         "redispatched_from": from_i}
            with self._lock:
                self.redispatched += 1
            get_metrics().event("router_redispatch", request_id=req.id,
                                from_replica=from_i, to_replica=j,
                                adapter=req.params.adapter)
            return True
        return False

    def restart_replica(self, i: int) -> DecodeEngine:
        """Bring a drained (or dead) replica back: fresh engine from the
        build factory, warmed, started, re-entered into dispatch. The
        fresh engine compiles its own program family (a warmup, not a
        recompile — its watchers freeze after), then serves."""
        if self._factory is None:
            raise RuntimeError(
                "restart_replica needs a router built via "
                "EngineRouter.build (no engine factory)")
        t0 = time.monotonic()
        old = self.engines[i]
        old.shutdown(drain=False)
        eng = self._factory(i)
        eng.warmup()
        eng.start()
        self.engines[i] = eng
        with self._lock:
            self._out.discard(i)
        get_metrics().event("replica_restart", replica=i,
                            seconds=round(time.monotonic() - t0, 3))
        logger.warning("Replica %d restarted (%.1fs).", i,
                       time.monotonic() - t0)
        return eng

    def drain(self, timeout: float = 30.0) -> dict:
        """Fleet drain (the SIGTERM path): ROLLING — each replica's
        queued work re-dispatches onto the replicas still serving, the
        last one drains plain. ``timeout`` applies per replica."""
        live = [i for i in range(len(self.engines))
                if i not in self._out]
        out: dict = {"n_preempted": 0, "n_redispatched": 0}
        for k, i in enumerate(live):
            s = self.drain_replica(i, timeout=timeout,
                                   redispatch=(k < len(live) - 1))
            out["n_preempted"] += s.get("n_preempted", 0)
        with self._lock:
            out["n_redispatched"] = self.redispatched
        return out

    # -- engine-shaped introspection --------------------------------------

    @property
    def draining(self) -> bool:
        return all(eng.draining or i in self._out
                   for i, eng in enumerate(self.engines))

    @property
    def _dead(self) -> Optional[str]:
        msgs = [eng._dead for eng in self.engines]
        if all(m is not None for m in msgs):
            return f"all {len(msgs)} replicas dead: {msgs[0]}"
        return None

    @property
    def warmed_up(self) -> bool:
        return all(eng.warmed_up for eng in self.engines)

    @property
    def default_max_new_tokens(self) -> int:
        return self.engines[0].default_max_new_tokens

    @property
    def n_recompiles(self) -> int:
        return sum(eng.n_recompiles for eng in self.engines)

    @property
    def n_replicas(self) -> int:
        return len(self.engines)

    def queue_capacity(self) -> int:
        return sum(eng.queue.max_size for eng in self.engines)

    def estimate_queue_clear_s(self) -> Optional[float]:
        """Fleet Retry-After: the BEST live replica's backlog estimate
        (a retrying client should come back when somewhere has room)."""
        from building_llm_from_scratch_tpu.serving.engine import (
            queue_clear_estimate,
        )

        ests = []
        for i in self._live():
            snap = self.engines[i].service_snapshot()
            est = queue_clear_estimate(
                snap["queue_depth"], snap["n_active"], snap["n_slots"],
                snap["tpot_ewma"], snap["tokens_ewma"])
            if est is not None:
                ests.append(est)
        return round(min(ests), 3) if ests else None

    def stats(self) -> dict:
        with self._lock:
            out = {
                "n_replicas": len(self.engines),
                "routed_total": self.routed_total,
                "routed_affinity": self.routed_affinity,
                "routed_spill": self.routed_spill,
                "hot_loads": self.hot_loads,
                "redispatched": self.redispatched,
            }
            if self.routed_total:
                out["routed_by_affinity_ratio"] = round(
                    self.routed_affinity / self.routed_total, 6)
        out["replicas"] = [eng.stats() for eng in self.engines]
        for key in ("requests_finished", "requests_failed",
                    "requests_shed", "requests_expired",
                    "tokens_generated", "n_recompiles"):
            out[key] = sum(r.get(key, 0) for r in out["replicas"])
        return out

    def metrics_snapshot(self) -> tuple:
        """Fleet (counters, gauges, histograms): every replica's series
        re-keyed with a ``{replica="i"}`` label (merged into existing
        label sets), plus unlabeled fleet-level aggregates."""
        counters: dict = {}
        gauges: dict = {}
        hists: dict = {}
        up = 0
        occ = []
        qdepth = 0
        for i, eng in enumerate(self.engines):
            c, g, h = eng.metrics_snapshot()
            for k, v in c.items():
                counters[_labeled(k, i)] = v
            for k, v in g.items():
                gauges[_labeled(k, i)] = v
            for k, v in h.items():
                hists[_labeled(k, i)] = v
            if eng._dead is None:
                up += 1
            occ.append(g.get("slot_occupancy", 0.0))
            qdepth += g.get("queue_depth", 0)
        with self._lock:
            counters["routed_requests"] = self.routed_total
            counters["routed_affinity"] = self.routed_affinity
            counters["routed_spill"] = self.routed_spill
            counters["adapter_hot_loads"] = self.hot_loads
            counters["redispatched_requests"] = self.redispatched
            ratio = (self.routed_affinity / self.routed_total
                     if self.routed_total else 0.0)
        gauges["replicas_up"] = up
        gauges["replicas_total"] = len(self.engines)
        gauges["fleet_occupancy"] = round(sum(occ) / max(len(occ), 1), 6)
        gauges["fleet_queue_depth"] = qdepth
        gauges["routed_by_affinity_ratio"] = round(ratio, 6)
        return counters, gauges, hists

    def prometheus_text(self) -> str:
        counters, gauges, hists = self.metrics_snapshot()
        return render_prometheus(counters, gauges, hists,
                                 prefix="bllm_serve_")

    def healthz_payload(self) -> dict:
        replicas = []
        for i, eng in enumerate(self.engines):
            p = eng.healthz_payload()
            replicas.append({
                "replica": i,
                "status": ("out" if i in self._out and p["status"] ==
                           "serving" else p["status"]),
                "active": p["active"],
                "queue_depth": p["queue_depth"],
                "occupancy": p["occupancy"],
                "restarts": p["restarts"],
                "slo_miss_ratio": p["slo_miss_ratio"],
                "kv_append": p["kv_append"],
                "decode_attention": p["decode_attention"],
                "chunk_attention": p["chunk_attention"],
            })
        up = [r for r in replicas if r["status"] == "serving"]
        if self._dead is not None:
            status = "dead"
        elif self.draining:
            status = "draining"
        elif not up:
            status = "degraded"
        else:
            status = "serving"
        with self._lock:
            routing = {
                "routed_total": self.routed_total,
                "routed_affinity": self.routed_affinity,
                "routed_spill": self.routed_spill,
                "redispatched": self.redispatched,
            }
        return {
            "status": status,
            "replicas_up": len(up),
            "replicas_total": len(self.engines),
            "queue_depth": sum(r["queue_depth"] for r in replicas),
            "queue_capacity": self.queue_capacity(),
            "warmed_up": self.warmed_up,
            "draining": self.draining,
            "routing": routing,
            "replicas": replicas,
        }
