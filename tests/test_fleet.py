"""Scale-out serving tests: the tp-sharded engine (NamedSharding'd
weights + heads-sharded slot KV over the ``model`` mesh axis, tokens
bit-identical to the unsharded engine on the forced-host 8-device CPU
backend, zero recompiles) and the fleet router (adapter-affinity +
prefix-affinity dispatch, deadline-aware spill, drain-one-replica with
queued-work re-dispatch and zero request loss, per-replica labeled
``/metrics``, one closed span tree per routed request with the router
hop as a child span, replica-count-invariant gate fingerprint)."""

import json
import os
import tempfile

import jax
import numpy as np
import pytest

from building_llm_from_scratch_tpu.configs import ModelConfig
from building_llm_from_scratch_tpu.models import init_params
from building_llm_from_scratch_tpu.obs import configure_metrics
from building_llm_from_scratch_tpu.parallel.sharding import (
    partition_serve_devices,
    serve_mesh_plan,
)
from building_llm_from_scratch_tpu.serving import (
    DecodeEngine,
    EngineRouter,
    SamplingParams,
)


def tiny_cfg(ctx=64, **kw):
    base = dict(name="fleet-tiny", vocab_size=96, context_length=ctx,
                emb_dim=32, n_heads=2, n_layers=2, hidden_dim=64,
                n_kv_groups=2, norm="layernorm", positional="learned",
                activation="gelu", drop_rate=0.0, eos_id=1)
    base.update(kw)
    return ModelConfig(**base)


@pytest.fixture(scope="module")
def model():
    cfg = tiny_cfg()
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture
def sink(tmp_path):
    path = tmp_path / "metrics.jsonl"
    logger = configure_metrics(str(path), run_metadata={"test": True})
    yield str(path)
    logger.close()
    configure_metrics(None)


def load_rows(path):
    return [json.loads(line) for line in open(path)]


def mixed_requests(n, seed=0, max_new=6):
    """Greedy + seeded-sampling mix, varied prompts — the parity diet."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        prompt = rng.integers(2, 96, (4 + i % 3,)).astype(np.int32)
        sp = SamplingParams(max_new_tokens=max_new, ignore_eos=True,
                            seed=i, temperature=0.0 if i % 2 else 0.9,
                            top_k=None if i % 2 else 8)
        out.append((prompt, sp))
    return out


def run_engine(engine, reqs):
    handles = [engine.submit(p, sp, block=True) for p, sp in reqs]
    engine.run_until_idle()
    toks = [list(h.result(timeout=120).output_ids) for h in handles]
    engine.shutdown()
    return toks


# ---------------------------------------------------------------------------
# parallel layer units
# ---------------------------------------------------------------------------

def test_cache_spec_rules():
    plan = serve_mesh_plan(tp=2)
    # k/v panes (S, Hkv, T, hd): heads axis on `model` when divisible
    assert tuple(plan.cache_spec((4, 2, 32, 16))) == (None, "model",
                                                      None, None)
    # int8 scale sidecars (S, Hkv, T, 1): same rule (heads axis)
    assert tuple(plan.cache_spec((4, 2, 32, 1))) == (None, "model",
                                                     None, None)
    # indivisible heads replicate; non-4d leaves replicate
    assert tuple(plan.cache_spec((4, 3, 32, 16))) == ()
    assert tuple(plan.cache_spec((4, 32))) == ()
    # tp=1 plans never shard the cache
    assert tuple(serve_mesh_plan(tp=1).cache_spec((4, 2, 32, 16))) == ()


def test_partition_serve_devices():
    devs = jax.devices()
    assert len(devs) == 8        # conftest forces the 8-device backend
    slices = partition_serve_devices(4, tp=2)
    assert [len(s) for s in slices] == [2, 2, 2, 2]
    assert len({d for s in slices for d in s}) == 8     # disjoint
    # oversubscribed: overlapping slices, still tp devices each
    slices = partition_serve_devices(8, tp=2)
    assert all(len(s) == 2 for s in slices)
    with pytest.raises(ValueError):
        partition_serve_devices(1, tp=16)


# ---------------------------------------------------------------------------
# tp-sharded engine
# ---------------------------------------------------------------------------

def test_tp_engine_tokens_bit_identical_zero_recompiles(model):
    """The tentpole invariant: a tp=2-sharded engine (Megatron param
    rules + heads-sharded slot KV over the forced-host 8-device mesh)
    commits the BIT-identical token stream of the unsharded engine over
    mixed greedy+sampled traffic, with zero recompiles under the frozen
    watchers."""
    cfg, params = model
    reqs = mixed_requests(6)
    ref = run_engine(DecodeEngine(cfg, params, n_slots=4, max_len=32,
                                  warmup_prompt_cap=16), reqs)
    plan = serve_mesh_plan(tp=2)
    eng = DecodeEngine(cfg, params, n_slots=4, max_len=32,
                       warmup_prompt_cap=16, mesh_plan=plan)
    eng.warmup()                 # compiles + freezes the watchers
    # the cache really is sharded on the heads axis of the model mesh
    k0 = eng.cache["k"][0]
    assert k0.sharding.spec == plan.cache_spec(tuple(k0.shape))
    tp_toks = run_engine(eng, reqs)
    assert tp_toks == ref
    assert eng.n_recompiles == 0


def test_tp_engine_with_adapters_parity(model):
    """tp x multi-tenant LoRA: the stacked adapter pool is re-placed on
    the replica mesh (replicated), and adapter/base mixed traffic is
    bit-identical to the unsharded registry engine."""
    from building_llm_from_scratch_tpu.models.lora import (
        init_lora_params,
        save_adapter,
    )
    from building_llm_from_scratch_tpu.serving import AdapterRegistry

    cfg, params = model
    d = tempfile.mkdtemp()
    path = os.path.join(d, "a.npz")
    lora = init_lora_params(cfg, params, jax.random.PRNGKey(3), rank=4)
    save_adapter(path, lora, rank=4, alpha=8.0, cfg=cfg)

    def reqs():
        out = []
        for i in range(4):
            sp = SamplingParams(max_new_tokens=5, ignore_eos=True,
                                seed=i, adapter="a" if i % 2 else None)
            out.append((np.arange(3 + i, dtype=np.int32) + 2, sp))
        return out

    ref_reg = AdapterRegistry.from_artifacts(cfg, params, {"a": path})
    ref = run_engine(DecodeEngine(cfg, params, n_slots=2, max_len=32,
                                  warmup_prompt_cap=16,
                                  adapters=ref_reg), reqs())
    tp_reg = AdapterRegistry.from_artifacts(cfg, params, {"a": path})
    eng = DecodeEngine(cfg, params, n_slots=2, max_len=32,
                       warmup_prompt_cap=16, adapters=tp_reg,
                       mesh_plan=serve_mesh_plan(tp=2))
    eng.warmup()
    assert run_engine(eng, reqs()) == ref
    assert eng.n_recompiles == 0


# ---------------------------------------------------------------------------
# router: dispatch, affinity, spans, metrics
# ---------------------------------------------------------------------------

def make_adapters(cfg, params, names, tmp):
    from building_llm_from_scratch_tpu.models.lora import (
        init_lora_params,
        save_adapter,
    )

    paths = {}
    for i, name in enumerate(names):
        lora = init_lora_params(cfg, params, jax.random.PRNGKey(10 + i),
                                rank=4)
        p = os.path.join(str(tmp), f"{name}.npz")
        save_adapter(p, lora, rank=4, alpha=8.0, cfg=cfg)
        paths[name] = p
    return paths


def test_router_affinity_spans_and_metrics(model, sink, tmp_path):
    """Mixed-tenant traffic through a 2-replica router: adapter traffic
    lands on the resident replica (affinity ratio > 0), every request
    closes exactly ONE span tree with the router hop as a child +
    replica attribution, /metrics re-exports per-replica labeled series
    (histograms included) next to fleet gauges, and the whole run costs
    zero recompiles."""
    cfg, params = model
    paths = make_adapters(cfg, params, ("ta", "tb"), tmp_path)
    router = EngineRouter.build(cfg, params, n_replicas=2,
                                adapter_specs=paths, n_slots=2,
                                max_len=32, warmup_prompt_cap=16,
                                metrics_every=2)
    router.warmup()
    # round-robin placement: one adapter per replica
    residency = {name: [i for i, e in enumerate(router.engines)
                        if e.adapters.lookup(name) is not None]
                 for name in paths}
    assert sorted(len(v) for v in residency.values()) == [1, 1]
    rng = np.random.default_rng(0)
    handles = []
    for i in range(9):
        sp = SamplingParams(max_new_tokens=4, ignore_eos=True, seed=i,
                            adapter=[None, "ta", "tb"][i % 3])
        handles.append(router.submit(
            rng.integers(2, 96, (4,)).astype(np.int32), sp, block=True))
    router.run_until_idle()
    for h in handles:
        h.result(timeout=120)
        if h.params.adapter is not None:
            # adapter-affinity measurably routed: the request ran on the
            # replica holding its adapter row
            assert h.route["replica"] in residency[h.params.adapter]
            assert h.route["affinity"] == "adapter"
    stats = router.stats()
    assert stats["routed_by_affinity_ratio"] > 0
    assert stats["requests_finished"] == 9
    assert router.n_recompiles == 0

    rows = load_rows(sink)
    spans = [r for r in rows if r.get("type") == "span"]
    done = [r for r in rows if r.get("event") == "request_done"]
    assert len(spans) == len(done) == 9
    ids = [s["request_id"] for s in spans]
    assert len(set(ids)) == 9           # exactly one closed tree per id
    for s in spans:
        kids = [c["name"] for c in s["children"]]
        assert kids[0] == "router"      # the router hop child span
        assert "replica" in s
        t0, t1 = s["t0"], s["t0"] + s["dur_s"]
        for c in s["children"]:
            assert c["t0"] >= t0 - 1e-6
            assert c["t0"] + c["dur_s"] <= t1 + 1e-6
    for r in done:
        assert r.get("replica") in (0, 1)

    text = router.prometheus_text()
    assert 'bllm_serve_requests_finished_total{replica="0"}' in text
    assert 'bllm_serve_requests_finished_total{replica="1"}' in text
    assert 'bllm_serve_ttft_seconds_bucket{replica="0",le=' in text
    assert "bllm_serve_replicas_up 2" in text
    assert "bllm_serve_routed_by_affinity_ratio" in text
    # adapter + replica labels merge into one label set
    assert 'adapter="ta",replica=' in text
    payload = router.healthz_payload()
    assert payload["status"] == "serving"
    assert payload["replicas_total"] == 2
    assert len(payload["replicas"]) == 2
    router.shutdown()


def test_router_hot_load_on_miss(model, sink, tmp_path):
    """Fleet-wide residency miss: the router hot-loads the tenant's
    artifact onto a live replica and serves — no client-visible 400."""
    cfg, params = model
    paths = make_adapters(cfg, params, ("tc",), tmp_path)
    router = EngineRouter.build(cfg, params, n_replicas=2, n_slots=2,
                                max_len=32, warmup_prompt_cap=16,
                                adapter_specs={}, metrics_every=0)
    # registries exist but are empty; the router knows the path
    router._adapter_paths.update(paths)
    router.warmup()
    h = router.submit(np.array([2, 3, 4], np.int32),
                      SamplingParams(max_new_tokens=4, ignore_eos=True,
                                     adapter="tc"))
    router.run_until_idle()
    h.result(timeout=120)
    assert router.hot_loads == 1
    assert h.route["affinity"] == "adapter"
    # unknown adapter with no path still rejects like a single engine
    with pytest.raises(ValueError):
        router.submit(np.array([2], np.int32),
                      SamplingParams(adapter="nope"))
    router.shutdown()


def test_router_drain_replica_loses_nothing(model, sink):
    """Drain ONE replica under live traffic: its queued work re-dispatches
    onto the survivor (same Request handles), in-flight work finishes,
    every submitted request completes, zero recompiles anywhere."""
    cfg, params = model
    router = EngineRouter.build(cfg, params, n_replicas=2, n_slots=1,
                                max_len=48, warmup_prompt_cap=16,
                                max_queue=16, metrics_every=0)
    router.warmup()
    rng = np.random.default_rng(1)
    # submit BEFORE starting the loops: both replicas' queues fill
    # deterministically, so the drain below must actually re-dispatch
    handles = [router.submit(rng.integers(2, 96, (4,)).astype(np.int32),
                             SamplingParams(max_new_tokens=16,
                                            ignore_eos=True, seed=i),
                             block=True)
               for i in range(8)]
    stolen = len(router.engines[0].queue)
    assert stolen > 0
    router.drain_replica(0, timeout=120)
    assert router.redispatched == stolen      # every queued request moved
    router.start()
    for h in handles:
        h.result(timeout=300)           # raises if anything was dropped
    assert all(len(h.output_ids) == 16 for h in handles)
    stats = router.stats()
    assert stats["requests_finished"] == 8
    assert router.n_recompiles == 0
    rows = load_rows(sink)
    drains = [r for r in rows if r.get("event") == "replica_drain"]
    assert {d["phase"] for d in drains} == {"start", "end"}
    redis = [r for r in rows if r.get("event") == "router_redispatch"]
    end = [d for d in drains if d["phase"] == "end"][0]
    assert end["n_redispatched"] == len(redis)
    assert len(redis) == stolen
    for r in redis:
        assert r["from_replica"] == 0 and r["to_replica"] == 1
    # the drained replica is out of dispatch; traffic still flows
    h = router.submit(np.array([5, 6], np.int32),
                      SamplingParams(max_new_tokens=3, ignore_eos=True))
    h.result(timeout=120)
    assert h.route["replica"] == 1
    router.shutdown()


def test_drain_keeps_tenant_work_on_resident_replica(model, tmp_path):
    """A drain must NOT re-dispatch tenant work onto a replica that
    doesn't hold (and can't load) the adapter — adopt() bypasses
    submit-time validation, so it would fail at admission. The queued
    requests stay with the draining replica, which finishes them."""
    from building_llm_from_scratch_tpu.serving import AdapterRegistry

    cfg, params = model
    paths = make_adapters(cfg, params, ("ta",), tmp_path)
    regs = [AdapterRegistry.from_artifacts(cfg, params, paths),
            AdapterRegistry(cfg, params, capacity=2)]
    engines = [DecodeEngine(cfg, params, n_slots=1, max_len=32,
                            warmup_prompt_cap=16, adapters=regs[i],
                            replica=i)
               for i in range(2)]
    for eng in engines:
        eng.warmup()
    router = EngineRouter(engines)      # no artifact paths known
    handles = [router.submit(np.array([2, 3], np.int32),
                             SamplingParams(max_new_tokens=4,
                                            ignore_eos=True,
                                            adapter="ta", seed=i),
                             block=True)
               for i in range(3)]
    assert len(router.engines[0].queue) == 3    # manual mode: all queued
    router.drain_replica(0, timeout=120)        # drain ticks them done
    for h in handles:
        h.result(timeout=120)                   # nothing dropped/failed
    assert router.redispatched == 0
    router.shutdown()


def test_router_deadline_aware_dispatch(model):
    """Deadline-aware dispatch: with replica 0 backlogged (its live
    TPOT/queue EWMAs predict a miss), a deadline request routes to the
    idle replica; when EVERY replica predicts a miss the router sheds
    fleet-wide with a Retry-After."""
    from building_llm_from_scratch_tpu.serving import SLOShedError

    cfg, params = model
    router = EngineRouter.build(cfg, params, n_replicas=2, n_slots=1,
                                max_len=48, warmup_prompt_cap=16,
                                max_queue=32, metrics_every=0,
                                prefix_affinity=False)
    router.warmup()
    # seed both replicas' service EWMAs with one finished request each
    for eng in router.engines:
        eng.submit(np.array([2, 3], np.int32),
                   SamplingParams(max_new_tokens=4, ignore_eos=True))
        eng.run_until_idle()
    # backlog replica 0 directly (bypassing the router)
    backlog = [router.engines[0].submit(
        np.array([2, 3], np.int32),
        SamplingParams(max_new_tokens=16, ignore_eos=True))
        for _ in range(6)]
    snap = router.engines[0].service_snapshot()
    est0 = router._estimate(snap, 8)
    assert est0 is not None and est0 > 0
    deadline = max(est0 / 4, 0.05)      # replica 0 predicts a miss
    h = router.submit(np.array([4, 5], np.int32),
                      SamplingParams(max_new_tokens=8, ignore_eos=True,
                                     deadline_s=60.0))
    assert h.route["replica"] == 1      # routed around the backlog
    # now blow every replica's budget: fleet-wide shed
    backlog += [router.engines[1].submit(
        np.array([2, 3], np.int32),
        SamplingParams(max_new_tokens=16, ignore_eos=True))
        for _ in range(6)]
    with pytest.raises(SLOShedError):
        router.submit(np.array([4, 5], np.int32),
                      SamplingParams(max_new_tokens=8, ignore_eos=True,
                                     deadline_s=deadline / 1000))
    router.run_until_idle()
    for h2 in backlog:
        h2.result(timeout=300)
    router.shutdown()


def test_router_prefix_affinity(model):
    """Shared-prefix traffic lands on ONE replica (stable hash), so its
    PrefixStore accumulates hits instead of every replica going cold."""
    from building_llm_from_scratch_tpu.serving import KVCachePolicy

    cfg, params = model
    policy = KVCachePolicy(prefix_cache=True, prefill_chunk=8)
    router = EngineRouter.build(cfg, params, n_replicas=2, n_slots=2,
                                max_len=48, warmup_prompt_cap=16,
                                kv_policy=policy, metrics_every=0)
    router.warmup()
    system = np.arange(8, dtype=np.int32) + 2       # shared 8-tok prefix
    handles = []
    for i in range(6):
        prompt = np.concatenate([system,
                                 np.array([20 + i], np.int32)])
        handles.append(router.submit(
            prompt, SamplingParams(max_new_tokens=3, ignore_eos=True,
                                   seed=i)))
    router.run_until_idle()
    replicas = set()
    for h in handles:
        h.result(timeout=120)
        assert h.route["affinity"] == "prefix"
        replicas.add(h.route["replica"])
    assert len(replicas) == 1           # all on one replica
    hit_store = router.engines[replicas.pop()].prefix_store
    assert hit_store.n_hits >= 5        # co-located traffic actually hit
    router.shutdown()


# ---------------------------------------------------------------------------
# CLI wiring (run_serve / make_http_server single-engine assumption fix)
# ---------------------------------------------------------------------------

def _serve_cli(tmp_path, extra, n=6):
    from building_llm_from_scratch_tpu.args import get_args
    from building_llm_from_scratch_tpu.main import main

    d = str(tmp_path)
    reqs = os.path.join(d, "requests.jsonl")
    with open(reqs, "w") as f:
        for i in range(n):
            f.write(json.dumps({"prompt": "abcd"[: 1 + i % 4],
                                "max_new_tokens": 3, "ignore_eos": True,
                                "seed": i}) + "\n")
    out = os.path.join(d, "results.jsonl")
    mj = os.path.join(d, "metrics.jsonl")
    engine = main(get_args([
        "--mode", "serve", "--debug", "--byte_tokenizer",
        "--data_dir", d, "--serve_prompts", reqs, "--serve_out", out,
        "--serve_slots", "2", "--serve_max_queue", str(max(n, 8)),
        "--metrics_jsonl", mj] + extra))
    return engine, [json.loads(line) for line in open(out)], \
        [json.loads(line) for line in open(mj)]


def test_cli_single_replica_path_pinned(tmp_path):
    """--serve_replicas 1 (the default) is the historical path: a plain
    DecodeEngine, NO router object, no replica fields in the telemetry,
    no `router` span child — byte-identical single-engine behavior."""
    engine, results, rows = _serve_cli(tmp_path, [])
    assert isinstance(engine, DecodeEngine)
    assert not isinstance(engine, EngineRouter)
    assert len(results) == 6
    for r in rows:
        if r.get("event") in ("request_done", "serve_warmup"):
            assert "replica" not in r
        if r.get("type") == "span":
            assert "router" not in [c["name"] for c in r["children"]]
            assert "replica" not in r


def test_cli_router_path(tmp_path):
    """--serve_replicas 2 routes through an EngineRouter: all requests
    complete, telemetry rows carry replica attribution, every span tree
    has the router-hop child, zero recompiles in every replica."""
    engine, results, rows = _serve_cli(tmp_path, ["--serve_replicas", "2"])
    assert isinstance(engine, EngineRouter)
    assert engine.n_replicas == 2
    assert len(results) == 6
    assert all(r["finish_reason"] == "length" for r in results)
    assert engine.n_recompiles == 0
    done = [r for r in rows if r.get("event") == "request_done"]
    assert len(done) == 6
    assert all(r.get("replica") in (0, 1) for r in done)
    spans = [r for r in rows if r.get("type") == "span"]
    # each replica's set-up books are a span row of their own
    setups = [s for s in spans if s["name"] == "setup"]
    assert sorted(s["replica"] for s in setups) == [0, 1]
    spans = [s for s in spans if s["name"] == "request"]
    assert len(spans) == 6
    for s in spans:
        assert [c["name"] for c in s["children"]][0] == "router"
    fleet = [r for r in rows if r.get("event") == "serve_fleet"]
    assert any(f["phase"] == "build" for f in fleet)


def test_stray_serve_replicas_flag_guarded():
    from building_llm_from_scratch_tpu.args import get_args

    with pytest.raises(ValueError, match="serve_replicas"):
        get_args(["--data_dir", "/tmp", "--serve_replicas", "2"])
    with pytest.raises(ValueError, match="serve_tp"):
        get_args(["--data_dir", "/tmp", "--serve_tp", "2"])


def test_micro_router_fingerprint_replica_count_invariant():
    """The micro_router gate contract: with watch_compiles="first" the
    captured fingerprint is ONE replica's program family — adding a
    replica must not change it (same digest at 2 and 3 replicas)."""
    from building_llm_from_scratch_tpu.configs import get_config
    from building_llm_from_scratch_tpu.obs import perf

    cfg = get_config("GPT2", "124M", dtype="fp32", debug=True)
    params = init_params(cfg, jax.random.PRNGKey(0))

    def digest(n):
        with perf.FingerprintCollector() as col:
            router = EngineRouter.build(cfg, params, n_replicas=n,
                                        n_slots=2, warmup_prompt_cap=4,
                                        metrics_every=0,
                                        watch_compiles="first")
            router.warmup()
            router.shutdown()
        return perf.fingerprint_digest(col.fingerprint())

    assert digest(2) == digest(3)
