"""Multi-tenant LoRA serving tests (serving/adapters.py + the model/ops
adapter path):

  - merge-free ``apply_lora`` parity against ``merge_lora`` (forward
    logits + generate() token equality) — the shared unmerged helper;
  - adapter artifact round-trip (rank/alpha/fingerprint) and the
    registry's refusal modes (fingerprint mismatch, capacity, rank,
    tree shape);
  - batched per-slot application: engine tokens bit-identical to
    single-adapter merged-weights ``generate()`` per adapter, mixed
    co-residency isolation (slot A's adapter never leaks into slot B),
    hot-load/evict under live traffic, zero recompiles throughout
    (frozen CompileWatcher);
  - per-adapter telemetry (request_done fields, labeled /metrics
    series) and the BGMV pallas kernel (interpret-mode parity on CPU,
    real-kernel parity TPU-gated).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from building_llm_from_scratch_tpu.configs import ModelConfig
from building_llm_from_scratch_tpu.generate import generate
from building_llm_from_scratch_tpu.models import init_params
from building_llm_from_scratch_tpu.models.lora import (
    adapter_fingerprint,
    apply_lora,
    count_lora_params,
    init_lora_params,
    load_adapter,
    merge_lora,
    save_adapter,
)
from building_llm_from_scratch_tpu.serving import (
    AdapterMismatchError,
    AdapterRegistry,
    AdapterRegistryFullError,
    DecodeEngine,
    SamplingParams,
)


def tiny_cfg(ctx=64, **kw):
    base = dict(name="lora-serve-tiny", vocab_size=96, context_length=ctx,
                emb_dim=32, n_heads=2, n_layers=2, hidden_dim=64,
                n_kv_groups=2, norm="layernorm", positional="learned",
                activation="gelu", drop_rate=0.0, eos_id=1)
    base.update(kw)
    return ModelConfig(**base)


def make_lora(cfg, params, seed, rank):
    """An adapter with NONZERO B (init_lora_params zeros B — its delta
    would be trivially zero and every parity test vacuous)."""
    lora = init_lora_params(cfg, params, jax.random.PRNGKey(seed),
                            rank=rank)
    return jax.tree_util.tree_map(
        lambda a: a + 0.05 * jax.random.normal(
            jax.random.PRNGKey(seed + 1000), a.shape, a.dtype), lora)


@pytest.fixture(scope="module")
def model():
    cfg = tiny_cfg()
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture()
def registry(model, tmp_path):
    """Registry with adapters 'a' (rank 4), 'b' (rank 8) and 'c' (rank 2)
    loaded from real artifacts, one spare row; returns (registry,
    {name: (lora, rank, alpha)})."""
    cfg, params = model
    specs, loras = {}, {}
    for i, (name, rank, alpha) in enumerate([("a", 4, 8.0),
                                             ("b", 8, 16.0),
                                             ("c", 2, 3.0)]):
        lora = make_lora(cfg, params, 10 + i, rank)
        path = str(tmp_path / f"{name}.npz")
        save_adapter(path, lora, rank=rank, alpha=alpha, cfg=cfg)
        specs[name] = path
        loras[name] = (lora, rank, alpha)
    return AdapterRegistry.from_artifacts(cfg, params, specs,
                                          capacity=5), loras


def solo_tokens(ref_params, cfg, prompt, sp: SamplingParams):
    out, n = generate(ref_params, cfg, np.asarray(prompt)[None],
                      max_new_tokens=sp.max_new_tokens,
                      temperature=sp.temperature, top_k=sp.top_k,
                      eos_id=(None if sp.ignore_eos else cfg.eos_id),
                      rng=jax.random.PRNGKey(sp.seed),
                      return_n_generated=True)
    Tp = len(prompt)
    return [int(t) for t in out[0, Tp: Tp + int(n[0])]]


def merged_for(model, loras, name):
    cfg, params = model
    if name is None:
        return params
    lora, rank, alpha = loras[name]
    return merge_lora(params, lora, alpha, rank)


# ---------------------------------------------------------------------------
# apply_lora: the shared merge-free helper
# ---------------------------------------------------------------------------

def test_apply_lora_matches_merge_lora_projection():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 3, 16)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((16, 24)).astype(np.float32))
    a = jnp.asarray(rng.standard_normal((16, 4)).astype(np.float32))
    b = jnp.asarray(rng.standard_normal((4, 24)).astype(np.float32))
    scaling = 2.0
    got = apply_lora(x, w, {"A": a, "B": b}, scaling)
    want = x @ (w + scaling * a @ b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # node None is bit-identical to the bare matmul (base-path guarantee)
    np.testing.assert_array_equal(np.asarray(apply_lora(x, w, None)),
                                  np.asarray(x @ w))
    # per-row scale 0 = exact zero delta even with nonzero A/B
    batched = {"A": jnp.stack([a, a]), "B": jnp.stack([b, b])}
    got0 = apply_lora(x, w, batched, jnp.asarray([0.0, 2.0]))
    np.testing.assert_array_equal(np.asarray(got0[0]), np.asarray(x @ w)[0])


def test_unmerged_forward_and_generate_match_merged(model):
    from building_llm_from_scratch_tpu.models.transformer import forward

    cfg, params = model
    rank, alpha = 4, 8.0
    lora = make_lora(cfg, params, 7, rank)
    merged = merge_lora(params, lora, alpha, rank)
    toks = (np.arange(12, dtype=np.int32)[None, :] % 90)
    lm = forward(merged, cfg, jnp.asarray(toks))
    lu = forward(params, cfg, jnp.asarray(toks), lora=lora,
                 lora_scaling=alpha / rank)
    np.testing.assert_allclose(np.asarray(lm), np.asarray(lu),
                               rtol=2e-5, atol=2e-5)
    om = generate(merged, cfg, toks, max_new_tokens=12, eos_id=None,
                  rng=jax.random.PRNGKey(3))
    ou = generate(params, cfg, toks, max_new_tokens=12, eos_id=None,
                  rng=jax.random.PRNGKey(3), lora=lora, lora_alpha=alpha,
                  lora_rank=rank)
    np.testing.assert_array_equal(om, ou)


def test_generate_lora_requires_alpha_rank(model):
    cfg, params = model
    lora = make_lora(cfg, params, 7, 4)
    with pytest.raises(ValueError, match="lora_alpha"):
        generate(params, cfg, np.zeros((1, 4), np.int32),
                 max_new_tokens=2, lora=lora)


def test_count_lora_params(model):
    cfg, params = model
    lora = init_lora_params(cfg, params, jax.random.PRNGKey(0), rank=2)
    expect = sum(int(np.prod(np.shape(leaf)))
                 for leaf in jax.tree_util.tree_leaves(lora))
    assert count_lora_params(lora) == expect > 0


# ---------------------------------------------------------------------------
# adapter artifacts + registry
# ---------------------------------------------------------------------------

def test_adapter_artifact_roundtrip(model, tmp_path):
    cfg, params = model
    lora = make_lora(cfg, params, 3, 4)
    path = str(tmp_path / "adap.npz")
    save_adapter(path, lora, rank=4, alpha=8.0, cfg=cfg)
    got, meta = load_adapter(path)
    assert meta["rank"] == 4 and meta["alpha"] == 8.0
    assert meta["fingerprint"] == adapter_fingerprint(cfg)
    for a, b in zip(jax.tree_util.tree_leaves(lora),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_load_rejects_non_adapter_npz(model, tmp_path):
    cfg, params = model
    path = str(tmp_path / "not_adapter.npz")
    np.savez(path, foo=np.zeros(3))
    reg = AdapterRegistry(cfg, params, capacity=2, max_rank=8)
    with pytest.raises(ValueError, match="not an adapter artifact"):
        reg.load("x", path)


def test_registry_refuses_fingerprint_mismatch(model, tmp_path):
    cfg, params = model
    other_cfg = tiny_cfg(emb_dim=48, n_heads=3)
    other_params = init_params(other_cfg, jax.random.PRNGKey(1))
    lora = make_lora(other_cfg, other_params, 5, 4)
    path = str(tmp_path / "mismatch.npz")
    save_adapter(path, lora, rank=4, alpha=8.0, cfg=other_cfg)
    reg = AdapterRegistry(cfg, params, capacity=2, max_rank=8)
    with pytest.raises(AdapterMismatchError):
        reg.load("bad", path)
    assert reg.n_loaded == 0


def test_registry_capacity_rank_and_duplicates(model, tmp_path):
    cfg, params = model
    paths = {}
    for name, rank in [("r1", 2), ("r2", 2), ("big", 16)]:
        p = str(tmp_path / f"{name}.npz")
        save_adapter(p, make_lora(cfg, params, hash(name) % 100, rank),
                     rank=rank, alpha=4.0, cfg=cfg)
        paths[name] = p
    reg = AdapterRegistry(cfg, params, capacity=2, max_rank=8)
    assert reg.load("r1", paths["r1"]) == 0
    with pytest.raises(ValueError, match="already loaded"):
        reg.load("r1", paths["r1"])
    with pytest.raises(ValueError, match="max_rank"):
        reg.load("big", paths["big"])
    assert reg.load("r2", paths["r2"]) == 1
    with pytest.raises(AdapterRegistryFullError):
        reg.load("r3", paths["r1"])
    # names flow into /metrics label values: quotes/braces/spaces refused
    for bad in ('ten"ant', "a b", "x{y}", "", "-lead"):
        with pytest.raises(ValueError, match="invalid"):
            reg.load(bad, paths["r1"])
    with pytest.raises(KeyError):
        reg.evict("nope")
    assert reg.evict("r1") == 0
    assert reg.lookup("r1") is None and reg.lookup("r2") == 1
    # freed row is reusable (no engine attached -> nothing in use)
    assert reg.load("r1b", paths["r1"]) == 0


# ---------------------------------------------------------------------------
# engine: batched per-slot application
# ---------------------------------------------------------------------------

def test_engine_adapter_parity_vs_merged_generate(model, registry):
    """Acceptance: mixed-adapter traffic (2 adapters + base interleaved),
    greedy AND seeded sampling — every request's tokens bit-identical to
    single-adapter merged-weights generate(), zero recompiles."""
    cfg, params = model
    reg, loras = registry
    engine = DecodeEngine(cfg, params, n_slots=4, max_len=64,
                          warmup_prompt_cap=32, adapters=reg)
    engine.warmup()
    rng = np.random.default_rng(0)
    cases = []
    for i, name in enumerate([None, "a", "b", "c", "a", None, "b", "c"]):
        prompt = rng.integers(0, 90, (4 + i % 5,)).astype(np.int32)
        sp = SamplingParams(
            max_new_tokens=6 + i % 4, ignore_eos=True, seed=i,
            temperature=0.8 if i % 2 else 0.0,
            top_k=8 if i % 2 else None, adapter=name)
        cases.append((engine.submit(prompt, sp), prompt, sp, name))
    engine.run_until_idle()
    for handle, prompt, sp, name in cases:
        handle.result(timeout=30)
        expect = solo_tokens(merged_for(model, loras, name), cfg, prompt,
                             sp)
        assert handle.output_ids == expect, (name, sp.seed)
    assert engine.n_recompiles == 0
    engine.shutdown()


def test_coresident_adapters_do_not_leak(model, registry):
    """Isolation: a request's tokens are identical whether it runs alone
    or co-batched with OTHER adapters' traffic — slot A's adapter never
    contaminates slot B."""
    cfg, params = model
    reg, _ = registry
    prompt = np.arange(5, dtype=np.int32) + 3
    sp = SamplingParams(max_new_tokens=8, ignore_eos=True, seed=42)

    def run(co_traffic: bool):
        engine = DecodeEngine(cfg, params, n_slots=4, max_len=64,
                              warmup_prompt_cap=32, adapters=reg)
        engine.warmup()
        main_req = engine.submit(prompt, sp)
        if co_traffic:
            rng = np.random.default_rng(9)
            for i, nm in enumerate(["a", "b", "a"]):
                engine.submit(rng.integers(0, 90, (6,)).astype(np.int32),
                              SamplingParams(max_new_tokens=10,
                                             ignore_eos=True, seed=50 + i,
                                             adapter=nm))
        engine.run_until_idle()
        main_req.result(timeout=30)
        engine.shutdown()
        return main_req.output_ids

    assert run(co_traffic=False) == run(co_traffic=True)


def test_hot_load_evict_under_traffic(model, registry, tmp_path):
    """Acceptance: hot-load and evict complete under live traffic (engine
    loop running) without failing in-flight requests, with zero
    recompiles."""
    cfg, params = model
    reg, loras = registry
    engine = DecodeEngine(cfg, params, n_slots=2, max_len=64,
                          warmup_prompt_cap=32, max_queue=64, adapters=reg)
    engine.warmup()
    engine.start()
    try:
        rng = np.random.default_rng(1)
        handles = []
        for i in range(10):       # steady 'a'/base traffic
            nm = "a" if i % 2 else None
            handles.append((nm, engine.submit(
                rng.integers(0, 90, (5,)).astype(np.int32),
                SamplingParams(max_new_tokens=12, ignore_eos=True,
                               seed=i, adapter=nm))))
        # hot-load 'hot' mid-traffic into the spare row, serve with it
        lora_c = make_lora(cfg, params, 77, 4)
        path_c = str(tmp_path / "hot.npz")
        save_adapter(path_c, lora_c, rank=4, alpha=8.0, cfg=cfg)
        reg.load("hot", path_c)
        c_prompt = rng.integers(0, 90, (5,)).astype(np.int32)
        c_sp = SamplingParams(max_new_tokens=8, ignore_eos=True, seed=99,
                              adapter="hot")
        c_handle = engine.submit(c_prompt, c_sp)
        # evict 'b' (no traffic) under load; in-flight work is untouched
        reg.evict("b")
        for nm, h in handles:
            h.result(timeout=60)
            assert h.finish_reason == "length", (nm, h.error)
        c_handle.result(timeout=60)
        merged_c = merge_lora(params, lora_c, 8.0, 4)
        assert c_handle.output_ids == solo_tokens(merged_c, cfg, c_prompt,
                                                  c_sp)
        # post-evict submits for 'b' reject at submit (HTTP 400 class)
        with pytest.raises(ValueError, match="not loaded"):
            engine.submit(c_prompt, SamplingParams(adapter="b"))
        assert engine.n_recompiles == 0
    finally:
        engine.shutdown()


def test_evicted_while_queued_fails_in_isolation(model, registry):
    """A queued request whose adapter is evicted before admission fails
    ALONE (reason adapter_not_loaded); co-queued base traffic decodes."""
    cfg, params = model
    reg, _ = registry
    engine = DecodeEngine(cfg, params, n_slots=1, max_len=64,
                          warmup_prompt_cap=32, max_queue=8, adapters=reg)
    engine.warmup()
    prompt = np.arange(4, dtype=np.int32) + 2
    doomed = engine.submit(prompt, SamplingParams(
        max_new_tokens=4, ignore_eos=True, adapter="a"))
    survivor = engine.submit(prompt, SamplingParams(
        max_new_tokens=4, ignore_eos=True))
    reg.evict("a")                # before any tick ran
    engine.run_until_idle()
    with pytest.raises(RuntimeError, match="evicted while queued"):
        doomed.result(timeout=10)
    survivor.result(timeout=10)
    assert survivor.finish_reason == "length"
    assert engine.n_recompiles == 0
    engine.shutdown()


def test_row_in_use_not_reused(model, registry, tmp_path):
    """An evicted adapter's pool row must not be overwritten while an
    active slot still decodes against it."""
    cfg, params = model
    reg, _ = registry   # capacity 5: rows 0-2 = 'a'/'b'/'c', rows 3-4 free
    engine = DecodeEngine(cfg, params, n_slots=1, max_len=64,
                          warmup_prompt_cap=32, adapters=reg)
    engine.warmup()
    prompt = np.arange(4, dtype=np.int32) + 2
    h = engine.submit(prompt, SamplingParams(max_new_tokens=50,
                                             ignore_eos=True, adapter="a"))
    assert engine.step()          # admitted: slot 0 references row 0
    reg.evict("a")
    # fill the two genuinely free rows (3, 4); row 0 must stay untouchable
    paths = {}
    for i, name in enumerate(["x1", "x2"]):
        p = str(tmp_path / f"{name}.npz")
        save_adapter(p, make_lora(cfg, params, 200 + i, 2), rank=2,
                     alpha=4.0, cfg=cfg)
        paths[name] = p
        row = reg.load(name, p)
        assert row != 0, "reused a row an active slot references"
    with pytest.raises(AdapterRegistryFullError, match="referenced"):
        reg.load("x3", paths["x1"])
    engine.run_until_idle()       # request finishes, slot frees
    h.result(timeout=30)
    assert reg.load("x3", paths["x1"]) == 0   # now reusable
    assert engine.n_recompiles == 0
    engine.shutdown()


def test_per_adapter_telemetry(model, registry):
    """request_done carries the adapter name; /metrics exports labeled
    per-adapter counters; stats() aggregates per adapter."""
    from building_llm_from_scratch_tpu.obs.metrics import (
        configure_metrics,
        get_metrics,
    )

    cfg, params = model
    reg, _ = registry
    configure_metrics(None)
    engine = DecodeEngine(cfg, params, n_slots=2, max_len=64,
                          warmup_prompt_cap=32, adapters=reg)
    engine.warmup()
    rows = []
    orig_event = get_metrics().event

    def spy(kind, **fields):
        rows.append((kind, fields))
        return orig_event(kind, **fields)

    get_metrics().event = spy
    try:
        prompt = np.arange(5, dtype=np.int32) + 1
        for nm in ["a", None, "b", "a"]:
            engine.submit(prompt, SamplingParams(
                max_new_tokens=4, ignore_eos=True, adapter=nm))
        engine.run_until_idle()
    finally:
        get_metrics().event = orig_event
    done = [f for k, f in rows if k == "request_done"]
    assert sorted(f.get("adapter", "base") for f in done) == \
        ["a", "a", "b", "base"]
    stats = engine.stats()
    assert stats["per_adapter"]["a"]["finished"] == 2
    assert stats["per_adapter"]["base"]["tokens"] == 4
    text = engine.prometheus_text()
    assert 'bllm_serve_adapter_requests_finished_total{adapter="a"} 2' \
        in text
    assert "bllm_serve_adapters_loaded" in text
    engine.shutdown()


def test_registry_less_engine_signature_unchanged(model):
    """Without a registry the engine's compiled call signature (and
    behavior) is the historical one — adapters are pay-for-use."""
    cfg, params = model
    engine = DecodeEngine(cfg, params, n_slots=2, max_len=64,
                          warmup_prompt_cap=32)
    engine.warmup()
    prompt = np.arange(4, dtype=np.int32) + 2
    with pytest.raises(ValueError, match="no adapter registry"):
        engine.submit(prompt, SamplingParams(adapter="a"))
    h = engine.submit(prompt, SamplingParams(max_new_tokens=4,
                                             ignore_eos=True))
    engine.run_until_idle()
    h.result(timeout=10)
    assert engine.n_recompiles == 0
    engine.shutdown()


# ---------------------------------------------------------------------------
# BGMV kernel (ops/decode_step.py)
# ---------------------------------------------------------------------------

def _bgmv_case():
    rng = np.random.default_rng(0)
    S, N, D, r, O = 5, 3, 128, 8, 256
    x = rng.standard_normal((S, D)).astype(np.float32)
    A = rng.standard_normal((N, D, r)).astype(np.float32)
    B = rng.standard_normal((N, r, O)).astype(np.float32)
    ids = np.array([0, -1, 2, 1, 2], np.int32)
    scales = np.array([0.5, 2.0, 0.25], np.float32)
    ref = np.stack([
        (scales[i] * (x[s] @ A[i]) @ B[i]) if i >= 0
        else np.zeros(O, np.float32)
        for s, i in enumerate(ids)
    ])
    return x, A, B, ids, scales, ref


def test_lora_bgmv_interpret_parity():
    from building_llm_from_scratch_tpu.ops.decode_step import lora_bgmv

    x, A, B, ids, scales, ref = _bgmv_case()
    out = np.asarray(lora_bgmv(jnp.asarray(x), jnp.asarray(A),
                               jnp.asarray(B), jnp.asarray(ids),
                               jnp.asarray(scales), interpret=True))
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


@pytest.mark.needs_tpu
def test_lora_bgmv_tpu_parity():
    from building_llm_from_scratch_tpu.ops.decode_step import lora_bgmv

    x, A, B, ids, scales, ref = _bgmv_case()
    out = np.asarray(lora_bgmv(jnp.asarray(x), jnp.asarray(A),
                               jnp.asarray(B), jnp.asarray(ids),
                               jnp.asarray(scales)))
    np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)


def test_supports_lora_shape_gate():
    from building_llm_from_scratch_tpu.ops.decode_step import (
        supports_lora_shape,
    )

    assert supports_lora_shape(768, 8, 768)
    assert supports_lora_shape(768, 16, 3072)
    assert not supports_lora_shape(100, 8, 768)      # unaligned in
    assert not supports_lora_shape(768, 8, 50257)    # unaligned out
    assert not supports_lora_shape(768, 4, 768)      # sub-sublane rank
