"""The chunk program's attention kernel (ops/chunk_attention.py, interpret
mode) against ``decode_attention`` on the same row, which stays the
reference and the path of everything the rule refuses; the rule's table; and
what the engine says of it. ``tests/test_cohere2_moe.py`` holds a whole
chunked prefill to the materialised path's, ``tests/test_tpu_compile.py``
the rag cell's chunk program to the chip's compiler.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from building_llm_from_scratch_tpu.models import transformer as tf
from building_llm_from_scratch_tpu.ops import chunk_attention as ca
from building_llm_from_scratch_tpu.ops.attention import (
    decode_attention,
    ring_positions,
)

HD, C = 128, 128        # the kernel's head_dim, and a chunk of one key block
FULL, RING, WINDOW = 512, 384, 256      # buffers: a full layer; window + 2C


def _case(ring, chunk_start, prompt_len, G, *, slot=0, C=C, Tmax=None,
          dtype=jnp.bfloat16, S=3, Hkv=2, garbage=None):
    """q of one chunk, a layer's buffers with the request's positions
    [0, chunk_start + C) in row ``slot`` (pads zeroed, a ring by position),
    everything the request never wrote holding ``garbage`` in the dirty
    pair, and ``decode_attention``'s answer on the clean pair."""
    Tmax = Tmax or (RING if ring else FULL)
    ks = jax.random.split(jax.random.PRNGKey(chunk_start + prompt_len + G), 3)
    q = jax.random.normal(ks[0], (1, C, Hkv * G, HD)).astype(dtype)
    last = chunk_start + C - 1
    kv_len = min(chunk_start + C, prompt_len)
    held = (ring_positions(jnp.asarray(last), Tmax) if ring
            else jnp.arange(Tmax))
    written = ((held >= 0) & (held <= last) & (held < prompt_len))
    written = written[None, None, :, None] & (
        jnp.arange(S) == slot)[:, None, None, None]
    clean, dirty = [], []
    for k in ks[1:]:
        x = 2.0 * jax.random.normal(k, (S, Hkv, Tmax, HD))
        clean.append(jnp.where(written, x, 0.0).astype(dtype))
        dirty.append(jnp.where(written, x, 0.0 if garbage is None
                               else garbage).astype(dtype))
    ring_kw = ({"kv_positions": held[None], "window": WINDOW} if ring else {})
    want = decode_attention(
        q, *(x[slot:slot + 1] for x in clean),
        q_positions=(chunk_start + jnp.arange(C))[None],
        kv_length=jnp.asarray([kv_len]), **ring_kw)
    return q, dirty, kv_len, want


@pytest.mark.parametrize("name,ring,chunk_start,prompt_len,kw", [
    ("full_first_chunk", False, 0, 500, {}),
    ("full_mid_prompt", False, 256, 500, {}),
    ("full_prompt_ends_in_chunk", False, 128, 200, {}),
    ("full_live_length_on_block_edge", False, 256, 384, {}),
    ("full_live_length_one_past_edge", False, 256, 257, {}),
    ("full_mha_one_head_a_group", False, 128, 250, dict(G=1)),
    ("full_other_slot_longer_leftovers", False, 0, 100,
     dict(slot=2, garbage=np.nan)),
    ("full_fp32", False, 128, 250, dict(dtype=jnp.float32)),
    ("full_two_key_blocks_a_chunk", False, 256, 300, dict(C=256, Tmax=1024)),
    ("ring_first_chunk", True, 0, 500, {}),
    ("ring_mid_prompt_unwrapped", True, 128, 500, {}),
    ("ring_wrapped", True, 640, 2000, {}),
    ("ring_wrapped_prompt_ends_in_chunk", True, 1152, 1200, {}),
    ("ring_wrapped_edge_one_past", True, 768, 769, {}),
    ("ring_mha_wrapped", True, 512, 600, dict(G=1)),
    ("ring_other_slot_longer_leftovers", True, 128, 200,
     dict(slot=1, garbage=3e38)),
    ("ring_two_key_blocks_a_chunk_pads_dead", True, 1536, 1600,
     dict(C=256, Tmax=768, garbage=np.nan)),
])
def test_chunk_live_attention_matches_decode_attention(name, ring,
                                                       chunk_start,
                                                       prompt_len, kw):
    """Every real query's output equals ``decode_attention``'s: causal
    inside the chunk, ``kv_len`` and the window by absolute position, a
    ring that has wrapped (chunk_start > window + C), a prompt that ends
    inside the chunk (pad queries are garbage in their own rows, and
    finite), sixteen query heads on a key-value head and one. What the
    request never wrote (another request's longer leftovers: NaN, or the
    largest number) reaches nothing: those blocks are not read."""
    kw = dict({"G": 16}, **kw)
    dtype = kw.get("dtype", jnp.bfloat16)
    q, (K, V), kv_len, want = _case(ring, chunk_start, prompt_len, **kw)
    got = jax.jit(functools.partial(
        ca.chunk_live_attention, window=WINDOW if ring else None,
        interpret=True))(q, K, V, kw.get("slot", 0), chunk_start, kv_len)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert bool(jnp.isfinite(got.astype(jnp.float32)).all())
    real = kv_len - chunk_start
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(
        np.asarray(got.astype(jnp.float32))[:, :real],
        np.asarray(want.astype(jnp.float32))[:, :real], atol=tol, rtol=tol)


@pytest.mark.parametrize("lo,hi,Tmax,positions", [
    (0, 100, FULL, 128),            # one block of the first chunk
    (256, 384, FULL, 384),          # a live length on a block's edge
    (256, 385, 1024, 512),          # and one past it (C = 128)
    (0, 128, RING, 128),            # a ring before it wraps: like a full one
    (640, 700, RING, 384),          # wrapped: the whole ring
])
def test_chunk_positions_read_counts_live_blocks(lo, hi, Tmax, positions):
    assert ca.chunk_positions_read(lo, hi, C, Tmax) == positions


def _cache(S=2, H=8, T=4608, D=HD, dtype=jnp.bfloat16, quant=False):
    shape = lambda d: jax.ShapeDtypeStruct((S, H, T, d), dtype)
    cache = {"k": [shape(D)], "v": [shape(D)]}
    if quant:
        cache["k_scale"] = cache["v_scale"] = [
            jax.ShapeDtypeStruct((S, H, T, 1), jnp.float32)]
    return cache


@pytest.mark.parametrize("why,C_,kw,path", [
    ("rag_ring", 512, {}, "live_blocks"),
    ("rag_full_layer", 512, dict(T=20480), "live_blocks"),
    ("fp32_cache", 512, dict(dtype=jnp.float32), "live_blocks"),
    ("head_dim_64", 512, dict(D=64), "materialised"),
    ("int8_cache", 512, dict(dtype=jnp.int8, quant=True), "materialised"),
    ("chunk_not_whole_blocks", 64, {}, "materialised"),
    ("buffer_not_whole_blocks", 512, dict(T=4608 + 64), "materialised"),
    ("over_vmem_budget", 8192, dict(T=16384), "materialised"),
    ("not_a_tpu", 512, {}, "materialised"),
])
def test_chunk_attention_rule(why, C_, kw, path):
    """One rule, on shapes and a dtype: the rag cell's four buffers take
    the kernel, and everything it refuses keeps ``decode_attention``."""
    backend = None if why == "not_a_tpu" else "tpu"
    assert tf.chunk_attention_path(_cache(**kw), C_, 128,
                                   backend=backend) == path


@pytest.mark.parametrize("forced", [False, True],
                         ids=["materialised", "live_blocks"])
def test_chunk_kv_asks_the_rule_once_a_layer(monkeypatch, forced):
    """``_ChunkKV`` is the rule's only asker: told it is on a TPU the traced
    chunk holds one kernel call and the row is never sliced out (the index
    maps reach it by ``slot``); on the CPU it really is on, today's
    operations and no kernel."""
    from tests.test_serving import tiny_cfg

    if forced:
        monkeypatch.setattr(tf, "chunk_attention_path", functools.partial(
            tf.chunk_attention_path, backend="tpu"))
    cfg = tiny_cfg(ctx=FULL)
    cache = {"k": [jnp.zeros((2, 2, FULL, HD))],
             "v": [jnp.zeros((2, 2, FULL, HD))]}
    x = jnp.ones((1, C, 2, HD))

    def attend(cache, x):
        kv = tf._ChunkKV(cfg, cache, C, jnp.int32(1), jnp.int32(128),
                         jnp.int32(200))
        return kv.append_and_attend(0, "full", x, x, x)

    jaxpr = str(jax.make_jaxpr(attend)(cache, x))
    assert ("chunk_live_attention" in jaxpr) == forced
    assert ("dynamic_slice[" in jaxpr) != forced


def test_engine_names_chunk_attention():
    """``chunk_attention`` in ``stats()`` and ``/healthz``: None where no
    chunk program is built, ``materialised`` for a ``head_dim`` the rule
    refuses, with the tick record's ``chunk_kv_touched`` counting whole
    buffers."""
    from building_llm_from_scratch_tpu.models import init_params
    from building_llm_from_scratch_tpu.obs.metrics import get_metrics
    from building_llm_from_scratch_tpu.serving import (
        DecodeEngine,
        KVCachePolicy,
        SamplingParams,
    )
    from tests.test_serving import tiny_cfg

    cfg = tiny_cfg(ctx=64)
    params = init_params(cfg, jax.random.PRNGKey(0))
    plain = DecodeEngine(cfg, params, n_slots=2, max_len=64)
    assert plain.chunk_attention is None
    assert plain.stats()["chunk_attention"] is None
    eng = DecodeEngine(cfg, params, n_slots=2, max_len=64,
                       kv_policy=KVCachePolicy(prefill_chunk=16))
    assert eng.chunk_attention == "materialised"
    assert eng.stats()["chunk_attention"] == "materialised"
    assert eng.healthz_payload()["chunk_attention"] == "materialised"
    req = eng.submit(np.arange(2, 22, dtype=np.int32), SamplingParams(
        max_new_tokens=3, temperature=0.0, ignore_eos=True))
    eng.run_until_idle()
    assert req.finish_reason == "length"
    chunked = [t for t in get_metrics().recent("tick") if t.get("chunks")]
    assert [t["chunk_kv_touched"] for t in chunked[-2:]] \
        == [cfg.n_layers * 64] * 2


@pytest.mark.parametrize("tp", [1, 2], ids=["one_device", "serve_tp2"])
def test_engine_tokens_identical_under_chunk_kernel(monkeypatch, tp):
    """A dense model of ``head_dim`` 128 under chunked prefill: one engine
    run with the chunk program's attention on the kernel (the rule told it
    is on a TPU; the kernel interprets on the CPU it really is on), one on
    ``decode_attention``: the same greedy and sampled tokens for a prompt of
    three chunks (the last carries pads) beside a short one. Under
    ``--serve_tp`` each device's kernel attends its own head."""
    from building_llm_from_scratch_tpu.models import init_params
    from building_llm_from_scratch_tpu.parallel.sharding import (
        serve_mesh_plan,
    )
    from building_llm_from_scratch_tpu.serving import (
        DecodeEngine,
        KVCachePolicy,
        SamplingParams,
    )
    from building_llm_from_scratch_tpu.serving import engine as engine_mod
    from tests.test_serving import tiny_cfg

    cfg = tiny_cfg(ctx=FULL, emb_dim=2 * HD)
    assert cfg.head_dim == HD
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompts = [np.arange(300, dtype=np.int32) % 90 + 2,
               np.array([5, 6, 7, 8, 9], np.int32)]
    cases = [SamplingParams(max_new_tokens=5, seed=3, ignore_eos=True),
             SamplingParams(max_new_tokens=6, temperature=0.9, top_k=5,
                            seed=3, ignore_eos=True)]

    def run():
        eng = DecodeEngine(cfg, params, n_slots=2, max_len=FULL,
                           kv_policy=KVCachePolicy(prefill_chunk=C),
                           mesh_plan=serve_mesh_plan(tp=tp) if tp > 1
                           else None)
        handles = [eng.submit(p, sp) for p, sp in zip(prompts, cases)]
        eng.run_until_idle()
        assert all(h.done and h.finish_reason == "length" for h in handles)
        return eng.chunk_attention, [h.output_ids for h in handles]

    materialised = run()
    on_tpu = functools.partial(tf.chunk_attention_path, backend="tpu")
    monkeypatch.setattr(tf, "chunk_attention_path", on_tpu)
    monkeypatch.setattr(engine_mod, "chunk_attention_path", on_tpu)
    kernel = run()
    assert (materialised[0], kernel[0]) == ("materialised", "live_blocks")
    assert kernel[1] == materialised[1]
