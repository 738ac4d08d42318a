"""Gated-delta-rule linear layers beside one gated NoPE attention layer, with
sparse experts (Solar-Open2, ``model_type: solar_open2``) at the debug size
(two periods of full, linear, linear, linear; 4 heads of 16; 8 experts
top-2, 1 shared), against its plain reference
(``benchmark/reference/solar_open2.py``, which imports nothing of the
program and runs the recurrence token by token): the two forms of the
recurrence, the slot cache's state beside keys and values, continuous
batching over rows whose memory is no prefix, the shares of an
expert-parallel deployment, and what the engine refuses.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import solar_open2 as ref
from building_llm_from_scratch_tpu.configs import (
    UNSUPPORTED,
    get_config,
    refuse_unsupported,
)
from building_llm_from_scratch_tpu.generate import generate
from building_llm_from_scratch_tpu.models import moe
from building_llm_from_scratch_tpu.models import transformer as tf
from building_llm_from_scratch_tpu.obs.metrics import get_metrics
from building_llm_from_scratch_tpu.ops import linear_attention as la
from building_llm_from_scratch_tpu.serving import (
    DecodeEngine,
    KVCachePolicy,
    SamplingParams,
)

CHUNK = 16
CHUNKED = KVCachePolicy(prefill_chunk=CHUNK)
GREEDY = dict(temperature=0.0, ignore_eos=True)


def debug_cfg(**kw):
    return get_config("solar_open2", "250B", debug=True,
                      dtype="fp32").replace(**kw)


@pytest.fixture(scope="module")
def model():
    cfg = debug_cfg()
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    # a router that spreads its scores: at the init's 0.02 every margin
    # between experts is a near-tie
    params["blocks"]["moe"]["router"] = 40.0 * params["blocks"]["moe"]["router"]
    return cfg, params, dataclasses.asdict(cfg)


def tokens_of(cfg, n, seed=1, rows=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, n), 0,
                              cfg.vocab_size)


def prefill(cfg, params, cache, seq, n_prompt, slot, chunked):
    """The prompt into ``slot`` as serving does it: chunks of CHUNK (the last
    one padded) or one bucket of 64. -> (logits at the last position,
    cache)."""
    if not chunked:
        padded = np.zeros((1, 64), np.int32)
        padded[0, :n_prompt] = seq[:n_prompt]
        return jax.jit(lambda c, t: tf.prefill_into_slot(
            params, cfg, t, jnp.int32(n_prompt), jnp.int32(slot), c))(
                cache, padded)
    chunk = jax.jit(lambda c, t, s: tf.prefill_chunk_into_slot(
        params, cfg, t, s, jnp.int32(n_prompt), jnp.int32(slot), c))
    for lo in range(0, n_prompt, CHUNK):
        piece = np.zeros((1, CHUNK), np.int32)
        hi = min(lo + CHUNK, n_prompt)
        piece[0, :hi - lo] = seq[lo:hi]
        logits, cache = chunk(cache, piece, jnp.int32(lo))
    return logits, cache


def decode(cfg, params, cache, token, length, slot, S=3, others=()):
    """One tick in which ``slot`` alone decodes; ``others``: (slot, length)
    of rows in mid-prefill (the engine keeps their next write position)."""
    toks = np.zeros((S, 1), np.int32)
    toks[slot] = token
    lengths = np.zeros((S,), np.int32)
    lengths[slot] = length
    for s, n in others:
        lengths[s] = n
    logits, cache = jax.jit(lambda c, t, l: tf.decode_slots(
        params, cfg, t, l, c, live=jnp.arange(S) == slot))(
            cache, toks, lengths)
    return logits[slot], cache


# -- (a) the cached path against one pass of the reference -------------------

@pytest.mark.parametrize("chunked", [False, True],
                         ids=["bucketed", "three_chunks"])
def test_cached_path_matches_reference(model, chunked):
    """37 prompt tokens (three chunks of 16, the last with 11 pads, or one
    bucket of 64 with 27) into a slot that held another request's state,
    then 20 decode ticks: float32 against float32 agrees to rounding at
    every position."""
    cfg, params, m = model
    seq = np.asarray(tokens_of(cfg, 57)[0])
    policy = CHUNKED if chunked else KVCachePolicy()
    cache = tf.init_slot_cache(cfg, 3, cfg.context_length, policy=policy)
    dirty = lambda a: None if a is None else a + 1
    cache = dict(cache, state=[dirty(a) for a in cache["state"]],
                 conv=[dirty(a) for a in cache["conv"]])
    with jax.default_matmul_precision("highest"):
        logits, cache = prefill(cfg, params, cache, seq, 37, 1, chunked)
        got = [logits]
        for t in range(37, 57):
            logits, cache = decode(cfg, params, cache, seq[t], t, 1)
            got.append(logits)
        want = ref.logits_fn(params, m, seq[None])[0, 36:]
    assert float(jnp.abs(jnp.stack(got) - want).max()) < 2e-5
    # a linear layer holds no positions, a full one no state
    assert [a is None for a in cache["k"]] == [False, True, True, True] * 2
    assert [a is None for a in cache["state"]] == [True, False, False,
                                                   False] * 2


def test_reference_in_bf16_fails_the_tolerance(model):
    cfg, params, m = model
    seq = tokens_of(cfg, 57)
    with jax.default_matmul_precision("highest"):
        want = ref.logits_fn(params, m, seq)
        low = ref.logits_fn(params, m, seq, precision="bf16")
        blocked = ref.logits_fn(params, m, seq[:, :48], block_rows=8,
                                piece_rows=16)
    assert float(jnp.abs(low - want).max()) > 2e-3
    # in pieces (the state and the tail go from piece to piece) and blocks
    assert float(jnp.abs(blocked - want[:, :48]).max()) < 1e-5


def test_forward_and_gradient_match_reference(model):
    cfg, params, m = model
    seq = tokens_of(cfg, 40, seed=2, rows=2)

    def loss(logits_of):
        def fn(p):
            logp = jax.nn.log_softmax(logits_of(p)[:, :-1], axis=-1)
            return -jnp.sum(jnp.take_along_axis(
                logp, seq[:, 1:, None], axis=-1))
        return fn

    with jax.default_matmul_precision("highest"):
        got = tf.forward(params, cfg, seq)
        want = ref.logits_fn(params, m, seq)
        g_got = jax.grad(loss(lambda p: tf.forward(p, cfg, seq)))(params)
        g_want = jax.grad(loss(lambda p: ref.logits_fn(p, m, seq)))(params)
    assert float(jnp.abs(got - want).max()) < 2e-5
    worst = jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-9)),
        g_got, g_want)
    assert max(jax.tree_util.tree_leaves(worst)) < 1e-3, worst


def test_generate_matches_reference_greedy(model):
    cfg, params, m = model
    prompt = np.asarray(tokens_of(cfg, 21, seed=5))
    out = generate(params, cfg, prompt, max_new_tokens=30, temperature=0.0,
                   eos_id=None, rng=jax.random.PRNGKey(0))
    seq = np.asarray(out[0])
    want = np.asarray(jnp.argmax(ref.logits_fn(params, m, seq[None, :-1])[0],
                                 -1))[20:]
    assert seq.shape == (51,) and (seq[21:] == want).all()


# -- (b) the two forms of the recurrence -------------------------------------

@pytest.mark.parametrize("T,sub_chunk", [(1, None), (64, None), (64, 16),
                                         (150, None), (37, 32)])
def test_chunked_form_equals_token_by_token(T, sub_chunk):
    """From a non-zero state, with the stress a trained model brings: one
    head that forgets everything in a token (log-decay -11: a decay split
    into exp(G_t) and exp(-G_s) overflows there), one that forgets nothing
    (decay 1), step sizes next to 2."""
    B, H, dk, dv = 2, 3, 32, 16
    ks = jax.random.split(jax.random.PRNGKey(T), 6)
    q = la.l2norm(jax.random.normal(ks[0], (B, T, H, dk))) * dk ** -0.5
    k = la.l2norm(jax.random.normal(ks[1], (B, T, H, dk)))
    v = jax.random.normal(ks[2], (B, T, H, dv))
    g = -jax.random.uniform(ks[3], (B, T, H, dk), minval=1e-5, maxval=0.02)
    g = g.at[:, :, 0].set(-11.0).at[:, :, 1].set(0.0)
    beta = 2 - jax.random.uniform(ks[4], (B, T, H), maxval=0.1)
    state = 0.1 * jax.random.normal(ks[5], (B, H, dk, dv))
    o_want, s_want = la.recurrent_scan(q, k, v, g, beta, state)
    o_got, s_got = la.chunked_delta_rule(q, k, v, g, beta, state,
                                         sub_chunk=sub_chunk)
    assert bool(jnp.isfinite(o_got).all())
    assert float(jnp.abs(o_got - o_want).max()) < 2e-5
    assert float(jnp.abs(s_got - s_want).max()) < 1e-4
    assert la.linear_attention_path(T) == ("step" if T == 1 else "chunked")


# -- (c) the shares of an expert-parallel deployment add up ------------------

def test_sixteen_shares_of_a_layers_experts_add_up_to_the_uncut_layer():
    """32 experts over 16 chips of 2: what every chip computes alike (the
    mixer, the shared expert) counted once, plus the routed part of each
    chip's held pair, is the uncut reference's layer (a linear one)."""
    cfg = debug_cfg(n_routed_experts=32, n_experts_per_tok=4)
    params = tf.init_params(cfg, jax.random.PRNGKey(4))
    params["blocks"]["moe"]["router"] = 40.0 * params["blocks"]["moe"]["router"]
    m = dataclasses.asdict(cfg)
    p1 = tf._layer_of(cfg, params["blocks"], 1)
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 24, cfg.emb_dim))
    with jax.default_matmul_precision("highest"):
        want, _ = ref._layer(m, "float32", params["blocks"], 1, x[0], 24, 24)
        for chip in range(16):
            held = (2 * chip, 2 * chip + 1)
            cut = cfg.replace(experts_held=held)
            p_cut = dict(p1, moe=dict(p1["moe"], experts=jax.tree_util.tree_map(
                lambda a: a[jnp.asarray(held)], p1["moe"]["experts"])))
            full = tf._block(cut, p_cut, x, None, None, None, True,
                             kind="linear")[0]
            n2 = tf._norm(cfg, p1["norm2"], _after_mixer(cut, p_cut, x))
            routed = (moe.moe_ffn(cut, p_cut["moe"], n2)[0][0]
                      - moe._shared(p_cut["moe"]["shared"], n2[0]))
            if chip == 0:
                total = full - routed           # mixer + shared expert, once
            total = total + routed
    assert float(jnp.abs(total - want).max()) < 2e-5


def _after_mixer(cfg, p, x):
    """x + Mix(RMSNorm1(x)): what the expert layer's norm reads."""
    n1 = tf._norm(cfg, p["norm1"], x)
    return x + tf._linear_mixer(cfg, p["linear"], n1, lambda run: run(
        *tf._fresh_state(cfg, "linear", x.shape[0], x.dtype))[0])


# -- (d) rows whose memory is no prefix ---------------------------------------

def test_decode_tick_leaves_a_mid_prefill_slot_bit_identical(model):
    """Slot 1 is between two of its chunks while slot 0 decodes: the tick
    runs slot 1's row too (fixed shapes), and must leave its state and its
    convolution's tail as they were, bit for bit; its later chunks and
    tokens then read as if no tick had come between."""
    cfg, params, m = model
    seq = np.asarray(tokens_of(cfg, 45, seed=8)[0])
    other = np.asarray(tokens_of(cfg, 20, seed=9)[0])
    cache = tf.init_slot_cache(cfg, 3, cfg.context_length, policy=CHUNKED)
    _, cache = prefill(cfg, params, cache, other, 19, 0, True)
    _, cache = prefill(cfg, params, cache, seq, 16, 1, True)     # chunk one
    before = jax.tree_util.tree_map(lambda a: np.asarray(a[1]),
                                    {n: cache[n] for n in ("state", "conv")})
    _, cache = decode(cfg, params, cache, other[19], 19, 0, others=[(1, 16)])
    after = jax.tree_util.tree_map(lambda a: np.asarray(a[1]),
                                   {n: cache[n] for n in ("state", "conv")})
    for a, b in zip(jax.tree_util.tree_leaves(before),
                    jax.tree_util.tree_leaves(after)):
        assert a.tobytes() == b.tobytes()
    assert any(np.abs(a).max() > 0 for a in jax.tree_util.tree_leaves(after))
    # the rest of slot 1's prompt, chunk by chunk, from where it stood
    chunk = jax.jit(lambda c, t, s: tf.prefill_chunk_into_slot(
        params, cfg, t, s, jnp.int32(40), jnp.int32(1), c))
    for lo in (16, 32):
        piece = np.zeros((1, CHUNK), np.int32)
        piece[0, :min(CHUNK, 40 - lo)] = seq[lo:min(lo + CHUNK, 40)]
        logits, cache = chunk(cache, piece, jnp.int32(lo))
    got = [logits]
    for t in range(40, 45):
        logits, cache = decode(cfg, params, cache, seq[t], t, 1)
        got.append(logits)
    want = ref.logits_fn(params, m, seq[None])[0, 39:]
    assert float(jnp.abs(jnp.stack(got) - want).max()) < 2e-5


# -- (d2) the tick's step walks the decoding rows in place -------------------

@pytest.mark.parametrize("S,H,hd,live", [
    (48, 64, 128, [3, 17, 18, 40]),                 # the cell's widths
    (6, 4, 16, []),
    (6, 4, 16, [2]),
    (6, 4, 16, [0, 1]),                             # live rows first
    (6, 4, 16, [4, 5]),                             # last
    (6, 4, 16, [0, 3, 5]),                          # scattered
    (6, 4, 16, range(6)),                           # all
], ids=lambda v: str(len(v)) if not isinstance(v, int) else str(v))
def test_the_walk_is_the_step_on_the_live_rows_and_reads_no_other(S, H, hd,
                                                                   live):
    """``recurrent_step_rows`` against ``recurrent_step``. Every state a
    tick may not read holds NaN and 3e38: those rows come back bit for bit,
    and nothing non-finite reaches ``o``."""
    from building_llm_from_scratch_tpu.ops.selective_scan import (
        live_rows_table,
    )

    ks = jax.random.split(jax.random.PRNGKey(S + len(live)), 6)
    q = la.l2norm(jax.random.normal(ks[0], (S, H, hd))) * hd ** -0.5
    k = la.l2norm(jax.random.normal(ks[1], (S, H, hd)))
    v = jax.random.normal(ks[2], (S, H, hd))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (S, H, hd)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (S, H)))
    mask = np.zeros((S,), bool)
    mask[list(live)] = True
    poison = jnp.where(jnp.arange(hd) % 2 == 0, jnp.nan, 3e38)
    state = jnp.where(mask[:, None, None, None],
                      jax.random.normal(ks[5], (S, H, hd, hd)), poison)
    want_o, want_s = la.recurrent_step(q, k, v, g, beta, state)
    o, new = jax.jit(la.recurrent_step_rows)(
        q, k, v, g, beta, state, live_rows_table(jnp.asarray(mask)))
    dead = ~mask
    assert np.array_equal(np.asarray(new)[dead], np.asarray(state)[dead],
                          equal_nan=True)
    assert bool(jnp.isfinite(o).all()) and not np.asarray(o)[dead].any()
    np.testing.assert_allclose(np.asarray(o)[mask], np.asarray(want_o)[mask],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(new)[mask],
                               np.asarray(want_s)[mask], rtol=1e-6, atol=1e-6)


def walking(monkeypatch):
    """The rule as a TPU answers it, in the program and in the engine."""
    from building_llm_from_scratch_tpu.serving import engine as engine_mod

    rule = tf.state_step_path
    forced = lambda *a, **kw: rule(*a, **dict(kw, backend="tpu"))
    monkeypatch.setattr(tf, "state_step_path", forced)
    monkeypatch.setattr(engine_mod, "state_step_path", forced)


def test_state_step_path_takes_any_linear_state(model):
    cfg = model[0]
    cache = tf.init_slot_cache(cfg, 3, cfg.context_length, policy=CHUNKED)
    l = cfg.state_layers[0]
    path = lambda **kw: tf.state_step_path(cache, "linear", kw.pop("Tq", 1),
                                           **{"layer": l, "rows_named": True,
                                              "backend": "tpu", **kw})
    assert path() == "live_rows"
    assert path(backend="cpu") == path(backend=None) == "whole_buffer"
    assert path(rows_named=False) == path(Tq=2) == "whole_buffer"


def test_a_walked_tick_leaves_a_mid_prefill_slot_bit_identical(model,
                                                               monkeypatch):
    """``test_decode_tick_leaves_a_mid_prefill_slot_bit_identical`` on the
    walk's path, the free slot's states NaN: the decoding row's logits are
    the whole-buffer form's, and no other row's state or tail moved."""
    cfg, params, _ = model
    seq = np.asarray(tokens_of(cfg, 45, seed=8)[0])
    other = np.asarray(tokens_of(cfg, 20, seed=9)[0])
    cache = tf.init_slot_cache(cfg, 3, cfg.context_length, policy=CHUNKED)
    _, cache = prefill(cfg, params, cache, other, 19, 0, True)
    _, cache = prefill(cfg, params, cache, seq, 16, 1, True)     # chunk one
    cache["state"] = [None if a is None else a.at[2].set(jnp.nan)
                      for a in cache["state"]]
    kept = lambda c: jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda a: np.asarray(a[1:]), {n: c[n] for n in ("state", "conv")}))
    before = kept(cache)
    want, _ = decode(cfg, params, cache, other[19], 19, 0, others=[(1, 16)])
    walking(monkeypatch)
    got, after = decode(cfg, params, cache, other[19], 19, 0,
                        others=[(1, 16)])
    assert bool(jnp.isfinite(got).all())
    assert float(jnp.abs(got - want).max()) < 2e-5
    for a, b in zip(before, kept(after)):
        assert a.tobytes() == b.tobytes()
    assert all(not np.array_equal(np.asarray(cache["state"][l][0]),
                                  np.asarray(after["state"][l][0]))
               for l in cfg.state_layers)


def test_engine_tokens_identical_with_the_walk_on_and_off(model, monkeypatch):
    """Greedy requests through the engine, chunked prefill between decode
    ticks and slots used again, with the tick's step on either path: the same
    tokens; on the walk's path every tick's ``state_rows_touched`` is its
    ``state_rows``, a tick whose one decoding row stands beside a free slot
    and one between two of its chunks among them."""
    cfg, params, _ = model
    prompts = [np.asarray(tokens_of(cfg, n, seed=s)[0])
               for n, s in ((5, 12), (38, 11), (13, 13), (33, 14))]

    def serve():
        eng = DecodeEngine(cfg, params, None, n_slots=3, kv_policy=CHUNKED,
                           max_queue=8)
        first = eng.submit(prompts[0], SamplingParams(max_new_tokens=14,
                                                      **GREEDY))
        while not first.output_ids:
            eng.step()
        rest = [eng.submit(p, SamplingParams(max_new_tokens=6, **GREEDY))
                for p in prompts[1:]]
        eng.run_until_idle()
        return eng, [list(r.output_ids) for r in [first] + rest]

    eng, want = serve()
    assert eng.state_step == eng.stats()["state_step"] == "whole_buffer"
    mark = get_metrics().recent("tick")[-1]["t1"]
    walking(monkeypatch)
    eng, got = serve()
    assert eng.state_step == eng.healthz_payload()["state_step"] \
        == "live_rows"
    assert got == want
    ticks = [t for t in get_metrics().recent("tick")
             if t.get("state_rows") and t["t0"] >= mark]
    assert ticks and all(
        t["state_rows_touched"] == t["state_rows"] == 6 * t["rows"]
        for t in ticks)
    assert any(t["rows"] == 1 and t.get("chunk_tokens") == CHUNK
               for t in ticks)


@pytest.mark.parametrize("policy", [CHUNKED, KVCachePolicy()],
                         ids=["chunked", "monolithic"])
def test_engine_tokens_match_reference_and_slot_reuse_is_clean(model, policy):
    """Two slots, four requests: a long prompt is between its chunks while
    another decodes, and each slot is used a second time by a request that
    must start from a zero state: every request's greedy tokens are the
    reference's own argmax over its sequence alone (what a fresh engine
    gives)."""
    cfg, params, m = model
    eng = DecodeEngine(cfg, params, None, n_slots=2, kv_policy=policy,
                       max_queue=8)
    prompts = [np.asarray(tokens_of(cfg, n, seed=s)[0])
               for n, s in ((38, 11), (5, 12), (13, 13), (33, 14))]
    reqs = [eng.submit(p, SamplingParams(max_new_tokens=20, **GREEDY))
            for p in prompts]
    eng.run_until_idle()
    for p, r in zip(prompts, reqs):
        assert r.finish_reason == "length"
        seq = np.concatenate([p, np.asarray(r.output_ids)])
        want = np.asarray(jnp.argmax(
            ref.logits_fn(params, m, seq[None, :-1])[0], -1))[len(p) - 1:]
        assert (np.asarray(r.output_ids) == want).all()
    assert eng.n_recompiles == 0


def test_coresident_requests_in_bf16_agree_with_the_reference(model):
    """The cell's own arithmetic (bfloat16 weights and cache, float32 state
    and router, two of the eight experts held) with three requests sharing
    ticks: at positions without a router near-tie the served token lies
    within a bfloat16 rounding of the reference's best, and the fp8 control
    does not."""
    cfg, params, _ = model
    held = (1, 4)
    cfg16 = cfg.replace(dtype="bf16", experts_held=held)
    p16 = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)
    p16["blocks"]["moe"]["experts"] = jax.tree_util.tree_map(
        lambda a: a[:, jnp.asarray(held)], p16["blocks"]["moe"]["experts"])
    eng = DecodeEngine(cfg16, p16, None, n_slots=3, kv_policy=CHUNKED)
    assert eng.cache["state"][1].dtype == jnp.float32
    prompts = [np.asarray(tokens_of(cfg, n, seed=s)[0])
               for n, s in ((30, 21), (7, 22), (19, 23))]
    reqs = [eng.submit(p, SamplingParams(max_new_tokens=24, **GREEDY))
            for p in prompts]
    eng.run_until_idle()
    p32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p16)
    out = ref.served_token_gaps(
        p32, dataclasses.asdict(cfg16),
        [(p, np.asarray(r.output_ids)) for p, r in zip(prompts, reqs)],
        pad_to=64, control="fp8_e4m3")
    assert out["tokens"] == 72
    assert out["compared_share"] >= ref.LEAST_COMPARED
    assert out["widest_gap"] < 0.02


@pytest.mark.parametrize("logits, near", [
    ([1.0, 5.0, 0.99, 0.0, 0.0, 0.0], True),     # held last in, 3rd near it
    ([0.99, 5.0, 1.0, 0.0, 0.0, 0.0], True),     # held best out, 2nd near it
    ([1.0, 0.995, 0.99, 0.0, 0.0, 0.0], True),   # held 1st, 2nd AND 3rd near
    ([0.99, 5.0, 1.0, 0.995, 0.0, 0.0], True),   # held 4th, near the 2nd
    ([1.0, 0.995, 0.5, 0.0, 0.0, 0.0], False),   # held 1st, only the 2nd near
    ([0.5, 5.0, 1.0, 0.995, 0.0, 0.0], False),   # held far out, 2nd/3rd tie
    ([5.0, 1.0, 0.995, 0.0, 0.0, 0.0], False),   # held far in, 2nd/3rd tie
])
def test_a_near_tie_is_a_held_expert_near_the_edge_at_any_rank(logits, near):
    """``_route``'s margin and ``left_out``: the held expert (id 0) within
    ``NEAR_TIE`` of the edge of the chosen two, whatever its rank (the chip's
    runs: a held 7th within 0.02 of the 9th went the other way in the
    program), and not a tie between two experts held elsewhere."""
    m = dict(n_experts_per_tok=2, n_routed_experts=6, experts_held=[0])
    rows = jnp.asarray([logits], jnp.float32)
    w, margin = ref._route(m, rows, jnp.eye(6, dtype=jnp.float32))
    chosen = np.argsort(logits)[-2:]
    assert sorted(np.flatnonzero(np.asarray(w[0])).tolist()) == sorted(chosen)
    rms = float(np.sqrt(np.mean(np.square(logits))))
    assert (float(margin[0]) < ref.NEAR_TIE) == near
    if near:
        np.testing.assert_allclose(float(margin[0]), 0.01 / rms, rtol=1e-3)
    margins = np.full((4, 3), np.inf)
    margins[2, 1] = float(margin[0])
    assert ref.left_out(margins).tolist() == [False, near, False]


# -- (e) the cache's bytes, the ledger, what the engine says of itself -------

def test_state_bytes_the_ledger_and_the_tick_record(model):
    cfg, params, _ = model
    S = 3
    eng = DecodeEngine(cfg, params, None, n_slots=S, kv_policy=CHUNKED)
    assert CHUNKED.layer_lengths(cfg, 64) == [64, 0, 0, 0] * 2
    bps = CHUNKED.bytes_per_slot(cfg, cfg.context_length)
    H, hd = cfg.linear_heads, cfg.linear_head_dim
    assert bps["kv_bytes"] == 2 * 64 * 2 * cfg.n_kv_groups * cfg.head_dim * 4
    assert bps["state_bytes"] == 6 * (H * hd * hd * 4 + 3 * 3 * H * hd * 4)
    assert bps["total_bytes"] == bps["kv_bytes"] + bps["state_bytes"]
    held = lambda *names: sum(a.nbytes for n in names for a in eng.cache[n]
                              if a is not None)
    assert held("k", "v") == S * bps["kv_bytes"]
    assert held("state", "conv") == S * bps["state_bytes"]
    snap = eng.memory_ledger.observe()
    assert snap["slot_kv"] == S * bps["kv_bytes"]
    assert snap["slot_state"] == S * bps["state_bytes"]
    assert eng.memory_ledger.n_drift_events == 0
    assert eng.layout()["state"] == {"layers": 6,
                                     "bytes_per_slot": bps["state_bytes"]}
    assert eng.layout()["kv_positions"] == {"full": 64}
    want = {"tick": "step", "prefill": "chunked"}
    assert eng.linear_attention == want
    assert eng.stats()["linear_attention"] == want
    assert eng.healthz_payload()["linear_attention"] == want
    assert eng.healthz_payload()["state"]["layers"] == 6

    req = eng.submit(np.asarray(tokens_of(cfg, 9)[0]),
                     SamplingParams(max_new_tokens=4, **GREEDY))
    eng.run_until_idle()
    assert req.finish_reason == "length"
    last = [t for t in get_metrics().recent("tick") if "state_rows" in t][-1]
    # one decoding row of three, six linear layers; two full layers of
    # 9 + 3 positions
    assert (last["rows"], last["state_rows"], last["state_rows_touched"]) \
        == (1, 6, 18)
    assert last["kv_positions"] == 2 * 12
    assert sum(last["expert_rows"]) == 16
    # a model without such layers says so
    dense = get_config("GPT2", "124M", debug=True)
    plain = DecodeEngine(dense, tf.init_params(dense, jax.random.PRNGKey(0)),
                         None, n_slots=2)
    assert plain.linear_attention is None and "state" not in plain.layout()


# -- what is refused, at construction, in one sentence -----------------------

@pytest.mark.parametrize("kw,match", [
    (dict(kv_policy=KVCachePolicy(prefill_chunk=4, paged=True,
                                   page_tokens=4)), "a page holds positions"),
    (dict(kv_policy=KVCachePolicy(prefill_chunk=4, prefix_cache=True)),
     "the state after them"),
    (dict(kv_policy=KVCachePolicy(kv_quant="int8")), "no int8 form"),
    (dict(spec_k=2), "already moved the recurrent state"),
    (dict(adapters=object()), "a layer that holds a state has others"),
])
def test_engine_refuses_what_a_state_does_not_support(model, kw, match):
    """By the linear layers alone: the config here has no experts."""
    cfg, params, _ = model
    cfg = cfg.replace(n_routed_experts=0, n_experts_per_tok=0,
                      n_shared_experts=0)
    with pytest.raises(ValueError, match=match):
        DecodeEngine(cfg, params, None, n_slots=2, **kw)


@pytest.mark.parametrize("feature,match", [
    ("tensor_parallel", "state's heads"),
    ("pipeline_parallel", "layers of one kind"),
    ("sequence_parallel", "from shard to shard"),
])
def test_refusals_follow_the_config_not_its_name(model, feature, match):
    cfg, _, _ = model
    linear_only = cfg.replace(name="another-name", n_routed_experts=0,
                              n_experts_per_tok=0, n_shared_experts=0)
    with pytest.raises(ValueError, match=match) as e:
        refuse_unsupported(linear_only, **{feature: True})
    assert "another-name (linear-attention layers)" in str(e.value)
    # every feature of the list is refused to it, none to a dense model
    for name in {name for name, _, _ in UNSUPPORTED}:
        with pytest.raises(ValueError):
            refuse_unsupported(linear_only, **{name: True})
        refuse_unsupported(get_config("GPT2", "124M"), **{name: True})


@pytest.mark.parametrize("flags,match", [
    ((), "no tokenizer is registered"),
    (("--byte_tokenizer", "--load_weights"), "no checkpoint converter"),
    (("--byte_tokenizer", "--use_lora"), "LoRA"),
    (("--byte_tokenizer", "--run_type", "multi_chip", "--sp", "2"),
     "sequence split"),
])
def test_flags_refuse_what_the_config_does_not_support(tmp_path, flags,
                                                       match):
    from building_llm_from_scratch_tpu.args import get_args

    base = ["--data_dir", str(tmp_path), "--model", "solar_open2",
            "--num_params", "250B", "--debug"]
    with pytest.raises(ValueError, match=match):
        get_args(base + list(flags))
    assert get_args(base + ["--byte_tokenizer"]).model == "solar_open2"


def test_a_config_with_linear_layers_says_what_they_are():
    with pytest.raises(ValueError, match="linear_heads"):
        debug_cfg(linear_heads=0)
    with pytest.raises(ValueError, match="'sliding', 'full', 'linear' or 'ssm'"):
        debug_cfg(layer_kinds=("full", "conv"))
