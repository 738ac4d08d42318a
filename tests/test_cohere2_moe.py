"""The parallel block of window and full layers with sparse experts
(command-a-plus, ``model_type: cohere2_moe``) at the debug size (window 8,
chunk 4, two periods, 8 experts top-2, 2 shared), against its plain
reference (``benchmark/reference/cohere2_moe.py``, which imports nothing of
the program): the slot cache's rings, the expert layer's dispatch, the
shares of an expert-parallel deployment, and what the engine refuses.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import cohere2_moe as ref
from building_llm_from_scratch_tpu.configs import (
    UNSUPPORTED,
    get_config,
    refuse_unsupported,
)
from building_llm_from_scratch_tpu.generate import generate
from building_llm_from_scratch_tpu.models import moe
from building_llm_from_scratch_tpu.models import transformer as tf
from building_llm_from_scratch_tpu.obs.metrics import get_metrics
from building_llm_from_scratch_tpu.serving import (
    DecodeEngine,
    KVCachePolicy,
    SamplingParams,
)

CHUNK = 4
CHUNKED = KVCachePolicy(prefill_chunk=CHUNK)
GREEDY = dict(temperature=0.0, ignore_eos=True)


def debug_cfg(**kw):
    return get_config("command_a_plus", "218B", debug=True,
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


def through_the_cache(cfg, params, seq, n_prompt, S=3, slot=1):
    """Logits at every position from ``n_prompt - 1`` on, as serving makes
    them: chunked prefill into a slot's rings, then one decode tick a
    token, other rows idle at length 0."""
    cache = tf.init_slot_cache(cfg, S, cfg.context_length, policy=CHUNKED)
    chunk = jax.jit(lambda c, t, s, n: tf.prefill_chunk_into_slot(
        params, cfg, t, s, n, jnp.int32(slot), c), donate_argnums=0)
    step = jax.jit(lambda c, t, l: tf.decode_slots(
        params, cfg, t, l, c, live=jnp.arange(S) == slot), donate_argnums=0)
    for lo in range(0, n_prompt, CHUNK):
        piece = np.zeros((1, CHUNK), np.int32)
        hi = min(lo + CHUNK, n_prompt)
        piece[0, :hi - lo] = seq[lo:hi]
        logits, cache = chunk(cache, piece, jnp.int32(lo),
                              jnp.int32(n_prompt))
    out = [logits]
    for t in range(n_prompt, len(seq)):
        toks = np.zeros((S, 1), np.int32)
        toks[slot] = seq[t]
        lengths = np.zeros((S,), np.int32)
        lengths[slot] = t
        logits, cache = step(cache, toks, lengths)
        out.append(logits[slot])
    return jnp.stack(out), cache


# -- (a) chunked prefill then decode through rings, against one pass --------

def test_cached_path_matches_reference_past_the_ring(model):
    """37 prompt tokens and 20 decoded: the ring of 12 wraps four times.
    Float32 against float32 agrees to rounding; the reference computed in
    bfloat16 is a hundred times further off, so the tolerance tells them
    apart."""
    cfg, params, m = model
    seq = np.asarray(tokens_of(cfg, 57)[0])
    with jax.default_matmul_precision("highest"):
        got, cache = through_the_cache(cfg, params, seq, 37)
        want = ref.logits_fn(params, m, seq[None])[0, 36:]
        low = ref.logits_fn(params, m, seq[None], precision="bf16")[0, 36:]
    assert [k.shape[2] for k in cache["k"]] == [12, 12, 12, 64] * 2
    assert float(jnp.abs(got - want).max()) < 2e-5
    assert float(jnp.abs(low - want).max()) > 2e-3


def test_reference_in_blocks_equals_reference_whole(model):
    cfg, params, m = model
    seq = tokens_of(cfg, 48, seed=3)
    with jax.default_matmul_precision("highest"):
        whole = ref.logits_fn(params, m, seq)
        blocked = ref.logits_fn(params, m, seq, block_rows=8, piece_rows=16)
    assert float(jnp.abs(whole - blocked).max()) < 1e-5


# -- (b) the uncached side: forward_hidden and its gradient -----------------

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
    """``generate()``'s cache is as long as the sequence for every layer: a
    window layer there is masked, not a ring."""
    cfg, params, m = model
    prompt = np.asarray(tokens_of(cfg, 21, seed=5))
    out = generate(params, cfg, prompt, max_new_tokens=30, temperature=0.0,
                   eos_id=None, rng=jax.random.PRNGKey(0))
    seq = np.asarray(out[0])
    want = np.asarray(jnp.argmax(ref.logits_fn(params, m, seq[None, :-1])[0],
                                 -1))[20:]
    assert seq.shape == (51,) and (seq[21:] == want).all()


# -- (c) the shares of an expert-parallel deployment add up -----------------

def test_shares_of_disjoint_held_sets_add_up_to_the_uncut_layer(model):
    """What every chip computes alike (attention, shared experts) counted
    once, plus the routed part of each chip's held set, is the uncut
    reference's layer."""
    cfg, params, m = model
    p0 = jax.tree_util.tree_map(lambda a: a[0], params["blocks"])
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 24, cfg.emb_dim))
    with jax.default_matmul_precision("highest"):
        want, _ = ref._layer(m, "sliding", "float32", params["blocks"], 0,
                             x[0], 24, 24)
        n = tf._norm(cfg, p0["norm1"], x)
        shared = moe._shared(p0["moe"]["shared"], n[0])
        total = None
        for held in ((0, 3, 6), (1, 2), (4, 5, 7)):
            cut = cfg.replace(experts_held=held)
            p_cut = dict(p0, moe=dict(p0["moe"], experts=jax.tree_util.tree_map(
                lambda a: a[jnp.asarray(held)], p0["moe"]["experts"])))
            full = tf._block(cut, p_cut, x, tf._rope_tables(cfg), None,
                             None, True, kind="sliding")
            routed = moe.moe_ffn(cut, p_cut["moe"], n)[0][0] - shared
            once = full[0] - x[0] - routed        # attention + shared experts
            total = x[0] + once if total is None else total
            total = total + routed
    assert float(jnp.abs(total - want).max()) < 2e-5


# -- (d) dispatch: nothing dropped, nothing computed for nobody -------------

@pytest.mark.parametrize("rows", [5, 16, 29])
def test_skewed_router_drops_no_row_and_skips_unchosen_experts(
        model, monkeypatch, rows):
    """Every row picks experts 0 and 1 (the worst skew); blocks of 8 rows,
    so 29 rows are four blocks with the last part-filled. Each row gets
    exactly its two experts' outputs, the counter says who computed what,
    and the experts no row chose are not computed at all: their weights
    are NaN here and nothing of it reaches the output."""
    cfg, params, _ = model
    monkeypatch.setattr(moe, "ROW_BLOCK", 8)
    p = jax.tree_util.tree_map(lambda a: a[0],
                               params["blocks"]["moe"]["experts"])
    x = jax.random.normal(jax.random.PRNGKey(3), (rows, cfg.emb_dim))
    ids = jnp.tile(jnp.asarray([[1, 0]]), (rows, 1))
    w = jnp.tile(jnp.asarray([[0.75, 0.25]]), (rows, 1))
    live = jnp.arange(rows) != 2
    want = sum(wt * moe._expert(x, *(p[k][e] for k in ("gate", "up", "down")))
               for e, wt in ((1, 0.75), (0, 0.25))) * live[:, None]
    poisoned = {k: v.at[2:].set(jnp.nan) for k, v in p.items()}
    got, counts = jax.jit(lambda p: moe._routed(cfg, p, x, ids, w, live))(
        poisoned)
    assert counts.tolist() == [rows - 1, rows - 1, 0, 0, 0, 0, 0, 0]
    assert bool(jnp.isfinite(got).all())
    assert float(jnp.abs(got - want).max()) < 1e-5


def test_engine_counts_expert_rows_of_live_rows_only(model):
    cfg, params, _ = model
    eng = DecodeEngine(cfg, params, None, n_slots=3, kv_policy=CHUNKED)
    req = eng.submit(np.asarray(tokens_of(cfg, 9)[0]),
                     SamplingParams(max_new_tokens=4, **GREEDY))
    eng.run_until_idle()
    assert req.finish_reason == "length"
    ticks = [t for t in get_metrics().recent("tick") if "expert_rows" in t]
    last = ticks[-1]
    # one live row, top-2, eight layers: 16 rows over the held experts, and
    # the two idle slots' rows count for none
    assert last["rows"] == 1 and sum(last["expert_rows"]) == 16
    assert 2 <= last["experts_touched"] <= 16
    # 6 window layers of min(len, 8) positions and 2 full ones of len
    n = 9 + 3
    assert last["kv_positions"] == 6 * 8 + 2 * n


# -- (e) the cache's lengths, its bytes, the ledger --------------------------

def test_ring_and_full_buffers_have_the_lengths_the_policy_states(model):
    cfg, params, _ = model
    S = 3
    eng = DecodeEngine(cfg, params, None, n_slots=S, kv_policy=CHUNKED)
    lengths = CHUNKED.layer_lengths(cfg, cfg.context_length)
    assert lengths == [12, 12, 12, 64] * 2
    assert [k.shape for k in eng.cache["k"]] == [
        (S, cfg.n_kv_groups, n, cfg.head_dim) for n in lengths]
    bps = CHUNKED.bytes_per_slot(cfg, cfg.context_length)
    per_pos = 2 * cfg.n_kv_groups * cfg.head_dim * 4
    assert bps["kv_bytes"] == sum(lengths) * per_pos
    assert sum(a.nbytes for kv in ("k", "v") for a in eng.cache[kv]) \
        == S * bps["kv_bytes"]
    snap = eng.memory_ledger.observe()
    assert snap["slot_kv"] == S * bps["kv_bytes"]
    assert eng.memory_ledger.n_drift_events == 0
    assert eng.layout()["kv_positions"] == {"full": 64, "ring": 12}
    assert eng.layout()["experts"]["held"] == list(range(8))
    # monolithic prefill writes a whole prompt at once: no ring then
    assert KVCachePolicy().layer_lengths(cfg, 64) == [64] * 8


def test_engine_holds_the_block_weights_once(model):
    cfg, params, _ = model
    eng = DecodeEngine(cfg, params, None, n_slots=2, kv_policy=CHUNKED)
    held = {id(a) for a in jax.tree_util.tree_leaves(eng._weights)}
    assert held == {id(a) for a in jax.tree_util.tree_leaves(params)}


# -- (f) a slot reused after a longer request reads nothing of it ------------

@pytest.mark.parametrize("policy", [CHUNKED, KVCachePolicy()],
                         ids=["rings", "monolithic"])
def test_engine_tokens_match_reference_and_slot_reuse_is_clean(model, policy):
    """One slot, a long request (its rings wrap and fill) and then a short
    one in the same slot: each request's greedy tokens are the reference's
    own argmax over its sequence alone."""
    cfg, params, m = model
    eng = DecodeEngine(cfg, params, None, n_slots=1, kv_policy=policy,
                       max_queue=8)
    prompts = [np.asarray(tokens_of(cfg, n, seed=s)[0])
               for n, s in ((38, 11), (5, 12), (13, 13))]
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


def test_chunk_kernel_path_equals_the_materialised_path(monkeypatch):
    """The chunk program's attention on the live-block kernel (the rule told
    it is on a TPU; the kernel interprets on the CPU it really is on) at a
    ``head_dim`` of 128, window 128 and chunks of 128: a 450-token prompt
    (its fourth chunk carries pads, and starts past window + chunk: the
    rings of 256 have wrapped) leaves the logits and every layer's buffers
    that the materialised path leaves; a second, short request in the same
    slot reads nothing of the first; the engine names its path and the tick
    record counts the live key blocks where the other path counts whole
    buffers."""
    import functools

    from building_llm_from_scratch_tpu.serving import engine as engine_mod

    C, window, T, n_prompt = 128, 128, 1024, 450
    cfg = debug_cfg(attn_head_dim=128, sliding_window=window, n_layers=4,
                    context_length=T)
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    policy = KVCachePolicy(prefill_chunk=C)
    seq = np.asarray(tokens_of(cfg, n_prompt)[0])

    def prefill():
        cache = tf.init_slot_cache(cfg, 2, T, policy=policy)
        chunk = jax.jit(lambda c, t, s: tf.prefill_chunk_into_slot(
            params, cfg, t, s, jnp.int32(n_prompt), jnp.int32(1), c))
        for lo in range(0, n_prompt, C):
            piece = np.zeros((1, C), np.int32)
            piece[0, :min(C, n_prompt - lo)] = seq[lo:lo + C]
            logits, cache = chunk(cache, piece, jnp.int32(lo))
        return logits, cache

    def serve():
        eng = DecodeEngine(cfg, params, None, n_slots=1, kv_policy=policy,
                           max_len=T)
        reqs = [eng.submit(p, SamplingParams(max_new_tokens=3, **GREEDY))
                for p in (seq, seq[:140])]
        eng.run_until_idle()
        chunks = [t["chunk_kv_touched"]
                  for t in get_metrics().recent("tick") if t.get("chunks")]
        assert eng.stats()["chunk_attention"] == eng.chunk_attention
        return (eng.chunk_attention, [r.output_ids for r in reqs],
                chunks[-6:])

    want_logits, want_cache = prefill()
    want = serve()
    on_tpu = functools.partial(tf.chunk_attention_path, backend="tpu")
    monkeypatch.setattr(tf, "chunk_attention_path", on_tpu)
    monkeypatch.setattr(engine_mod, "chunk_attention_path", on_tpu)
    got_logits, got_cache = prefill()
    got = serve()
    assert [k.shape[2] for k in got_cache["k"]] == [256, 256, 256, T]
    assert float(jnp.abs(got_logits - want_logits).max()) < 1e-4
    for name in ("k", "v"):
        for a, b in zip(got_cache[name], want_cache[name]):
            assert float(jnp.abs(a - b).max()) < 1e-4
    assert (want[0], got[0]) == ("materialised", "live_blocks")
    assert got[1] == want[1]
    # the long prompt's four chunks and the short one's two, three rings
    # and a full layer each: whole buffers; or what is written so far (a
    # ring: at most 256) less the chunk's own blocks past the prompt
    assert want[2] == [3 * 256 + T] * 6
    assert got[2] == [4 * 128, 4 * 256, 3 * 256 + 384, 3 * 256 + 512,
                      4 * 128, 4 * 256]


def test_coresident_requests_in_bf16_agree_with_the_reference(model):
    """The cell's own arithmetic (bfloat16 weights and cache, float32
    router, three of the eight experts held) with three requests sharing
    ticks: at positions without a
    router near-tie the served token lies within a bfloat16 rounding of the
    reference's best."""
    cfg, params, _ = model
    held = (1, 4, 6)
    cfg16 = cfg.replace(dtype="bf16", experts_held=held)
    p16 = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)
    p16["blocks"]["moe"]["experts"] = jax.tree_util.tree_map(
        lambda a: a[:, jnp.asarray(held)], p16["blocks"]["moe"]["experts"])
    eng = DecodeEngine(cfg16, p16, None, n_slots=3, kv_policy=CHUNKED)
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
    assert out["tokens"] == 72 and out["compared_share"] >= 0.5
    assert out["widest_gap"] < 0.02


def test_repeated_first_layer_tie_is_read_under_both_resolutions(
        model, capsys, monkeypatch):
    """A token whose first-layer router ties (a thousandth of the rms logit
    between ranks k and k+1) stands 14 times in a prompt, and the "program"
    is the reference resolving that tie the other way at every one of them:
    read under the reference's own resolution alone, positions with no tie of
    their own differ; read under both, the other one fits to rounding and is
    the one named."""
    cfg, params, m = model
    A, PAD, k = 5, 64, cfg.n_experts_per_tok
    params = jax.tree_util.tree_map(lambda a: a, params)
    moe_p = dict(params["blocks"]["moe"])
    # the first layer's experts loud enough that which one ran shows
    moe_p["experts"] = dict(moe_p["experts"],
                            down=moe_p["experts"]["down"].at[0].multiply(30.0))
    n0 = ref._layernorm(params["tok_emb"]["weight"][A],
                        params["blocks"]["norm1"]["scale"][0],
                        m["layernorm_eps"])
    logits = n0 @ moe_p["router"][0]
    order = jnp.argsort(-logits)
    lo, hi = int(order[k]), int(order[k - 1])
    lift = float(logits[hi] - logits[lo]) - 0.001 * float(
        jnp.sqrt(jnp.mean(logits ** 2)))
    moe_p["router"] = moe_p["router"].at[0, :, lo].add(
        lift * n0 / jnp.dot(n0, n0))
    params["blocks"] = dict(params["blocks"], moe=moe_p)

    rng = np.random.default_rng(2)
    prompt = rng.integers(0, cfg.vocab_size, 36).astype(np.int32)
    prompt[rng.choice(36, 14, replace=False)] = A
    other = jax.jit(lambda t, s: ref.hidden_fn(params, m, t, swap_first=s)[0])
    seq = list(prompt)
    for _ in range(20):
        padded = np.zeros((PAD,), np.int32)
        padded[:len(seq)] = seq
        h = other(jnp.asarray(padded), jnp.asarray(padded == A))
        seq.append(int(jnp.argmax(h[len(seq) - 1]
                                  @ params["tok_emb"]["weight"].T)))
    served = np.asarray(seq[36:], np.int32)

    def read():
        capsys.readouterr()
        out = ref.served_token_gaps(params, m, [(prompt, served)], pad_to=PAD)
        line = json.loads(capsys.readouterr().out.splitlines()[0])
        return out, line["reference_compared"]["repeated_first_layer_ties"]

    out, ties = read()
    assert out["widest_gap"] < 1e-4 and out["compared_share"] >= 0.5
    assert ties[0]["tokens"] == [[A, 14]] and ties[0]["swapped"] == [True]
    assert ties[0]["widest_gap_by_resolution"][0] > 0.05
    monkeypatch.setattr(ref, "COHERENT_REPEATS", 10 ** 9)
    out, ties = read()
    assert out["widest_gap"] > 0.05 and ties == []


# -- what is refused, at construction, in one sentence -----------------------

@pytest.mark.parametrize("kw,match", [
    (dict(kv_policy=KVCachePolicy(prefill_chunk=4, paged=True,
                                   page_tokens=4)), "paged"),
    (dict(kv_policy=KVCachePolicy(prefill_chunk=4, prefix_cache=True)),
     "prefix"),
    (dict(kv_policy=KVCachePolicy(kv_quant="int8")), "int8"),
    (dict(spec_k=2), "spec_k"),
    (dict(spec_k=2, no_rings=True), "rejected drafts to experts"),
    (dict(kv_policy=KVCachePolicy(prefill_chunk=3)), "whole prefill chunks"),
    (dict(adapters=object()), "LoRA"),
])
def test_engine_refuses_what_rings_and_experts_do_not_support(model, kw,
                                                              match):
    cfg, params, _ = model
    kw = dict(kw)
    if kw.pop("no_rings", False):          # the experts alone refuse it too
        cfg = cfg.replace(layer_kinds=(), sliding_window=0)
    with pytest.raises(ValueError, match=match):
        DecodeEngine(cfg, params, None, n_slots=2, **kw)


@pytest.mark.parametrize("flags,match", [
    ((), "no tokenizer is registered"),
    (("--byte_tokenizer", "--load_weights"), "no checkpoint converter"),
    (("--byte_tokenizer", "--use_lora"), "LoRA"),
    (("--byte_tokenizer", "--run_type", "multi_chip", "--shard_mode", "pp"),
     "pipeline stage"),
    (("--byte_tokenizer", "--run_type", "multi_chip", "--shard_mode", "tp",
      "--tp", "2"), "tensor-parallel"),
    (("--byte_tokenizer", "--run_type", "multi_chip", "--sp", "2"),
     "sequence split"),
])
def test_flags_refuse_what_the_config_does_not_support(tmp_path, flags,
                                                       match):
    from building_llm_from_scratch_tpu.args import get_args

    base = ["--data_dir", str(tmp_path), "--model", "command_a_plus",
            "--num_params", "218B", "--debug"]
    with pytest.raises(ValueError, match=match):
        get_args(base + list(flags))
    assert get_args(base + ["--byte_tokenizer"]).model == "command_a_plus"


def test_refusals_follow_the_config_not_its_name(model):
    """The flags' checks and the engine ask one list, by what the config is:
    another name changes nothing, a dense model is refused nothing, and a
    model with window layers alone keeps what only experts refuse."""
    cfg, _, _ = model
    everything = {name: True for name, _, _ in UNSUPPORTED}
    for name in everything:
        with pytest.raises(ValueError, match="another-name"):
            refuse_unsupported(cfg.replace(name="another-name"),
                               **{name: True})
    refuse_unsupported(get_config("GPT2", "124M"), **everything)
    windows_only = cfg.replace(n_routed_experts=0, n_experts_per_tok=0,
                               n_shared_experts=0)
    refuse_unsupported(windows_only, lora=True)
    with pytest.raises(ValueError, match="window layers\\)"):
        refuse_unsupported(windows_only, paged=True)
    with pytest.raises(TypeError, match="no such feature"):
        refuse_unsupported(cfg, pagd=True)


def test_published_configuration_counts_its_parameters():
    cfg = get_config("command_a_plus", "218B", target_context_length=None)
    assert cfg.head_dim == 128 and cfg.rope_base == 50_000.0
    assert cfg.num_params() == 218_254_938_112
    assert cfg.num_params(active=True) == 24_981_409_792
    chip = cfg.replace(n_layers=4, vocab_size=32768, context_length=20480,
                       experts_held=tuple(range(8)))
    assert chip.num_params() == 3_122_679_808
    per_slot = KVCachePolicy(prefill_chunk=512).bytes_per_slot(chip, 20480)
    assert per_slot["kv_bytes"] == 140_509_184
