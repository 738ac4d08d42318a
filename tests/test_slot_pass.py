"""ONE block loop (``models/transformer.py`` ``_slot_pass``) serves every
cached program and every block form: each program fills a slot its own way
and the logits it serves equal the one-shot ``forward`` at the same
positions. Float32 against float32 under the highest matmul precision
agrees to rounding (the tolerance ``tests/test_cohere2_moe.py`` holds its
cached path to), so a wrong mask, position, residual or head shows.

Before the programs shared a body, verify and the three paged ones had
their own copy of the block, which knew the serial residual, the ``head``
leaf and the dense MLP only: their parallel-block, tied and expert cases
died with a ``KeyError`` at trace time.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from building_llm_from_scratch_tpu.configs import ModelConfig
from building_llm_from_scratch_tpu.models import transformer as tf
from building_llm_from_scratch_tpu.serving.kvcache import KVCachePolicy

S, SLOT, CTX = 3, 1, 32            # slots, the one in use, positions a slot
N_PROMPT, N_SEQ = 11, 19           # prompt tokens; prompt + decoded
CHUNK, PAGE, K = 8, 8, 3           # prefill chunk, page, drafted tokens

GPT2 = dict(name="slot-pass", vocab_size=96, context_length=CTX, emb_dim=32,
            n_heads=4, n_layers=2, hidden_dim=64, n_kv_groups=4,
            norm="layernorm", positional="learned", activation="gelu",
            attn_out_bias=True, mlp_bias=True, norm_bias=True, drop_rate=0.0,
            eos_id=1)
BLOCKS = {
    "gpt2_serial": GPT2,
    "parallel_block": dict(GPT2, parallel_block=True),
    "tied_embeddings": dict(GPT2, tie_embeddings=True),
    "gqa_rope_swiglu": dict(GPT2, n_kv_groups=2, norm="rmsnorm",
                            positional="rope", activation="swiglu",
                            attn_out_bias=False, mlp_bias=False,
                            norm_bias=False),
    # sparse experts in a parallel, tied block, every layer 'full': what the
    # engine may serve paged (rings it refuses: configs.UNSUPPORTED)
    "experts": dict(GPT2, parallel_block=True, tie_embeddings=True,
                    activation="swiglu", mlp_bias=False, n_routed_experts=8,
                    n_experts_per_tok=2, n_shared_experts=2),
    # a recurrent state beside keys and values: a gated full layer, then a
    # gated-delta-rule linear one (no positions), a dense MLP. A verify tick
    # and the paged pool refuse it (``REFUSED``)
    "linear_state": dict(GPT2, norm="rmsnorm", positional="none",
                         activation="swiglu", attn_out_bias=False,
                         mlp_bias=False, norm_bias=False,
                         layer_kinds=("full", "linear"), attn_out_gate=True,
                         linear_heads=4, linear_head_dim=8,
                         linear_gate_rank=4, linear_neg_eigval=True),
    # the other recurrent state: a selective-state-space layer (no
    # positions), then a multi-query full one, a tied head. Refused as above
    "ssm_state": dict(GPT2, norm="rmsnorm", positional="none",
                      activation="swiglu", attn_out_bias=False,
                      mlp_bias=False, norm_bias=False, tie_embeddings=True,
                      n_kv_groups=1, layer_kinds=("ssm", "full"),
                      ssm_inner=64, ssm_state=8, ssm_dt_rank=4),
}
#: what a block form does not run through, and the sentence that says so
_NO_STATE = {"verify": "no way back from a state",
             "paged_chunks": "a page holds positions",
             "paged_decode": "a page holds positions",
             "paged_verify": "a page holds positions"}
REFUSED = {"linear_state": _NO_STATE, "ssm_state": _NO_STATE}
PROGRAMS = ("prefill_bucket", "prefill_chunks", "decode", "verify",
            "paged_chunks", "paged_decode", "paged_verify")


@pytest.fixture(scope="module", params=list(BLOCKS))
def model(request):
    cfg = ModelConfig(**BLOCKS[request.param])
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    if cfg.is_moe:
        # a router that spreads its scores: at the init's 0.02 every margin
        # between experts is a near-tie
        params["blocks"]["moe"]["router"] = (
            40.0 * params["blocks"]["moe"]["router"])
    seq = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (N_SEQ,), 0,
                                        cfg.vocab_size))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(tf.forward(params, cfg, seq[None])[0])
    return cfg, params, seq, want


def _serve(cfg, params, seq, program):
    """{position: logits served there} by ``program``, the slot filled the
    way its engine would fill it; other rows idle at length 0."""
    paged = program.startswith("paged")
    policy = (KVCachePolicy(paged=True, page_tokens=PAGE,
                            prefill_chunk=CHUNK) if paged else None)
    cache = tf.init_slot_cache(cfg, S, CTX, policy=policy)
    kw = {}
    if paged:
        # the slot's pages in an order of their own; every other entry the
        # trash page 0, where idle rows' appends land
        table = np.zeros((S, CTX // PAGE), np.int32)
        table[SLOT] = 1 + np.random.default_rng(0).permutation(CTX // PAGE)
        kw = dict(page_table=jnp.asarray(table), cache_len=CTX)
    live = jnp.arange(S) == SLOT
    served = {}
    if program == "prefill_bucket":
        bucket = np.zeros((1, 16), np.int32)
        bucket[0, :N_PROMPT] = seq[:N_PROMPT]
        served[N_PROMPT - 1], cache = tf.prefill_into_slot(
            params, cfg, bucket, jnp.int32(N_PROMPT), jnp.int32(SLOT), cache)
        return served
    for lo in range(0, N_PROMPT, CHUNK):
        piece = np.zeros((1, CHUNK), np.int32)
        hi = min(lo + CHUNK, N_PROMPT)
        piece[0, :hi - lo] = seq[lo:hi]
        logits, cache = tf.prefill_chunk_into_slot(
            params, cfg, piece, jnp.int32(lo), jnp.int32(N_PROMPT),
            jnp.int32(SLOT), cache, **kw)
    served[N_PROMPT - 1] = logits
    if program.endswith("chunks"):
        return served
    lengths = np.zeros((S,), np.int32)
    if program.endswith("verify"):
        # the slot's last token and K drafts, all of them right: every one
        # of the K + 1 positions is a true next-token distribution
        toks = np.zeros((S, K + 1), np.int32)
        toks[SLOT] = seq[N_PROMPT:N_PROMPT + K + 1]
        lengths[SLOT] = N_PROMPT
        logits, cache = tf.verify_slots(params, cfg, toks, lengths, cache,
                                        live=live, **kw)
        for j in range(K + 1):
            served[N_PROMPT + j] = logits[SLOT, j]
        return served
    for t in range(N_PROMPT, N_SEQ):
        toks = np.zeros((S, 1), np.int32)
        toks[SLOT] = seq[t]
        lengths[SLOT] = t
        rows = []
        logits, cache = tf.decode_slots(params, cfg, toks, lengths, cache,
                                        live=live, expert_rows=rows, **kw)
        served[t] = logits[SLOT]
        # a sparse layer hands back its rows per expert: the live row's
        # top-2, no idle row's
        assert [int(r.sum()) for r in rows] == (
            [cfg.n_experts_per_tok] * cfg.n_layers if cfg.is_moe else [])
    return served


@pytest.mark.parametrize("program", PROGRAMS)
def test_every_program_serves_the_forward_logits(model, program):
    cfg, params, seq, want = model
    refusal = next((why[program] for form, why in REFUSED.items()
                    if cfg == ModelConfig(**BLOCKS[form])
                    and program in why), None)
    if refusal is not None:
        with pytest.raises(ValueError, match=refusal):
            _serve(cfg, params, seq, program)
        return
    with jax.default_matmul_precision("highest"):
        served = _serve(cfg, params, seq, program)
    assert len(served) == {"decode": 1 + N_SEQ - N_PROMPT,
                           "verify": 2 + K}.get(program.split("_")[-1], 1)
    for t, got in served.items():
        assert got.shape == (cfg.vocab_size,)
        err = float(np.abs(np.asarray(got) - want[t]).max())
        assert err < 2e-5, (program, t, err)


@pytest.mark.parametrize("program", ["verify", "paged_chunks",
                                     "paged_decode"])
def test_layouts_without_a_ring_refuse_a_sliding_layer(program):
    """A verify tick and the paged pool have no ring: a 'sliding' layer
    there is a sentence at trace time (the engine refuses the pair first,
    by ``configs.UNSUPPORTED``), never a full-attention layer in silence."""
    cfg = ModelConfig(**dict(GPT2, layer_kinds=("sliding", "full"),
                             sliding_window=8))
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    seq = np.arange(N_SEQ) % cfg.vocab_size
    with pytest.raises(ValueError, match="has no ring for a 'sliding'"):
        _serve(cfg, params, seq, program)


def test_engine_serves_experts_through_pages():
    """What the one body newly lets the ENGINE run: a sparse model (every
    layer 'full') on the paged pool. Its greedy tokens are the slot cache's
    own, and a tick still hands back the live rows' experts."""
    from building_llm_from_scratch_tpu.obs.metrics import get_metrics
    from building_llm_from_scratch_tpu.serving import (
        DecodeEngine,
        SamplingParams,
    )

    cfg = ModelConfig(**BLOCKS["experts"])
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    prompts = [np.arange(3, 3 + n) % cfg.vocab_size for n in (11, 5, 17)]
    out = {}
    for name, policy in (
            ("slots", KVCachePolicy(prefill_chunk=CHUNK)),
            ("pages", KVCachePolicy(paged=True, page_tokens=PAGE,
                                    prefill_chunk=CHUNK))):
        eng = DecodeEngine(cfg, params, None, n_slots=2, max_len=CTX,
                           kv_policy=policy, max_queue=8)
        reqs = [eng.submit(p, SamplingParams(max_new_tokens=6,
                                             temperature=0.0,
                                             ignore_eos=True))
                for p in prompts]
        eng.run_until_idle()
        assert all(r.finish_reason == "length" for r in reqs)
        out[name] = [list(r.output_ids) for r in reqs]
        ticks = [t for t in get_metrics().recent("tick")
                 if t.get("expert_rows")]
        assert ticks and all(
            sum(t["expert_rows"]) == t["rows"] * cfg.n_experts_per_tok
            * cfg.n_layers for t in ticks[-3:])
    assert out["pages"] == out["slots"]
    assert eng.kv_append == eng.decode_attention == "paged"
    # the chunk program reads through the table too: a gathered view of the
    # row's whole logical length, every layer
    assert eng.chunk_attention == eng.stats()["chunk_attention"] == "paged"
    chunked = [t for t in get_metrics().recent("tick") if t.get("chunks")]
    assert chunked[-1]["chunk_kv_touched"] == cfg.n_layers * eng._cache_len
