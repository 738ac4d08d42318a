"""Model-level tests: shapes, determinism, remat, KV-cache parity, configs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from building_llm_from_scratch_tpu.configs import (
    ModelConfig,
    get_config,
    get_config_gpt2,
    get_config_llama,
    rescale_theta,
)
from building_llm_from_scratch_tpu.models import (
    build_model,
    forward,
    forward_with_cache,
    init_cache,
    init_params,
)


def tiny_gpt2(**kw):
    return get_config("GPT2", "124M", debug=True, **kw)


def tiny_llama(**kw):
    return get_config("llama3_2", "1B", debug=True, **kw)


@pytest.mark.parametrize("cfg_fn", [tiny_gpt2, tiny_llama])
def test_forward_shapes(cfg_fn, rng_key):
    cfg = cfg_fn()
    params = init_params(cfg, rng_key)
    tokens = jnp.zeros((2, cfg.context_length), jnp.int32)
    logits = forward(params, cfg, tokens)
    assert logits.shape == (2, cfg.context_length, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert np.isfinite(np.asarray(logits)).all()


def test_remat_matches_plain(rng_key):
    cfg = tiny_llama()
    params = init_params(cfg, rng_key)
    tokens = jax.random.randint(rng_key, (2, 8), 0, cfg.vocab_size)
    plain = forward(params, cfg, tokens)
    ckpt = forward(params, cfg.replace(use_actv_ckpt=True), tokens)
    np.testing.assert_allclose(np.asarray(plain), np.asarray(ckpt),
                               rtol=1e-5, atol=1e-5)


def test_remat_gradients_match(rng_key):
    cfg = tiny_llama()
    params = init_params(cfg, rng_key)
    tokens = jax.random.randint(rng_key, (2, 8), 0, cfg.vocab_size)

    def loss(p, c):
        return jnp.mean(forward(p, c, tokens) ** 2)

    g1 = jax.grad(loss)(params, cfg)
    g2 = jax.grad(loss)(params, cfg.replace(use_actv_ckpt=True))
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                                rtol=1e-4, atol=1e-4), g1, g2)


def test_dropout_deterministic_flag(rng_key):
    cfg = tiny_gpt2()
    assert cfg.drop_rate > 0
    params = init_params(cfg, rng_key)
    tokens = jax.random.randint(rng_key, (2, 8), 0, cfg.vocab_size)
    a = forward(params, cfg, tokens)
    b = forward(params, cfg, tokens)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # training mode with different rngs differs
    r1 = forward(params, cfg, tokens, rng=jax.random.PRNGKey(1),
                 deterministic=False)
    r2 = forward(params, cfg, tokens, rng=jax.random.PRNGKey(2),
                 deterministic=False)
    assert not np.allclose(np.asarray(r1), np.asarray(r2))


def test_kv_cache_decode_matches_full_forward(rng_key):
    """Prefill + per-token decode must reproduce the uncached forward —
    the correctness condition the reference sidesteps by never caching
    (generate.py:36-45)."""
    cfg = tiny_llama()
    params = init_params(cfg, rng_key)
    T = 12
    tokens = jax.random.randint(jax.random.PRNGKey(7), (2, T), 0,
                                cfg.vocab_size)
    full = forward(params, cfg, tokens)

    cache = init_cache(cfg, batch_size=2, max_length=16)
    # prefill on the first 6 tokens, then decode 1-by-1
    logits_p, cache = forward_with_cache(params, cfg, tokens[:, :6], cache)
    outs = [logits_p]
    for t in range(6, T):
        step_logits, cache = forward_with_cache(params, cfg,
                                                tokens[:, t:t + 1], cache)
        outs.append(step_logits)
    cached = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(full), np.asarray(cached),
                               rtol=2e-3, atol=2e-3)


def test_gpt2_learned_positions_used(rng_key):
    cfg = tiny_gpt2()
    params = init_params(cfg, rng_key)
    # same token at different positions must produce different logits
    tokens = jnp.full((1, 4), 7, jnp.int32)
    logits = forward(params, cfg, tokens)
    assert not np.allclose(np.asarray(logits[0, 0]), np.asarray(logits[0, 3]))


def test_param_count_formula_matches_tree(rng_key):
    from building_llm_from_scratch_tpu.utils.memory import count_params

    for cfg in [tiny_gpt2(), tiny_llama(), tiny_gpt2(qkv_bias=True)]:
        params = init_params(cfg, rng_key)
        assert count_params(params) == cfg.num_params()


def test_gpt2_config_registry():
    cfg = get_config_gpt2("355M")
    assert (cfg.emb_dim, cfg.n_heads, cfg.n_layers) == (1024, 16, 24)
    assert cfg.vocab_size == 50257 and cfg.context_length == 1024
    with pytest.raises(ValueError):
        get_config_gpt2("999M")


def test_llama_config_clamp_and_theta_rescale():
    # default: reference behavior — clamp to 1024 w/ linear theta rescale
    cfg = get_config_llama("8B", "llama3")
    assert cfg.context_length == 1024
    assert np.isclose(cfg.rope_base, rescale_theta(500_000.0, 8192, 1024))
    # parameterized escape hatch: keep native context
    cfg_native = get_config_llama("8B", "llama3", target_context_length=None)
    assert cfg_native.context_length == 8192
    assert cfg_native.rope_base == 500_000.0
    # registry must NOT be mutated (reference defect §2.3 #5)
    again = get_config_llama("8B", "llama3")
    assert np.isclose(again.rope_base, cfg.rope_base)


def test_llama2_has_eos():
    # reference defect §2.3 #4: llama2 config lacked eos; ours must not
    cfg = get_config_llama("7B", "llama2")
    assert cfg.eos_id == 2 and cfg.eos_text == "</s>"


def test_build_model_factory():
    cfg, params = build_model("GPT2", "124M", debug=True)
    assert cfg.n_layers == 2
    assert "pos_emb" in params
    cfg2, params2 = build_model("llama3_2", "1B", debug=True)
    assert "pos_emb" not in params2
    assert "gate" in params2["blocks"]["mlp"]


def test_gpt2_124M_param_count_full_size():
    # GPT-2 124M with untied head: ~163M total params (124M backbone +
    # 38.6M untied head), matching the reference's GPTModel layout.
    cfg = get_config_gpt2("124M")
    n = cfg.num_params()
    assert 160e6 < n < 170e6


def test_bucketed_generate_greedy_matches_dense_loop(rng_key):
    """generate() pads the prompt to a shape bucket and resets the cache
    length to the REAL prompt length — greedy output must equal the naive
    full-forward re-run per token (reference semantics, generate.py:36-73)
    for prompt lengths off the bucket boundary."""
    from building_llm_from_scratch_tpu.generate import generate

    cfg = tiny_llama()
    params = init_params(cfg, rng_key)
    for Tp in (5, 9):
        prompt = np.asarray(jax.random.randint(
            jax.random.PRNGKey(Tp), (2, Tp), 0, cfg.vocab_size), np.int32)
        out = generate(params, cfg, prompt, max_new_tokens=7,
                       context_size=cfg.context_length)
        ids = prompt.copy()
        for _ in range(7):
            logits = forward(params, cfg, jnp.asarray(ids))[:, -1]
            nxt = np.asarray(jnp.argmax(logits, axis=-1))
            ids = np.concatenate([ids, nxt[:, None].astype(ids.dtype)], 1)
        np.testing.assert_array_equal(np.asarray(out), ids)


def test_generate_eos_stop_quirk(rng_key):
    """All-rows-eos stops WITHOUT appending the triggering token
    (reference generate.py:68-73)."""
    from building_llm_from_scratch_tpu.generate import generate

    cfg = tiny_llama()
    params = init_params(cfg, rng_key)
    # two IDENTICAL rows: greedy emits the same first token on both by
    # construction, so the all-rows-eos condition is guaranteed to trigger
    row = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (1, 6), 0, cfg.vocab_size), np.int32)
    prompt = np.concatenate([row, row], axis=0)
    probe = generate(params, cfg, prompt, max_new_tokens=1,
                     context_size=cfg.context_length)
    first = np.asarray(probe)[:, -1]
    assert first[0] == first[1]
    out = generate(params, cfg, prompt, max_new_tokens=5,
                   context_size=cfg.context_length,
                   eos_id=int(first[0]))
    assert out.shape[1] == prompt.shape[1]         # nothing appended
