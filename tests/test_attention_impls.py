"""Attention implementation parity: flash/pallas vs the exact xla oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from building_llm_from_scratch_tpu.configs import ModelConfig
from building_llm_from_scratch_tpu.models import forward, init_params
from building_llm_from_scratch_tpu.ops.attention import causal_attention


def _qkv(B=2, T=256, Hq=4, Hkv=2, D=16, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, T, Hq, D), dtype)
    k = jax.random.normal(ks[1], (B, T, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, T, Hkv, D), dtype)
    return q, k, v


def test_flash_matches_xla_fp32():
    q, k, v = _qkv()
    want = causal_attention(q, k, v, impl="xla")
    got = causal_attention(q, k, v, impl="flash", block_q=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_flash_matches_xla_bf16():
    q, k, v = _qkv(dtype=jnp.bfloat16)
    want = np.asarray(causal_attention(q, k, v, impl="xla"), np.float32)
    got = np.asarray(causal_attention(q, k, v, impl="flash", block_q=64),
                     np.float32)
    np.testing.assert_allclose(got, want, atol=1e-2, rtol=1e-2)


def test_flash_odd_lengths_fall_to_divisor_blocks():
    q, k, v = _qkv(T=192)                       # 192 % 256 != 0
    want = causal_attention(q, k, v, impl="xla")
    got = causal_attention(q, k, v, impl="flash")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_flash_gradients_match_xla():
    q, k, v = _qkv(T=128)

    def loss(impl, q, k, v):
        out = causal_attention(q, k, v, impl=impl, block_q=32)
        return jnp.sum(out * out)

    gw = jax.grad(lambda *a: loss("xla", *a), argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(lambda *a: loss("flash", *a), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gw):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_flash_dropout_preserves_mean_and_causality():
    """Dropout path: output stays causal (position t only sees <= t) and the
    kept weights are rescaled (mean roughly preserved)."""
    q, k, v = _qkv(T=64)
    rng = jax.random.PRNGKey(3)
    out = causal_attention(q, k, v, impl="flash", block_q=16,
                           dropout_rate=0.5, dropout_rng=rng,
                           deterministic=False)
    assert np.isfinite(np.asarray(out)).all()
    # causality probe: changing future k/v must not affect position 0
    k2 = k.at[:, 32:].set(0.0)
    v2 = v.at[:, 32:].set(0.0)
    out2 = causal_attention(q, k2, v2, impl="flash", block_q=16,
                            dropout_rate=0.5, dropout_rng=rng,
                            deterministic=False)
    np.testing.assert_allclose(np.asarray(out[:, :32]),
                               np.asarray(out2[:, :32]), atol=1e-6)


def test_full_model_forward_flash_matches_xla():
    cfg = ModelConfig(
        name="t", vocab_size=128, context_length=256, emb_dim=64, n_heads=4,
        n_layers=2, hidden_dim=128, n_kv_groups=2, norm="rmsnorm",
        positional="rope", activation="swiglu", drop_rate=0.0, dtype="fp32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = np.arange(2 * 256, dtype=np.int32).reshape(2, 256) % 128
    want = np.asarray(forward(params, cfg.replace(attn_impl="xla"), toks))
    got = np.asarray(forward(params, cfg.replace(attn_impl="flash"), toks))
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


def test_auto_uses_xla_for_decode_shapes():
    """Cached decode (kv_length set) must stay on the exact xla path."""
    q, k, v = _qkv(T=8)
    out = causal_attention(q[:, :1], k, v,
                           q_positions=jnp.asarray([4]),
                           kv_length=jnp.asarray([5, 5]), impl="flash")
    want = causal_attention(q[:, :1], k, v,
                            q_positions=jnp.asarray([4]),
                            kv_length=jnp.asarray([5, 5]), impl="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=0)


@pytest.mark.needs_tpu
def test_pallas_matches_xla_on_tpu():
    q, k, v = _qkv(T=512, D=64, dtype=jnp.bfloat16)
    want = np.asarray(causal_attention(q, k, v, impl="xla"), np.float32)
    got = np.asarray(causal_attention(q, k, v, impl="pallas"), np.float32)
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)


@pytest.mark.needs_tpu
def test_pallas_gradients_match_xla_on_tpu():
    """The tuned-block pallas path must be exact in the backward too (it
    feeds real training steps when auto picks it at seq >= 2048)."""
    q, k, v = _qkv(T=2048, Hq=4, Hkv=2, D=64, dtype=jnp.bfloat16)

    def loss(impl, q, k, v):
        out = causal_attention(q, k, v, impl=impl)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    gw = jax.grad(lambda *a: loss("xla", *a), argnums=(0, 1, 2))(q, k, v)
    gp = jax.grad(lambda *a: loss("pallas", *a), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gw):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=2e-1, rtol=5e-2)


def test_auto_selection_policy():
    """auto: xla for decode/q_positions; on TPU the in-house fused kernel
    (dropout included) owns block-divisible training shapes; flash covers
    CPU and odd shapes; xla otherwise."""
    from building_llm_from_scratch_tpu.ops.attention import _resolve_impl

    on_tpu = jax.default_backend() == "tpu"
    # decode / chunked-prefill shapes pin to the exact oracle
    assert _resolve_impl("auto", 1, 64, 64, None, jnp.asarray([5]), False,
                         256) == "xla"
    assert _resolve_impl("flash", 64, 64, 64, jnp.arange(64), None, False,
                         256) == "xla"
    assert _resolve_impl("pallas", 64, 64, 64, jnp.arange(64), None, False,
                         256) == "xla"
    # training shapes: fused on TPU (with or without dropout), flash on CPU
    expect_train = "fused" if on_tpu else "flash"
    assert _resolve_impl("auto", 1024, 1024, 64, None, None, False,
                         256) == expect_train
    assert _resolve_impl("auto", 2048, 2048, 64, None, None, False,
                         256) == expect_train
    assert _resolve_impl("auto", 2048, 2048, 64, None, None, True,
                         256) == expect_train
    # short sequences stay exact
    assert _resolve_impl("auto", 128, 128, 64, None, None, False,
                         256) == "xla"
