"""Fused decode-step kernel (ops/decode_step.py) parity vs the jnp
decode path, plus the custom-VJP norm gradient checks (round 5).

The decode-step kernel runs compiled on the chip and interpreted on the
CPU; the xent kernel case needs the real chip (chip_smoke.py runs it
there); the norm gradient tests run everywhere.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

needs_tpu = pytest.mark.needs_tpu      # skipped off-chip by conftest.py


def _decode_step():
    """The fused kernel, jitted: compiled on the chip, interpreted on the
    CPU (same kernel body, so the parity cases run everywhere)."""
    from building_llm_from_scratch_tpu.ops.decode_step import (
        fused_decode_step,
    )

    return jax.jit(functools.partial(
        fused_decode_step, interpret=jax.default_backend() != "tpu"))


@pytest.mark.parametrize("B,Hq,Hkv,hd,Tmax,t", [
    (2, 12, 12, 64, 320, 5),      # GPT2-ish MHA
    (2, 32, 8, 64, 320, 17),      # GQA
    (8, 12, 12, 64, 320, 0),      # append at the very start
    (1, 32, 8, 128, 256, 100),    # large head dim
])
def test_fused_decode_step_matches_jnp_path(B, Hq, Hkv, hd, Tmax, t):
    from building_llm_from_scratch_tpu.ops.attention import decode_attention

    Tq = 1
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(ks[0], (B, Tq, Hq, hd), jnp.bfloat16)
    kn = jax.random.normal(ks[1], (B, Tq, Hkv, hd), jnp.bfloat16)
    vn = jax.random.normal(ks[2], (B, Tq, Hkv, hd), jnp.bfloat16)
    K = jax.random.normal(ks[3], (B, Hkv, Tmax, hd), jnp.bfloat16)
    V = jax.random.normal(ks[4], (B, Hkv, Tmax, hd), jnp.bfloat16)
    length = jnp.asarray(t, jnp.int32)
    positions = t + jnp.arange(Tq)

    K2 = jax.lax.dynamic_update_slice(K, kn.transpose(0, 2, 1, 3),
                                      (0, 0, t, 0))
    V2 = jax.lax.dynamic_update_slice(V, vn.transpose(0, 2, 1, 3),
                                      (0, 0, t, 0))
    ref = decode_attention(q, K2, V2, q_positions=positions,
                           kv_length=length + Tq)

    out, Ko, Vo = _decode_step()(q, kn, vn, K, V, length)
    np.testing.assert_allclose(np.asarray(Ko, np.float32),
                               np.asarray(K2, np.float32))
    np.testing.assert_allclose(np.asarray(Vo, np.float32),
                               np.asarray(V2, np.float32))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=2e-2, rtol=2e-2)


def test_fused_decode_step_per_row_lengths():
    """Per-row lengths (the serving engine's slot batch, ops/decode_step
    slot semantics): each row appends at ITS offset and attends its own
    valid prefix — must match running each row alone at a scalar length."""
    B, Hq, Hkv, hd, Tmax = 3, 12, 12, 64, 320
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    q = jax.random.normal(ks[0], (B, 1, Hq, hd), jnp.bfloat16)
    kn = jax.random.normal(ks[1], (B, 1, Hkv, hd), jnp.bfloat16)
    vn = jax.random.normal(ks[2], (B, 1, Hkv, hd), jnp.bfloat16)
    K = jax.random.normal(ks[3], (B, Hkv, Tmax, hd), jnp.bfloat16)
    V = jax.random.normal(ks[4], (B, Hkv, Tmax, hd), jnp.bfloat16)
    lengths = jnp.asarray([0, 7, 133], jnp.int32)

    out, Ko, Vo = _decode_step()(q, kn, vn, K, V, lengths)
    for b in range(B):
        ob, Kb, Vb = _decode_step()(
            q[b:b + 1], kn[b:b + 1], vn[b:b + 1], K[b:b + 1], V[b:b + 1],
            lengths[b])
        np.testing.assert_allclose(np.asarray(Ko[b:b + 1], np.float32),
                                   np.asarray(Kb, np.float32))
        np.testing.assert_allclose(np.asarray(Vo[b:b + 1], np.float32),
                                   np.asarray(Vb, np.float32))
        np.testing.assert_allclose(np.asarray(out[b:b + 1], np.float32),
                                   np.asarray(ob, np.float32),
                                   atol=2e-2, rtol=2e-2)


def test_decode_step_supports_shape_gates():
    from building_llm_from_scratch_tpu.ops.decode_step import supports_shape

    mha = dict(Hkv=12, Hq=12)
    assert supports_shape(1, 320, 64, **mha)
    assert not supports_shape(2, 320, 64, **mha)   # single-token only
    assert not supports_shape(1, 60, 64, **mha)    # Tmax must be 8-aligned
    assert not supports_shape(1, 320, 96, **mha)   # head dim lane alignment
    # every Hkv pane of a row sits in VMEM at once: the engine's GPT2-124M
    # shape fits, a 32-head 8k-token fp32 cache does not
    assert supports_shape(1, 1024, 64, **mha)
    assert not supports_shape(1, 8192, 128, Hkv=32, Hq=32, itemsize=4)


# ---------------------------------------------------------------------------
# custom-VJP norms: gradients == autodiff of the plain formulation
# ---------------------------------------------------------------------------

def _ref_layernorm(x, s, b, eps=1e-5):
    x32 = x.astype(jnp.float32)
    m = jnp.mean(x32, -1, keepdims=True)
    v = jnp.var(x32, -1, keepdims=True)
    y = (x32 - m) / jnp.sqrt(v + eps) * s.astype(jnp.float32)
    if b is not None:
        y = y + b.astype(jnp.float32)
    return y.astype(x.dtype)


def _ref_rmsnorm(x, s, eps=1e-5):
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x32), -1, keepdims=True)
    return (x32 / jnp.sqrt(ms + eps) * s.astype(jnp.float32)).astype(x.dtype)


@pytest.fixture()
def _norm_inputs():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 7, 64)) * 2 + 0.3
    s = jax.random.normal(jax.random.PRNGKey(1), (64,)) * 0.5 + 1.0
    b = jax.random.normal(jax.random.PRNGKey(2), (64,)) * 0.1
    return x, s, b


def test_layernorm_custom_vjp_gradients(_norm_inputs):
    from building_llm_from_scratch_tpu.ops.norms import layernorm

    x, s, b = _norm_inputs
    np.testing.assert_allclose(layernorm(x, s, b), _ref_layernorm(x, s, b),
                               rtol=1e-6, atol=1e-6)
    g1 = jax.grad(lambda *a: jnp.sum(jnp.sin(layernorm(*a))), (0, 1, 2))(
        x, s, b)
    g2 = jax.grad(lambda *a: jnp.sum(jnp.sin(_ref_layernorm(*a))), (0, 1, 2))(
        x, s, b)
    for a, r in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=2e-5, atol=2e-6)


def test_layernorm_custom_vjp_gradients_no_bias(_norm_inputs):
    from building_llm_from_scratch_tpu.ops.norms import layernorm

    x, s, _ = _norm_inputs
    g1 = jax.grad(lambda x, s: jnp.sum(jnp.sin(layernorm(x, s, None))),
                  (0, 1))(x, s)
    g2 = jax.grad(lambda x, s: jnp.sum(jnp.sin(_ref_layernorm(x, s, None))),
                  (0, 1))(x, s)
    for a, r in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=2e-5, atol=2e-6)


def test_rmsnorm_custom_vjp_gradients(_norm_inputs):
    from building_llm_from_scratch_tpu.ops.norms import rmsnorm

    x, s, _ = _norm_inputs
    np.testing.assert_allclose(rmsnorm(x, s), _ref_rmsnorm(x, s),
                               rtol=1e-6, atol=1e-6)
    g1 = jax.grad(lambda x, s: jnp.sum(jnp.sin(rmsnorm(x, s))), (0, 1))(x, s)
    g2 = jax.grad(lambda x, s: jnp.sum(jnp.sin(_ref_rmsnorm(x, s))),
                  (0, 1))(x, s)
    for a, r in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=2e-5, atol=2e-6)


@needs_tpu
@pytest.mark.parametrize("N,D,V", [(1024, 768, 50257), (256, 128, 999)])
def test_pallas_xent_fwd_matches_xla(N, D, V):
    """ops/xent_fwd_pallas.py (opt-in BLLM_XENT_PALLAS=1): nll and lse
    match the XLA online-logsumexp forward exactly."""
    from building_llm_from_scratch_tpu.ops.softmax_xent import (
        _xent_fwd_xla,
    )
    from building_llm_from_scratch_tpu.ops.xent_fwd_pallas import xent_fwd

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(ks[0], (N, D), jnp.bfloat16)
    w = jax.random.normal(ks[1], (D, V), jnp.bfloat16) * 0.02
    t = jax.random.randint(ks[2], (N,), 0, V)
    nll, lse = jax.jit(xent_fwd)(x, w, t)
    nll_ref, lse_ref = _xent_fwd_xla(x, w, t, 51200)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(nll), np.asarray(nll_ref),
                               rtol=1e-4, atol=2e-4)


def test_pallas_xent_supports_shape_gates():
    from building_llm_from_scratch_tpu.ops.xent_fwd_pallas import (
        supports_shape,
    )

    assert supports_shape(8192, 768, 50257)
    assert not supports_shape(100, 768, 50257)     # row misalignment
    assert not supports_shape(65536, 4096, 128256)  # VMEM blowout


def test_fused_dropout_degenerate_rows_fall_back():
    """ADVICE r4 low #3: prime leading dims (best row block < 8) must not
    take the pallas path."""
    from building_llm_from_scratch_tpu.ops.fused_dropout import (
        supports_shape,
    )

    assert supports_shape((8, 1024, 768))
    assert not supports_shape((997, 128))     # prime rows -> r degenerates
    assert not supports_shape((1, 3, 128))    # tiny fold
    assert not supports_shape((8, 100))       # lane misalignment