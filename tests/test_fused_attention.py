"""Fused flash-attention kernel (ops/fused_attention.py) — TPU-only tests.

The kernel carries the reference's attention-dropout semantics
(/root/reference/Models/GPT2/GPT2.py:30-41) into the fused fast path. The
key test regenerates the kernel's exact keep-masks with a dump kernel and
checks forward AND backward against a dense same-mask oracle — proving the
forward and the two backward kernels all see bit-identical masks.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

needs_tpu = pytest.mark.needs_tpu      # skipped off-chip by conftest.py


def _qkv(B=2, T=512, Hq=4, Hkv=4, D=64, dtype=jnp.bfloat16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, T, Hq, D), dtype)
    k = jax.random.normal(ks[1], (B, T, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, T, Hkv, D), dtype)
    return q, k, v


def _dump_masks(B, H, T, seed, rate, bq, bk):
    """Regenerate the kernel's keep masks tile-by-tile (same _keep_mask)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from building_llm_from_scratch_tpu.ops import fused_attention as fa

    n_q, n_kv = T // bq, T // bk

    def kernel(seed_ref, out_ref):
        b, h, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)
        for j in range(n_kv):
            keep = fa._keep_mask(seed_ref, rate, b, h, i, j, n_q, n_kv,
                                 (bq, bk))
            out_ref[0, 0, :, pl.ds(j * bk, bk)] = keep.astype(jnp.int8)

    return pl.pallas_call(
        kernel,
        grid=(B, H, n_q),
        in_specs=[pl.BlockSpec((1, 2), lambda b, h, i: (0, 0),
                               memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((1, 1, bq, T), lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, T, T), jnp.int8),
    )(seed)


def _oracle(q, k, v, mask, rate):
    """Dense attention with an explicit keep mask (B,Hq,T,T)."""
    B, T, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qh = q.transpose(0, 2, 1, 3)
    kh = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1)
    vh = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh,
                   preferred_element_type=jnp.float32) / np.sqrt(D)
    causal = np.tril(np.ones((T, T), bool))
    s = jnp.where(causal, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    if mask is not None:
        p = p * mask / (1.0 - rate)
    out = jnp.einsum("bhqk,bhkd->bhqd", p.astype(vh.dtype), vh,
                     preferred_element_type=jnp.float32)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


@needs_tpu
def test_fused_matches_oracle_no_dropout():
    from building_llm_from_scratch_tpu.ops.fused_attention import (
        fused_causal_attention,
    )

    q, k, v = _qkv()
    want = np.asarray(_oracle(q, k, v, None, 0.0), np.float32)
    got = np.asarray(jax.jit(
        lambda q, k, v: fused_causal_attention(q, k, v, block_q=128,
                                               block_k=128))(q, k, v),
        np.float32)
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)


@needs_tpu
def test_fused_gradients_match_oracle_no_dropout():
    from building_llm_from_scratch_tpu.ops.fused_attention import (
        fused_causal_attention,
    )

    q, k, v = _qkv(Hq=8, Hkv=2)          # GQA: exercises the group-sum bwd

    def loss(fn, q, k, v):
        return jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)

    gw = jax.grad(lambda *a: loss(lambda q, k, v: _oracle(q, k, v, None, 0.0),
                                  *a), argnums=(0, 1, 2))(q, k, v)
    gf = jax.jit(jax.grad(
        lambda *a: loss(lambda q, k, v: fused_causal_attention(
            q, k, v, block_q=128, block_k=128), *a),
        argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(gf, gw):
        a32, b32 = np.asarray(a, np.float32), np.asarray(b, np.float32)
        scale = max(1.0, np.abs(b32).max())
        assert np.abs(a32 - b32).max() / scale < 2e-2


@needs_tpu
def test_fused_dropout_exact_vs_same_mask_oracle():
    """Dump the kernel's keep masks; forward and both backward kernels must
    match a dense oracle using those exact masks (fp32, tight tolerance)."""
    from building_llm_from_scratch_tpu.ops.fused_attention import (
        fused_causal_attention,
    )

    B, T, H, D, rate, blk = 2, 512, 4, 64, 0.1, 128
    q, k, v = _qkv(B=B, T=T, Hq=H, Hkv=H, D=D, dtype=jnp.float32)
    rng = jax.random.PRNGKey(7)
    seed = jax.random.bits(rng, (1, 2), jnp.uint32).astype(jnp.int32)
    mask = jnp.asarray(np.asarray(_dump_masks(B, H, T, seed, rate, blk, blk),
                                  np.float32))
    # keep fraction is Bernoulli(1-rate) over B*H*T*T/2 causal entries
    causal = np.tril(np.ones((T, T), bool))
    frac = np.asarray(mask)[:, :, causal].mean()
    assert abs(frac - (1 - rate)) < 5e-3

    fused = jax.jit(lambda q, k, v: fused_causal_attention(
        q, k, v, dropout_rate=rate, dropout_rng=rng, block_q=blk,
        block_k=blk))
    got = np.asarray(fused(q, k, v))
    want = np.asarray(_oracle(q, k, v, mask, rate))
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)

    def loss(fn, q, k, v):
        return jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)

    go = jax.grad(lambda *a: loss(
        lambda q, k, v: _oracle(q, k, v, mask, rate), *a),
        argnums=(0, 1, 2))(q, k, v)
    gf = jax.jit(jax.grad(lambda *a: loss(
        lambda q, k, v: fused_causal_attention(
            q, k, v, dropout_rate=rate, dropout_rng=rng, block_q=blk,
            block_k=blk), *a), argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(gf, go):
        a32, b32 = np.asarray(a, np.float32), np.asarray(b, np.float32)
        scale = max(1.0, np.abs(b32).max())
        assert np.abs(a32 - b32).max() / scale < 2e-2


@needs_tpu
def test_fused_dropout_deterministic_and_causal():
    from building_llm_from_scratch_tpu.ops.fused_attention import (
        fused_causal_attention,
    )

    q, k, v = _qkv(T=1024)
    rng = jax.random.PRNGKey(3)
    f = jax.jit(lambda q, k, v: fused_causal_attention(
        q, k, v, dropout_rate=0.1, dropout_rng=rng))
    o1 = np.asarray(f(q, k, v), np.float32)
    o2 = np.asarray(f(q, k, v), np.float32)
    assert np.array_equal(o1, o2)
    assert np.isfinite(o1).all()
    # causality: zeroing future kv leaves the first half untouched
    k2 = k.at[:, 512:].set(0.0)
    v2 = v.at[:, 512:].set(0.0)
    o3 = np.asarray(f(q, k2, v2), np.float32)
    np.testing.assert_array_equal(o1[:, :512], o3[:, :512])


@needs_tpu
def test_fused_different_rngs_give_different_masks():
    from building_llm_from_scratch_tpu.ops.fused_attention import (
        fused_causal_attention,
    )

    q, k, v = _qkv(T=512)
    f = functools.partial(fused_causal_attention, dropout_rate=0.5)
    o1 = np.asarray(f(q, k, v, dropout_rng=jax.random.PRNGKey(0)), np.float32)
    o2 = np.asarray(f(q, k, v, dropout_rng=jax.random.PRNGKey(1)), np.float32)
    assert not np.array_equal(o1, o2)


def test_supports_shape():
    from building_llm_from_scratch_tpu.ops.fused_attention import (
        supports_shape,
    )

    assert supports_shape(1024, 1024, 64)
    assert supports_shape(2048, 2048, 128)
    assert supports_shape(512, 512, 64)
    assert not supports_shape(1, 1024, 64)       # decode
    assert not supports_shape(1000, 1000, 64)    # not block-divisible
    assert not supports_shape(300, 300, 64)      # short but not lane-aligned
    assert not supports_shape(1024, 1024, 80)    # head dim not lane-friendly
    assert not supports_shape(128, 128, 64)      # too short to block
