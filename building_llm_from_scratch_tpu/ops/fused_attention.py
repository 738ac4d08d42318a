"""Fused TPU flash attention WITH in-kernel attention-weight dropout.

The reference applies dropout to the softmaxed attention weights
(/root/reference/Models/GPT2/GPT2.py:30-41). On TPU that semantics made the
fast path unusable: the stock pallas flash kernel has no dropout, so every
dropout-enabled config (all GPT-2 training) fell back to an XLA blockwise
path that materializes, stores, and re-reads O(T^2) dropout masks per layer
— measured at >20ms of a 61ms GPT2-124M step (round-4 profile).

This kernel keeps the masks entirely on-chip: each (q-block, kv-block) tile
reseeds the per-core PRNG from (seed, batch, head, qblk, kvblk) and draws
its keep-mask into VMEM, both in the forward pass and again — bit-identical
— in the backward recompute. Nothing T^2-sized ever touches HBM.

Math (flash + dropout): with P = softmax(S) and keep mask M ~ Bern(1-p),
    out_i = sum_j P_ij * M_ij * v_j / (1 - p)
The online-softmax accumulation applies M to the exp() terms but NOT to the
denominator l, because dropout multiplies the *normalized* weights. In the
backward, with Mt = M/(1-p) and D_i = sum(dO_i * O_i) (the usual flash
delta), the softmax jacobian still collapses:
    dS_ij = P_ij * (Mt_ij * (dO_i . v_j) - D_i)
because sum_k P_ik Mt_ik (dO_i . v_k) = dO_i . O_i = D_i exactly.

Layouts: kernel-internal (B, H, T, D); the public wrapper takes the model's
(B, T, H, D) and transposes (cheap, XLA-fused). GQA never materializes
repeated KV heads — the kv BlockSpec index_map divides the head index.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from building_llm_from_scratch_tpu.parallel.collectives import mesh_kernel
from building_llm_from_scratch_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

# lse/delta are stored row-scalar-replicated across this many lanes. 8 (the
# fp32 sublane tile) measured ~3% faster than 128 on the bs8 headline shape
# (4.17 vs 4.31 ms fwd+bwd) by cutting the replicated fp32 HBM traffic 16x.
LANES = 8
_NEG_BIG = -1e30
_WEYL = -1640531527  # 0x9E3779B9 as int32


def _keep_mask(seed_ref, rate: float, b, h, i, j, n_i: int, n_j: int, shape):
    """Draw the Bernoulli(1-rate) keep mask for tile (b,h,i,j).

    Reseeding per tile makes the mask a pure function of the tile
    coordinates, so the backward regenerates bit-identical masks in any
    loop order without storing them.
    """
    tile = (b * pl.num_programs(1) + h) * (n_i * n_j) + i * n_j + j
    # the TPU PRNG seeds from at most 2 words: mix the tile index into the
    # second with a Weyl-sequence constant (wrapping int32 multiply)
    pltpu.prng_seed(seed_ref[0, 0], seed_ref[0, 1] + tile * jnp.int32(_WEYL))
    # prng_random_bits yields SIGNED int32 — bitcast before the unsigned
    # threshold compare or half the range lands below any positive threshold
    # (empirically: keep fraction 0.4 instead of 0.9 at rate 0.1)
    bits = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
    threshold = min(int(rate * (2 ** 32)), 2 ** 32 - 1)
    return bits >= jnp.uint32(threshold)          # True = keep, P = 1-rate


def _causal_mask(i, j, bq: int, bk: int):
    q_pos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return q_pos >= k_pos


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                scale: float, rate: float, block_q: int, block_k: int,
                n_kv: int):
    b, h, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n_q = pl.num_programs(2)
    q = q_ref[0, 0]                                   # (BQ, D)

    acc = jnp.zeros((block_q, q.shape[-1]), jnp.float32)
    m = jnp.full((block_q, 1), _NEG_BIG, jnp.float32)
    l = jnp.zeros((block_q, 1), jnp.float32)

    def body(j, carry):
        acc, m, l = carry
        kb = k_ref[0, 0, pl.ds(j * block_k, block_k), :]
        vb = v_ref[0, 0, pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(_causal_mask(i, j, block_q, block_k), s, _NEG_BIG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        if rate > 0.0:
            keep = _keep_mask(seed_ref, rate, b, h, i, j, n_q, n_kv,
                              (block_q, block_k))
            p = jnp.where(keep, p, 0.0)
        acc = acc * corr + jax.lax.dot(
            p.astype(vb.dtype), vb, preferred_element_type=jnp.float32)
        return acc, m_new, l

    # causal block skipping: only kv blocks overlapping [0, (i+1)*BQ)
    hi = jax.lax.div((i + 1) * block_q + block_k - 1, block_k)
    acc, m, l = jax.lax.fori_loop(0, hi, body, (acc, m, l))

    out = acc / l
    if rate > 0.0:
        out = out * (1.0 / (1.0 - rate))
    o_ref[0, 0] = out.astype(o_ref.dtype)
    lse_ref[0, 0] = jnp.broadcast_to(m + jnp.log(l), (block_q, LANES))


# ---------------------------------------------------------------------------
# backward: dq kernel (grid over q blocks)
# ---------------------------------------------------------------------------

def _dq_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, *, scale: float, rate: float, block_q: int,
               block_k: int, n_kv: int):
    b, h, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n_q = pl.num_programs(2)
    q = q_ref[0, 0]                                   # (BQ, D)
    do = do_ref[0, 0]                                 # (BQ, D), model dtype
    lse = lse_ref[0, 0][:, :1]                        # (BQ, 1)
    delta = delta_ref[0, 0][:, :1]                    # (BQ, 1)
    inv_keep = 1.0 / (1.0 - rate) if rate > 0.0 else 1.0

    dq = jnp.zeros((block_q, q.shape[-1]), jnp.float32)

    def body(j, dq):
        kb = k_ref[0, 0, pl.ds(j * block_k, block_k), :]
        vb = v_ref[0, 0, pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(_causal_mask(i, j, block_q, block_k), s, _NEG_BIG)
        p = jnp.exp(s - lse)                          # true softmax weights
        dp = jax.lax.dot_general(do, vb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if rate > 0.0:
            keep = _keep_mask(seed_ref, rate, b, h, i, j, n_q, n_kv,
                              (block_q, block_k))
            dp = jnp.where(keep, dp * inv_keep, 0.0)
        ds = p * (dp - delta) * scale
        return dq + jax.lax.dot(ds.astype(kb.dtype), kb,
                                preferred_element_type=jnp.float32)

    hi = jax.lax.div((i + 1) * block_q + block_k - 1, block_k)
    dq = jax.lax.fori_loop(0, hi, body, dq)
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# backward: dk/dv kernel (grid over kv blocks, per QUERY head; the wrapper
# group-sums for GQA)
# ---------------------------------------------------------------------------

def _dkv_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, *, scale: float, rate: float, block_q: int,
                block_k: int, n_q: int):
    b, h, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n_kv = pl.num_programs(2)
    kb = k_ref[0, 0]                                  # (BK, D)
    vb = v_ref[0, 0]                                  # (BK, D)
    inv_keep = 1.0 / (1.0 - rate) if rate > 0.0 else 1.0

    dk = jnp.zeros((block_k, kb.shape[-1]), jnp.float32)
    dv = jnp.zeros((block_k, vb.shape[-1]), jnp.float32)

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, 0, pl.ds(i * block_q, block_q), :]
        do = do_ref[0, 0, pl.ds(i * block_q, block_q), :]
        lse = lse_ref[0, 0, pl.ds(i * block_q, block_q), :1]
        delta = delta_ref[0, 0, pl.ds(i * block_q, block_q), :1]
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(_causal_mask(i, j, block_q, block_k), s, _NEG_BIG)
        p = jnp.exp(s - lse)                          # (BQ, BK)
        if rate > 0.0:
            keep = _keep_mask(seed_ref, rate, b, h, i, j, n_q, n_kv,
                              (block_q, block_k))
            pt = jnp.where(keep, p * inv_keep, 0.0)
        else:
            pt = p
        dv = dv + jax.lax.dot_general(                # pt^T @ do
            pt.astype(do.dtype), do,
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, vb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if rate > 0.0:
            dp = jnp.where(keep, dp * inv_keep, 0.0)
        ds = p * (dp - delta) * scale                 # (BQ, BK)
        dk = dk + jax.lax.dot_general(                # ds^T @ q
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk, dv

    lo = jax.lax.div(j * block_k, block_q)            # first overlapping qblk
    dk, dv = jax.lax.fori_loop(lo, n_q, body, (dk, dv))
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call plumbing
# ---------------------------------------------------------------------------

def _specs_fwd(B, Hq, Hkv, T, D, bq, bk):
    G = Hq // Hkv
    seed = pl.BlockSpec((1, 2), lambda b, h, i: (0, 0),
                        memory_space=pltpu.SMEM)
    qs = pl.BlockSpec((1, 1, bq, D), lambda b, h, i: (b, h, i, 0))
    kv = pl.BlockSpec((1, 1, T, D), lambda b, h, i: (b, h // G, 0, 0))
    return [seed, qs, kv, kv]


def _fwd(q, k, v, seed, *, scale, rate, bq, bk):
    B, Hq, T, D = q.shape
    Hkv = k.shape[1]
    n_q, n_kv = T // bq, T // bk
    kernel = functools.partial(_fwd_kernel, scale=scale, rate=rate,
                               block_q=bq, block_k=bk, n_kv=n_kv)
    out, lse = pl.pallas_call(
        kernel,
        grid=(B, Hq, n_q),
        in_specs=_specs_fwd(B, Hq, Hkv, T, D, bq, bk),
        out_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, LANES), lambda b, h, i: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, T, D), q.dtype),
            jax.ShapeDtypeStruct((B, Hq, T, LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
    )(seed, q, k, v)
    return out, lse


def _bwd(q, k, v, seed, out, lse, do, *, scale, rate, bq, bk):
    B, Hq, T, D = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
    n_q, n_kv = T // bq, T // bk
    # flash delta: D_i = sum_d dO_id * O_id, lane-replicated like lse.
    # The 128x replication of lse/delta costs ~0.3% of the headline step
    # (~300MB of redundant fp32 traffic at bs8) — accepted for the simple
    # always-2D tile layout; revisit only if these rows show up in profiles.
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)
    delta = jnp.broadcast_to(delta, (B, Hq, T, LANES))

    seed_spec = pl.BlockSpec((1, 2), lambda b, h, i: (0, 0),
                             memory_space=pltpu.SMEM)
    qs_blk = pl.BlockSpec((1, 1, bq, D), lambda b, h, i: (b, h, i, 0))
    qs_full = pl.BlockSpec((1, 1, T, D), lambda b, h, j: (b, h, 0, 0))
    kv_full = pl.BlockSpec((1, 1, T, D), lambda b, h, i: (b, h // G, 0, 0))
    kv_blk = pl.BlockSpec((1, 1, bk, D), lambda b, h, j: (b, h // G, j, 0))
    lane_blk = pl.BlockSpec((1, 1, bq, LANES), lambda b, h, i: (b, h, i, 0))
    lane_full = pl.BlockSpec((1, 1, T, LANES), lambda b, h, j: (b, h, 0, 0))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, rate=rate, block_q=bq,
                          block_k=bk, n_kv=n_kv),
        grid=(B, Hq, n_q),
        in_specs=[seed_spec, qs_blk, kv_full, kv_full, qs_blk, lane_blk,
                  lane_blk],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, T, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
    )(seed, q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, rate=rate, block_q=bq,
                          block_k=bk, n_q=n_q),
        grid=(B, Hq, n_kv),
        in_specs=[seed_spec, qs_full, kv_blk, kv_blk, qs_full, lane_full,
                  lane_full],
        out_specs=[
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j: (b, h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, T, D), q.dtype),
            jax.ShapeDtypeStruct((B, Hq, T, D), q.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
    )(seed, q, k, v, do, lse, delta)

    if G > 1:        # GQA: per-query-head dk/dv -> sum over the group
        dk = dk.reshape(B, Hkv, G, T, D).sum(axis=2).astype(k.dtype)
        dv = dv.reshape(B, Hkv, G, T, D).sum(axis=2).astype(v.dtype)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom-vjp public op (kernel layout (B, H, T, D))
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _fused_bhtd(q, k, v, seed, rate, bq, bk):
    scale = 1.0 / float(q.shape[-1]) ** 0.5
    out, _ = _fwd(q, k, v, seed, scale=scale, rate=rate, bq=bq, bk=bk)
    return out


def _fused_fwd_rule(q, k, v, seed, rate, bq, bk):
    from jax.ad_checkpoint import checkpoint_name

    scale = 1.0 / float(q.shape[-1]) ** 0.5
    out, lse = _fwd(q, k, v, seed, scale=scale, rate=rate, bq=bq, bk=bk)
    # named so the transformer's selective-save remat policy stores these
    # residuals instead of re-running the forward kernel in the backward
    out = checkpoint_name(out, "attn_raw_out")
    lse = checkpoint_name(lse, "attn_lse")
    return out, (q, k, v, seed, out, lse)


def _fused_bwd_rule(rate, bq, bk, res, do):
    q, k, v, seed, out, lse = res
    scale = 1.0 / float(q.shape[-1]) ** 0.5
    dq, dk, dv = _bwd(q, k, v, seed, out, lse, do,
                      scale=scale, rate=rate, bq=bq, bk=bk)
    return dq, dk, dv, None


_fused_bhtd.defvjp(_fused_fwd_rule, _fused_bwd_rule)


def fused_causal_attention(
    q: jnp.ndarray,               # (B, T, Hq, D) — model layout
    k: jnp.ndarray,               # (B, T, Hkv, D)
    v: jnp.ndarray,               # (B, T, Hkv, D)
    *,
    dropout_rate: float = 0.0,
    dropout_rng: jax.Array | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
) -> jnp.ndarray:
    """Fused causal flash attention, optional in-kernel attention dropout.

    Requires T divisible by the block sizes (the auto-policy in
    ops/attention.py guarantees it; explicit callers must check
    ``supports_shape``).
    """
    import os

    if block_q is None:
        block_q = int(os.environ.get("BLLM_ATTN_BQ", "512"))
    if block_k is None:
        block_k = int(os.environ.get("BLLM_ATTN_BK", "512"))
    B, T, Hq, D = q.shape
    if k.shape[1] != T or v.shape[1] != T:
        raise ValueError(
            f"fused attention is self-attention only (Tq == Tkv); got "
            f"q T={T}, k T={k.shape[1]}, v T={v.shape[1]}")
    bq, bk = min(block_q, T), min(block_k, T)
    if T % bq or T % bk or T % 128:
        raise ValueError(f"fused attention needs T % block == 0 and lane-"
                         f"aligned T; T={T}, blocks=({bq},{bk})")
    if dropout_rate > 0.0:
        if dropout_rng is None:
            raise ValueError("dropout_rate > 0 requires dropout_rng")
        seed = jax.random.bits(dropout_rng, (1, 2), jnp.uint32)
        seed = seed.astype(jnp.int32)
    else:
        seed = jnp.zeros((1, 2), jnp.int32)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    def per_shard(shard, qs, ks, vs, seed):
        # tile coordinates are shard-local: mix the shard index into the
        # seed so every (batch, head) slice draws its own mask
        seed = seed + shard * jnp.int32(_WEYL)
        return _fused_bhtd(qs, ks, vs, seed, float(dropout_rate), bq, bk)

    bhtd = (DATA_AXIS, MODEL_AXIS, None, None)
    out = mesh_kernel(per_shard, (qt, kt, vt, seed),
                      (bhtd, bhtd, bhtd, (None, None)), bhtd)
    return out.transpose(0, 2, 1, 3)


def supports_shape(Tq: int, Tkv: int, D: int, block: int = 512) -> bool:
    """Shapes the fused kernel handles: self-attention, lane-aligned and
    block-divisible sequence, lane-friendly head dim. Note ``min(block,Tq)``
    makes ``Tq % b`` vacuous for short Tq — the explicit ``Tq % 128`` keeps
    non-lane-aligned shapes (e.g. T=300) on the exact paths."""
    b = min(block, Tq)
    return (Tq == Tkv and Tq >= 2 * 128 and Tq % b == 0 and Tq % 128 == 0
            and D % 64 == 0 and D <= 256)
