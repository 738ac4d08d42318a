"""Pallas TPU forward for the chunked softmax cross-entropy.

The XLA forward of ops/softmax_xent.py materializes the full (N, Vp) fp32
logits in HBM (1.6GB for GPT2-124M bs8) and re-reads them for the
logsumexp — ~7.8ms of the 80ms headline step (r5 profile: logits fusion
3.5ms + exponential_reduce 2.2ms + ancillary traffic). This kernel streams
the vocabulary in lane-chunks through ONE grid pass: the (N, D) hidden
block stays resident in VMEM (constant index map — pallas fetches it
once), each grid step matmuls one (D, BV) weight chunk, applies the online
logsumexp update and the target-logit pick entirely in VMEM, and only the
(N,) lse / target-logit vectors ever reach HBM.

Backward stays the XLA implementation in softmax_xent.py (its three
matmuls already run at ~87% MXU utilization).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_BIG = -1e30
# accumulators are (N, LANES) lane-replicated (mosaic wants 2D tiles);
# 128 lanes keeps the reductions layout-native
_LANES = 128
# the kernel keeps whole-N panes resident, far past the compiler's default
# 16 MiB scoped-VMEM limit; v5e has 128 MiB of VMEM per core
_VMEM_LIMIT = 100 * 2 ** 20


def _kernel(x_ref, w_ref, tgt_ref, lse_ref, tl_ref, m_ref, s_ref, *,
            bv: int, V: int):
    c = pl.program_id(0)
    n_chunks = pl.num_programs(0)

    @pl.when(c == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_BIG)
        s_ref[...] = jnp.zeros_like(s_ref)
        tl_ref[...] = jnp.full_like(tl_ref, _NEG_BIG)

    x = x_ref[...]                                    # (N, D) bf16
    w = w_ref[...]                                    # (D, BV)
    s = jax.lax.dot(x, w, preferred_element_type=jnp.float32)  # (N, BV)
    col = c * bv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(col < V, s, _NEG_BIG)               # mask padded vocab

    m_old = m_ref[:, :1]                              # (N, 1)
    m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
    corr = jnp.exp(m_old - m_new)
    s_sum = jnp.sum(jnp.exp(s - m_new), axis=-1, keepdims=True)
    s_ref[...] = jnp.broadcast_to(s_ref[:, :1] * corr + s_sum,
                                  s_ref.shape)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    # target logit: rows whose target falls in this chunk pick it up
    tgt = tgt_ref[:, :1]                              # (N, 1) int32
    local = tgt - c * bv
    in_chunk = (local >= 0) & (local < bv)
    lane = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    picked = jnp.sum(jnp.where(lane == local, s, 0.0), axis=-1,
                     keepdims=True)
    tl_ref[...] = jnp.where(
        jnp.broadcast_to(in_chunk, tl_ref.shape),
        jnp.broadcast_to(picked, tl_ref.shape), tl_ref[...])

    @pl.when(c == n_chunks - 1)
    def _finish():
        lse_ref[...] = m_ref[...] + jnp.log(s_ref[...])


def xent_fwd(x2: jnp.ndarray,       # (N, D) hidden states
             w_head: jnp.ndarray,   # (D, V)
             targets: jnp.ndarray,  # (N,) int32
             bv: int = 512):
    """(nll (N,), lse (N,)) fp32 — same math as softmax_xent's forward."""
    N, D = x2.shape
    V = w_head.shape[1]
    n_chunks = -(-V // bv)
    Vp = n_chunks * bv
    if Vp != V:
        w_head = jnp.pad(w_head, ((0, 0), (0, Vp - V)))
    tgt2 = jnp.broadcast_to(targets.astype(jnp.int32)[:, None],
                            (N, _LANES))

    lse, tl = pl.pallas_call(
        functools.partial(_kernel, bv=bv, V=V),
        grid=(n_chunks,),
        in_specs=[
            pl.BlockSpec((N, D), lambda c: (0, 0)),        # resident
            pl.BlockSpec((D, bv), lambda c: (0, c)),       # streamed
            pl.BlockSpec((N, _LANES), lambda c: (0, 0)),   # resident
        ],
        out_specs=[
            pl.BlockSpec((N, _LANES), lambda c: (0, 0)),
            pl.BlockSpec((N, _LANES), lambda c: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, _LANES), jnp.float32),  # lse
            jax.ShapeDtypeStruct((N, _LANES), jnp.float32),  # target logit
        ],
        scratch_shapes=[
            pltpu.VMEM((N, _LANES), jnp.float32),            # running max
            pltpu.VMEM((N, _LANES), jnp.float32),            # running sum
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
    )(x2, w_head, tgt2)
    lse1 = lse[:, 0]
    return lse1 - tl[:, 0], lse1


def supports_shape(N: int, D: int, V: int, bv: int = 512) -> bool:
    """VMEM budget under ``_VMEM_LIMIT``: resident x (N*D bf16) and the
    streamed weight chunk, both double-buffered by the pipeline, + logits
    chunk (N*bv f32) + 4 accumulator panes (N*128 f32). The model is
    conservative (the v5e compiler takes 41 MB where it says 60 MB at
    N8192 D768); the bound is where it stopped refusing shapes
    (tests/test_tpu_compile.py compiles the largest one admitted)."""
    x_mb = 2 * N * D * 2 / 1e6
    w_mb = 2 * D * bv * 2 / 1e6
    s_mb = N * bv * 4 / 1e6
    acc_mb = 4 * N * _LANES * 4 / 1e6
    return (N % 8 == 0 and D % 128 == 0 and N >= 128
            and x_mb + w_mb + s_mb + acc_mb < 98)
