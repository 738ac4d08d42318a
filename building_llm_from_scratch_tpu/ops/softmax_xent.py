"""Chunked softmax cross-entropy from the final hidden states.

The reference computes ``F.cross_entropy(model(x).flatten(...), targets)``
(/root/reference/train.py:88-92) — logits materialize, then log_softmax,
then the backward materializes dlogits. At GPT-2's 50257 vocab that is a
(B*T, 50257) fp32 tensor written and re-read several times per step: the
round-4 bs8 profile shows ~18ms of a 102ms step in the loss/head block
(log_softmax 5.0ms, lse reduce 2.2ms, fused softmax-grad+dx 6.7ms, ...).

This op chunks the vocabulary: the forward runs online logsumexp over
``chunk``-wide slices of the head matmul (peak live logits = (N, chunk))
and saves only the per-token lse; the backward recomputes each chunk's
logits and feeds dlogits straight into the dx/dW matmuls. fp32 logits
never exist in HBM at full width in either pass.

Pure JAX (lax.scan + dynamic_slice) — runs on CPU/TPU, shards under GSPMD
like any matmul, and is exact (same fp32 math as dense log_softmax; parity
tested to 1e-5 in tests/test_softmax_xent.py).

Chunk-size note (v5e-1, bs8 GPT2-124M loss+grad micro-bench): dense 16.1ms;
chunk 6400/12800/25600: 19.5-20.1ms; chunk 51200 (single padded chunk):
15.3ms. Sub-vocab chunking re-reads x2/W per chunk and loses more to that
than it saves in logits traffic at this model size — the win here comes
from the custom backward (no stored log-probs, dlogits feeding matmuls
directly), so the default is one padded chunk. Smaller chunks remain
correct and useful when (N, V) temps must be bounded (long-context eval).
r5 also split ``fwd_chunk`` from the backward chunk (the backward's three
matmuls run at ~87% MXU and only lose W re-reads from chunking, while the
forward's fp32 logits temp is pure HBM traffic) — measured in-model:
fwd_chunk 6400/12800/25600 gave 91.7/93.5/93.2k tok/s vs ~94-98k dense,
i.e. chunking the forward alone still loses (the scan boundary breaks
XLA's matmul+exp fusion). Dense stays the default on both sides;
``BLLM_XENT_FWD_CHUNK`` keeps the forward bound available for
long-context eval.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from building_llm_from_scratch_tpu.parallel.collectives import mesh_kernel
from building_llm_from_scratch_tpu.parallel.mesh import DATA_AXIS

_NEG_BIG = -1e30


def _pad_vocab(w_head: jnp.ndarray, chunk: int) -> Tuple[jnp.ndarray, int]:
    D, V = w_head.shape
    n_chunks = -(-V // chunk)
    Vp = n_chunks * chunk
    if Vp != V:
        w_head = jnp.pad(w_head, ((0, 0), (0, Vp - V)))
    return w_head, n_chunks


def _chunk_logits(x2, wp, c, chunk, V):
    """(N, chunk) fp32 logits for vocab slice [c*chunk, (c+1)*chunk), with
    out-of-vocab (padded) columns masked to -inf."""
    D = x2.shape[1]
    wc = jax.lax.dynamic_slice(wp, (0, c * chunk), (D, chunk))
    logits = jnp.einsum("nd,dc->nc", x2, wc,
                        preferred_element_type=jnp.float32)
    col = c * chunk + jnp.arange(chunk)
    return jnp.where(col[None, :] < V, logits, _NEG_BIG)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def softmax_xent(x2: jnp.ndarray,        # (N, D) final hidden states
                 w_head: jnp.ndarray,    # (D, V) untied output head
                 targets: jnp.ndarray,   # (N,) int32
                 chunk: int = 51200,
                 fwd_chunk: Optional[int] = None) -> jnp.ndarray:
    """Per-token negative log-likelihood (N,) fp32.

    ``chunk`` drives the BACKWARD's recompute granularity; ``fwd_chunk``
    (defaults to ``chunk``) the forward's. They are split because their
    trade-offs differ: the backward is three near-peak matmuls whose
    chunking only adds W re-reads, while the forward's live fp32 logits
    temp (N, fwd_chunk) is pure HBM traffic the online logsumexp can
    shrink."""
    nll, _ = _xent_fwd_impl(x2, w_head, targets, fwd_chunk or chunk)
    return nll


def _pallas_fwd_requested() -> bool:
    """Opt-in (BLLM_XENT_PALLAS=1): the pallas forward streams the vocab
    through VMEM so the (N, Vp) fp32 logits temp (1.6GB at GPT2-124M bs8)
    never exists — but measured DEAD-EVEN on the headline (97.42k vs
    97.41k tok/s, r5 A/B): XLA overlaps the logits HBM traffic with
    compute. Kept opt-in for memory-constrained shapes rather than
    default: it buys HBM headroom, not steady-state speed."""
    import os

    return (os.environ.get("BLLM_XENT_PALLAS", "0") == "1"
            and jax.default_backend() == "tpu")


def _xent_fwd_impl(x2, w_head, targets, chunk):
    """Forward (nll, lse)."""
    if not _pallas_fwd_requested():
        return _xent_fwd_xla(x2, w_head, targets, chunk)
    from building_llm_from_scratch_tpu.ops.xent_fwd_pallas import (
        supports_shape,
        xent_fwd,
    )

    def per_shard(_, x2, w_head, targets):
        # each shard decides for ITS rows: the gate budgets VMEM for the
        # shape the kernel really gets
        if supports_shape(x2.shape[0], *w_head.shape):
            return xent_fwd(x2, w_head, targets)
        return _xent_fwd_xla(x2, w_head, targets, chunk)

    # GSPMD cannot partition the kernel: token rows shard over the data
    # axis, the head is gathered whole
    rows = (DATA_AXIS,)
    return mesh_kernel(per_shard, (x2, w_head, targets),
                       (rows + (None,), (None, None), rows), (rows, rows))


def _xent_fwd_xla(x2, w_head, targets, chunk):
    N, D = x2.shape
    V = w_head.shape[1]
    wp, n_chunks = _pad_vocab(w_head, chunk)

    def body(carry, c):
        m, s, tl = carry
        logits = _chunk_logits(x2, wp, c, chunk, V)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        s = s * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(logits - m_new[:, None]), axis=-1)
        local = targets.astype(jnp.int32) - c * chunk
        in_range = (local >= 0) & (local < chunk)
        picked = jnp.take_along_axis(
            logits, jnp.clip(local, 0, chunk - 1)[:, None], axis=-1)[:, 0]
        tl = jnp.where(in_range, picked, tl)
        return (m_new, s, tl), None

    init = (jnp.full((N,), _NEG_BIG, jnp.float32),
            jnp.zeros((N,), jnp.float32),
            jnp.full((N,), _NEG_BIG, jnp.float32))
    (m, s, tl), _ = jax.lax.scan(body, init, jnp.arange(n_chunks))
    lse = m + jnp.log(s)
    return lse - tl, lse


def _xent_fwd(x2, w_head, targets, chunk, fwd_chunk):
    nll, lse = _xent_fwd_impl(x2, w_head, targets, fwd_chunk or chunk)
    return nll, (x2, w_head, targets, lse)


def _xent_bwd(chunk, fwd_chunk, res, g):
    """g: (N,) cotangent of the per-token nll."""
    x2, w_head, targets, lse = res
    N, D = x2.shape
    V = w_head.shape[1]
    wp, n_chunks = _pad_vocab(w_head, chunk)
    gx = g.astype(jnp.float32)

    def body(carry, c):
        dx, dwp = carry
        logits = _chunk_logits(x2, wp, c, chunk, V)
        p = jnp.exp(logits - lse[:, None])            # softmax over V
        local = targets.astype(jnp.int32) - c * chunk
        onehot = (local[:, None] == jnp.arange(chunk)[None, :])
        dl = (p - onehot.astype(jnp.float32)) * gx[:, None]
        dl = dl.astype(x2.dtype)
        wc = jax.lax.dynamic_slice(wp, (0, c * chunk), (D, chunk))
        dx = dx + jnp.einsum("nc,dc->nd", dl, wc,
                             preferred_element_type=jnp.float32)
        dwc = jnp.einsum("nd,nc->dc", x2, dl,
                         preferred_element_type=jnp.float32)
        dwp = jax.lax.dynamic_update_slice(
            dwp, dwc.astype(dwp.dtype), (0, c * chunk))
        return (dx, dwp), None

    init = (jnp.zeros((N, D), jnp.float32),
            jnp.zeros(wp.shape, w_head.dtype))
    (dx, dwp), _ = jax.lax.scan(body, init, jnp.arange(n_chunks))
    return dx.astype(x2.dtype), dwp[:, :V], None


softmax_xent.defvjp(_xent_fwd, _xent_bwd)


def _default_chunk() -> int:
    import os

    return int(os.environ.get("BLLM_XENT_CHUNK", "51200"))


def _default_fwd_chunk() -> Optional[int]:
    import os

    v = os.environ.get("BLLM_XENT_FWD_CHUNK")
    return int(v) if v else None


def fused_cross_entropy_loss(hidden: jnp.ndarray,      # (B, T, D)
                             w_head: jnp.ndarray,      # (D, V)
                             targets: jnp.ndarray,     # (B, T)
                             weights: Optional[jnp.ndarray] = None,
                             chunk: Optional[int] = None) -> jnp.ndarray:
    """Weighted token-mean CE — same semantics as
    training.train_step.cross_entropy_loss(forward(...), targets, weights)
    without ever materializing (B, T, V) fp32 logits."""
    # the env fwd-chunk default applies ONLY when the caller did not pass
    # an explicit chunk — an explicit bound must always win
    fwd_chunk = _default_fwd_chunk() if chunk is None else None
    if chunk is None:
        chunk = _default_chunk()
    B, T, D = hidden.shape
    nll = softmax_xent(hidden.reshape(B * T, D), w_head,
                       targets.reshape(B * T).astype(jnp.int32), chunk,
                       fwd_chunk)
    nll = nll.reshape(B, T)
    if weights is None:
        return jnp.mean(nll)
    w = weights.astype(jnp.float32)
    return (nll * w).sum() / jnp.maximum(w.sum(), 1.0)


def fused_cross_entropy_sums(hidden, w_head, targets, weights,
                             chunk: Optional[int] = None):
    """(weighted nll sum, weight sum) — the cross-shard-psum variant
    (mirrors train_step.cross_entropy_sums)."""
    fwd_chunk = _default_fwd_chunk() if chunk is None else None
    if chunk is None:
        chunk = _default_chunk()
    B, T, D = hidden.shape
    nll = softmax_xent(hidden.reshape(B * T, D), w_head,
                       targets.reshape(B * T).astype(jnp.int32), chunk,
                       fwd_chunk)
    nll = nll.reshape(B, T)
    if weights is None:
        weights = jnp.ones_like(nll)
    w = weights.astype(jnp.float32)
    return (nll * w).sum(), w.sum()
