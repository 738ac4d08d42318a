"""Causal (grouped-query) attention.

One implementation surface replaces the reference's three attention classes:
  - MultiHeadAttention        (Models/GPT2/GPT2.py:6-49)
  - MHA w/ RoPE               (Models/Llama/Llama2.py:61-114)
  - GroupedQueryAttention     (Models/Llama/Llama3.py:108-155)

Interchangeable implementations (``ModelConfig.attn_impl``):

  xla     — einsum scores + masked softmax. Materializes the full
            (B, Hkv, G, Tq, Tkv) fp32 score tensor; exact, used for short
            sequences and as the oracle in parity tests. Also the only path
            for cached decode (tiny Tq — blocking buys nothing there).
  flash   — chunked online attention: ``lax.scan`` over query blocks with a
            remat'd block body, so live score memory is O(BQ · Tkv) in both
            forward and backward instead of O(Tq · Tkv). Pure XLA: runs on
            CPU/TPU, differentiable, supports attention dropout (per-block
            folded PRNG).
  pallas  — the stock JAX pallas TPU kernel
            (jax.experimental.pallas.ops.tpu.flash_attention) with 512x512
            blocks. TPU only, no dropout. Kept as a cross-check; auto now
            prefers the in-house ``fused`` kernel.
  fused   — the in-house pallas kernel (ops/fused_attention.py): tiled
            online-softmax with IN-KERNEL PRNG attention dropout, custom
            fwd + dq + dkv kernels, causal block skipping, GQA via head
            index mapping. The only fast path that carries the reference's
            attention-dropout semantics (GPT2.py:30-41); measured 56.3ms ->
            GPT2-124M headline step vs 64.5ms on flash (r4).
  auto    — on TPU: fused for every block-divisible self-attention shape
            (dropout or not); else flash for block-divisible sequences;
            else xla.

Measured fwd+bwd ms on v5e-1, bf16 (2026-07, this module's impls; pallas =
512x512 blocks; best per row in [brackets]):

  shape                          xla     flash   pallas
  GPT2   b4  t1024 H12  D64      [5.2]   [5.1]    7.7
  GPT2   b4  t2048 H12  D64       9.3     9.9    [6.0]
  L3.2   b8  t1024 H32/8 D64     11.8    [8.9]    7.6*
  L2-7B  b4  t1024 H32  D128      7.4     8.5    [5.8]*
  L3.2   b4  t2048 H32/8 D64     18.7    16.2   [10.4]
  8B-ish b2  t4096 H32/8 D128    34.0    29.4   [11.8]

  (*r3 table, kept for the stock-kernel cross-check. Since r4 auto routes
  every block-divisible TPU training shape to the in-house ``fused``
  kernel instead — measured in-model: GPT2-124M bf16 step 56.3ms fused vs
  64.5ms flash at bs4, with identical dropout semantics.)

TPU-first details shared by all paths:
  - no (ctx, ctx) mask *buffer*: the causal mask comes from position iota
    (the reference registers a persistent O(T^2) buffer per layer);
  - KV heads are expanded by broadcasting inside the einsum for xla/flash
    (the reference materializes ``repeat_interleave`` copies, Llama3.py:133-137);
  - softmax runs in fp32 and matmuls carry
    ``preferred_element_type=float32`` so bf16 training is stable on the MXU.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

# Implementations currently wired up; args.py validates --attn_impl against
# this so unimplemented choices fail at flag time, not mid-run.
AVAILABLE_IMPLS = ("auto", "xla", "flash", "pallas", "fused")

_NEG_INF = -1e30


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve_impl(impl: str, Tq: int, Tkv: int, head_dim: int,
                  q_positions, kv_length, dropout_active: bool,
                  block_q: int, window: Optional[int] = None) -> str:
    """Pick the concrete implementation for ``impl='auto'`` and validate
    eligibility of explicit choices (falling back where semantics require)."""
    if impl not in AVAILABLE_IMPLS:
        raise NotImplementedError(
            f"attention impl '{impl}' is not available yet; "
            f"options: {AVAILABLE_IMPLS}")
    if kv_length is not None or window is not None:
        # cached decode: Tq is 1 (or a short prefill) — the score tensor is
        # already small and the fused kernels don't model cache validity,
        # nor a window
        return "xla"
    if q_positions is not None:
        # flash/pallas assume q starts at kv position 0; silently computing
        # the wrong causal mask for a chunked prefill would be a correctness
        # hazard (round-2 ADVICE low), so only xla honors q_positions
        return "xla"
    if impl != "auto":
        return impl
    # auto: on TPU the in-house fused kernel (ops/fused_attention.py) owns
    # every block-divisible training shape — with OR without dropout (its
    # in-kernel PRNG keeps T^2 masks out of HBM); flash/xla cover CPU and
    # odd shapes
    if _on_tpu():
        from building_llm_from_scratch_tpu.ops.fused_attention import (
            supports_shape,
        )

        if supports_shape(Tq, Tkv, head_dim):
            return "fused"
    if Tq == Tkv and Tq >= 2 * block_q and Tq % block_q == 0:
        return "flash"
    return "xla"


# ---------------------------------------------------------------------------
# xla path (exact oracle; also the decode path)
# ---------------------------------------------------------------------------

def _xla_attention(q, k, v, *, q_positions, kv_length, dropout_rate,
                   dropout_rng, deterministic, window=None):
    B, Tq, Hq, D = q.shape
    _, Tkv, Hkv, _ = k.shape
    G = Hq // Hkv

    if q_positions is None:
        q_pos = jnp.arange(Tq)
    else:
        q_pos = q_positions
    kv_pos = jnp.arange(Tkv)

    if q_pos.ndim == 1:
        mask = q_pos[:, None] >= kv_pos[None, :]            # (Tq, Tkv)
        mask = mask[None, None, None, :, :]                 # (1,1,1,Tq,Tkv)
    else:
        mask = q_pos[:, :, None] >= kv_pos[None, None, :]   # (B, Tq, Tkv)
        mask = mask[:, None, None, :, :]                    # (B,1,1,Tq,Tkv)
    if window is not None:
        # position i attends to j with i - window < j <= i
        near = (q_pos[..., :, None] - kv_pos) < window
        mask = mask & (near[None, None, None] if q_pos.ndim == 1
                       else near[:, None, None])
    if kv_length is not None:
        valid = kv_pos[None, :] < jnp.reshape(kv_length, (-1, 1))  # (B|1, Tkv)
        mask = mask & valid[:, None, None, None, :]

    qg = q.reshape(B, Tq, Hkv, G, D)
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, dtype=jnp.float32))
    # (B, Hkv, G, Tq, Tkv) in fp32 for a stable softmax
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                        preferred_element_type=jnp.float32)
    scores = scores * scale
    scores = jnp.where(mask, scores, jnp.asarray(_NEG_INF, scores.dtype))
    weights = jax.nn.softmax(scores, axis=-1)

    if dropout_rate > 0.0 and not deterministic:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate,
                                    weights.shape)
        weights = jnp.where(keep, weights / (1.0 - dropout_rate), 0.0)

    weights = weights.astype(v.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", weights, v)
    return out.reshape(B, Tq, Hq, D)


# ---------------------------------------------------------------------------
# flash path: chunked query blocks, remat'd body
# ---------------------------------------------------------------------------

def _flash_attention_xla(q, k, v, *, block_q, dropout_rate, dropout_rng,
                         deterministic):
    """Blockwise causal attention: scan over query blocks.

    Live memory per step is one (B, Hkv, G, BQ, Tkv) fp32 score block; the
    remat'd body makes the backward recompute it per block instead of
    saving all Tq/BQ blocks.
    """
    B, Tq, Hq, D = q.shape
    _, Tkv, Hkv, _ = k.shape
    G = Hq // Hkv
    assert Tq % block_q == 0, "flash impl requires Tq divisible by block_q"
    n_blocks = Tq // block_q
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, dtype=jnp.float32))
    kv_pos = jnp.arange(Tkv)
    dropout_active = dropout_rate > 0.0 and not deterministic
    if not dropout_active:
        dropout_rng = jax.random.PRNGKey(0)          # unused, fixed for scan

    # (n_blocks, B, Hkv, G, BQ, D) query blocks
    qb = q.reshape(B, n_blocks, block_q, Hkv, G, D).transpose(1, 0, 3, 4, 2, 5)

    def body(_, xs):
        q_block, block_idx = xs
        q_pos = block_idx * block_q + jnp.arange(block_q)
        s = jnp.einsum("bhgqd,bkhd->bhgqk", q_block, k,
                       preferred_element_type=jnp.float32) * scale
        mask = (q_pos[:, None] >= kv_pos[None, :])[None, None, None]
        s = jnp.where(mask, s, jnp.asarray(_NEG_INF, s.dtype))
        w = jax.nn.softmax(s, axis=-1)
        if dropout_active:
            keep = jax.random.bernoulli(
                jax.random.fold_in(dropout_rng, block_idx),
                1.0 - dropout_rate, w.shape)
            w = jnp.where(keep, w / (1.0 - dropout_rate), 0.0)
        o = jnp.einsum("bhgqk,bkhd->bhgqd", w.astype(v.dtype), v)
        return None, o

    _, ob = jax.lax.scan(jax.checkpoint(body, prevent_cse=False), None,
                         (qb, jnp.arange(n_blocks)))
    # (n_blocks, B, Hkv, G, BQ, D) -> (B, Tq, Hq, D)
    out = ob.transpose(1, 0, 4, 2, 3, 5).reshape(B, Tq, Hq, D)
    return out


# ---------------------------------------------------------------------------
# pallas path: fused TPU kernel
# ---------------------------------------------------------------------------

def _pallas_flash_attention(q, k, v, block: int = 512):
    """Fused flash attention on the MXU via the pallas TPU kernel
    (jax.experimental.pallas.ops.tpu.flash_attention — public JAX op with
    custom forward AND backward kernels, causal-block skipping included).

    512x512 blocks measured 1.3-2.2x faster than the kernel's defaults on
    v5e (module docstring table) — big K blocks amortize the causal-block
    skip and keep the MXU fed.
    """
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        BlockSizes,
        flash_attention,
    )

    B, Tq, Hq, D = q.shape
    _, Tkv, Hkv, _ = k.shape
    G = Hq // Hkv
    # kernel layout (B, H, T, D); broadcast KV heads up to Hq for GQA
    qh = q.transpose(0, 2, 1, 3)
    kh = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1)
    vh = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1)
    scale = 1.0 / float(D) ** 0.5
    bq, bk = min(block, Tq), min(block, Tkv)
    if Tq % bq == 0 and Tkv % bk == 0:
        bs = BlockSizes(
            block_q=bq, block_k_major=bk, block_k=bk, block_b=1,
            block_q_major_dkv=bq, block_k_major_dkv=bk, block_k_dkv=bk,
            block_q_dkv=bq, block_k_major_dq=bk, block_k_dq=bk,
            block_q_dq=bq)
    else:
        bs = None                      # odd length: kernel's own defaults
    out = flash_attention(qh, kh, vh, causal=True, sm_scale=scale,
                          block_sizes=bs)
    return out.transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# decode path: cache-layout-native attention
# ---------------------------------------------------------------------------

#: above this many bytes of float32 scores, ``decode_attention`` runs one
#: key-value head (with its group of query heads) at a time
_SCORE_BYTES_AT_ONCE = 2 ** 30


def decode_attention(
    q: jnp.ndarray,               # (B, Tq, Hq, D) — model layout (tiny Tq)
    k_cache: jnp.ndarray,         # (B, Hkv, Tmax, D) — cache-native layout
    v_cache: jnp.ndarray,         # (B, Hkv, Tmax, D)
    *,
    q_positions: jnp.ndarray,     # (Tq,) or (B, Tq) absolute positions
    kv_length: jnp.ndarray,       # scalar or (B,): valid cache prefix
    k_scale: Optional[jnp.ndarray] = None,   # (B, Hkv, Tmax, 1) int8 cache
    v_scale: Optional[jnp.ndarray] = None,   # (B, Hkv, Tmax, 1) scales
    kv_positions: Optional[jnp.ndarray] = None,   # (Tmax,) or (B, Tmax)
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Attention for KV-cache decode, consuming the cache in its OWN
    (B, H, T, D) layout.

    The general ``causal_attention`` takes (B, T, H, D) k/v; feeding it the
    cache made XLA materialize a transposed copy of the ENTIRE cache for
    every layer of every decoded token (r5 profile: ~24 full-buffer
    copies/step, ~40% of decode step time on GPT2-124M bs8). Here the
    score/value einsums batch over (B, H) directly, so the cache streams
    without re-layout. Exact same math/masking as the xla path with
    ``q_positions``/``kv_length``; no dropout (decode is eval-only).

    Per-row ``q_positions`` (B, Tq) + ``kv_length`` (B,) serve the serving
    engine's slot batch, where every row is a different request at a
    different sequence length (serving/engine.py).

    ``kv_positions``: the absolute position each cache index holds, for a
    buffer that is a RING (index = position mod its length; see
    ``ring_positions``); negative = never written. Default: index i holds
    position i. ``window``: a query at position p attends to positions in
    (p - window, p]. Masks are by absolute position either way.

    ``k_scale``/``v_scale`` dequantize an int8 cache (serving/kvcache.py
    int8 policy) WITHOUT materializing a dequantized copy: the per-
    position scales are constant over head_dim, so they factor out of
    the score dot (``q . (k8*s) = (q . k8) * s``) and fold into the
    probability row before the value dot (``sum_k p_k*(v8_k*s_k) =
    sum_k (p_k*s_k)*v8_k``) — exactly equal to dequantize-then-attend.

    Past ``_SCORE_BYTES_AT_ONCE`` of scores (a 512-token chunk against a
    20k-position row of 128 query heads is 5 GB) the key-value heads run
    one at a time (``lax.map``): the same arithmetic on a slice.
    """
    B, Tq, Hq, D = q.shape
    _, Hkv, Tkv, _ = k_cache.shape
    G = Hq // Hkv
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, dtype=jnp.float32))
    if k_scale is not None:
        k_cache = k_cache.astype(jnp.float32)
        v_cache = v_cache.astype(jnp.float32)
    # (B, Hkv, G, Tq, D) — tiny transpose (Tq is 1 for decode steps)
    qg = q.reshape(B, Tq, Hkv, G, D).transpose(0, 2, 3, 1, 4)
    kv_pos = jnp.arange(Tkv) if kv_positions is None else kv_positions
    if q_positions.ndim == 2:
        # per-row positions/lengths: mask (B, Tq, Tkv) -> (B, 1, 1, Tq, Tkv)
        kv_pos = kv_pos[None, None, :] if kv_pos.ndim == 1 \
            else kv_pos[:, None, :]
        q_pos = q_positions[:, :, None]
        mask = (q_pos >= kv_pos) \
            & (kv_pos < jnp.reshape(kv_length, (-1, 1, 1)))
    else:
        q_pos = q_positions[:, None]
        kv_pos = kv_pos[None, :]
        mask = (q_pos >= kv_pos) & (kv_pos < kv_length)
    if kv_positions is not None:
        mask = mask & (kv_pos >= 0)
    if window is not None:
        mask = mask & (q_pos - kv_pos < window)
    mask = mask[:, None, None] if q_positions.ndim == 2 \
        else mask[None, None, None]

    def attend(qg, k_cache, v_cache, k_scale, v_scale):
        scores = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k_cache,
                            preferred_element_type=jnp.float32) * scale
        if k_scale is not None:
            # (B, Hkv, Tkv, 1) -> (B, Hkv, 1, 1, Tkv), broadcast over (G,
            # Tq): one multiply per score, the whole K-side dequant cost
            scores = scores * k_scale[:, :, :, 0][:, :, None, None, :]
        scores = jnp.where(mask, scores,
                           jnp.asarray(_NEG_INF, scores.dtype))
        weights = jax.nn.softmax(scores, axis=-1).astype(v_cache.dtype)
        if v_scale is not None:
            # fold the V-side scales into the probability row (exact):
            # sum_k p_k * (v8_k * s_k) == sum_k (p_k * s_k) * v8_k
            weights = weights * v_scale[:, :, :, 0][:, :, None, None, :]
        return jnp.einsum("bhgqk,bhkd->bhgqd", weights, v_cache)

    if 4 * B * Hq * Tq * Tkv <= _SCORE_BYTES_AT_ONCE or Hkv == 1:
        out = attend(qg, k_cache, v_cache, k_scale, v_scale)
    else:
        heads_first = lambda a: (None if a is None
                                 else jnp.moveaxis(a, 1, 0)[:, :, None])
        out = jax.lax.map(
            lambda xs: attend(*xs)[:, 0],
            tuple(heads_first(a) for a in
                  (qg, k_cache, v_cache, k_scale, v_scale)))
        out = jnp.moveaxis(out, 0, 1)
    # (B, Hkv, G, Tq, D) -> (B, Tq, Hq, D)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Tq, Hq, D)


def ring_positions(last: jnp.ndarray, ring: int) -> jnp.ndarray:
    """The absolute position each index of a ring of ``ring`` positions
    holds once position ``last`` (a scalar, or (B,) per row) is written:
    the newest position congruent to the index, negative where the ring has
    not been written that far. A buffer as long as the sequence is the ring
    that never wraps: index i holds i, and indices past ``last`` read
    negative. -> (ring,) or (B, ring)."""
    last = jnp.asarray(last)[..., None]
    return last - (last - jnp.arange(ring)) % ring


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

def causal_attention(
    q: jnp.ndarray,               # (B, Tq, Hq, D)
    k: jnp.ndarray,               # (B, Tkv, Hkv, D)
    v: jnp.ndarray,               # (B, Tkv, Hkv, D)
    *,
    q_positions: Optional[jnp.ndarray] = None,   # (Tq,) or (B, Tq) absolute pos
    kv_length: Optional[jnp.ndarray] = None,     # scalar or (B,): valid kv prefix
    dropout_rate: float = 0.0,
    dropout_rng: Optional[jax.Array] = None,
    deterministic: bool = True,
    impl: str = "auto",
    block_q: int = 256,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Scaled dot-product attention with causal masking and GQA
    (``window``: each position attends to the last ``window`` positions,
    itself included; the exact xla path serves it).

    For training, call with q=k=v lengths equal and no kv_length. For
    cached decode, pass the full cache as k/v, absolute ``q_positions`` and
    ``kv_length`` = number of valid cache entries.
    """
    B, Tq, Hq, D = q.shape
    _, Tkv, Hkv, _ = k.shape
    assert Hq % Hkv == 0, "query heads must be a multiple of kv heads"

    dropout_active = dropout_rate > 0.0 and not deterministic
    chosen = _resolve_impl(impl, Tq, Tkv, D, q_positions, kv_length,
                           dropout_active, block_q, window)

    if chosen == "fused":
        from building_llm_from_scratch_tpu.ops.fused_attention import (
            fused_causal_attention,
        )

        return fused_causal_attention(
            q, k, v,
            dropout_rate=dropout_rate if dropout_active else 0.0,
            dropout_rng=dropout_rng)
    if chosen == "pallas":
        if dropout_active:
            raise ValueError(
                "attn_impl='pallas' does not support attention dropout; "
                "use 'flash' or set drop_rate=0")
        return _pallas_flash_attention(q, k, v)
    if chosen == "flash":
        bq = min(block_q, Tq)
        while Tq % bq:                   # largest divisor <= block_q (static)
            bq -= 1
        return _flash_attention_xla(q, k, v, block_q=bq,
                                    dropout_rate=dropout_rate,
                                    dropout_rng=dropout_rng,
                                    deterministic=deterministic)
    return _xla_attention(q, k, v, q_positions=q_positions,
                          kv_length=kv_length, dropout_rate=dropout_rate,
                          dropout_rng=dropout_rng,
                          deterministic=deterministic, window=window)
