"""The shared transformer core.

ONE parameterized implementation covers every model family the reference
builds with three separate module stacks:
  - GPTModel          (reference Models/GPT2/GPT2.py:91-124)
  - Llama2Model       (reference Models/Llama/Llama2.py:156-190)
  - Llama3Model       (reference Models/Llama/Llama3.py:185-204)

The architecture knobs live in ``ModelConfig`` (configs.py); the parameters
are a plain pytree; the forward pass is a pure function usable under ``jit``
/ ``pjit`` / ``grad`` / ``shard_map``.

TPU-first design choices (vs. the reference's nn.Module stacks):
  - all L transformer blocks are STACKED along a leading layer axis and
    executed with ``jax.lax.scan`` — one compiled block body instead of L
    unrolled copies (compile time O(1) in depth, XLA-friendly);
  - ``--use_actv_ckpt`` maps to ``jax.checkpoint`` (remat) of the scanned
    block body (reference: torch checkpoint_sequential, GPT2.py:115-116);
  - no (ctx, ctx) causal-mask buffer; masking is positional iota inside the
    attention kernel;
  - KV-cache decode path with static shapes for jitted autoregressive
    generation (the reference re-runs the full forward per token,
    generate.py:36-45);
  - dropout uses explicit PRNG keys, folded per layer.

Parameter tree layout (linear weights stored (in, out), applied as x @ w):

  params = {
    "tok_emb":   {"weight": (V, D)},
    "pos_emb":   {"weight": (T, D)}          # learned positions (GPT-2) only
    "blocks": {
      "norm1":   {"scale": (L, D)[, "bias": (L, D)]},
      "attn":    {"wq": (L, D, Hq*hd), "wk": (L, D, Hkv*hd),
                  "wv": (L, D, Hkv*hd), "wo": (L, Hq*hd, D)
                  [, "bq", "bk", "bv" , "bo"]},
      "norm2":   {"scale": (L, D)[, "bias"]},
      "mlp":     {"up": (L, D, F), "down": (L, F, D)
                  [, "gate": (L, D, F)]      # SwiGLU (LLaMA)
                  [, "b_up": (L, F), "b_down": (L, D)]},
    },
    "final_norm": {"scale": (D,)[, "bias": (D,)]},
    "head":      {"weight": (D, V)},         # absent when tie_embeddings
  }

A ``parallel_block`` config has no ``norm2``; a sparse one (``cfg.is_moe``)
has ``blocks["moe"]`` (models/moe.py) in place of ``blocks["mlp"]``. Layers
of unlike KINDS (``cfg.layer_kinds``: 'sliding' | 'full') share one leaf
shape, so they stack like any others; the kind decides the layer's mask,
its positions and, in the slot cache, whether its buffer is a ring.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from building_llm_from_scratch_tpu.configs import ModelConfig
from building_llm_from_scratch_tpu.models.lora import apply_lora, lora_delta
from building_llm_from_scratch_tpu.models.moe import init_moe_params, moe_ffn
from building_llm_from_scratch_tpu.ops.attention import (
    causal_attention,
    decode_attention,
    ring_positions,
)
from building_llm_from_scratch_tpu.ops.activations import gelu, silu
from building_llm_from_scratch_tpu.ops.norms import layernorm, rmsnorm
from building_llm_from_scratch_tpu.ops.rope import (
    apply_rope,
    precompute_rope_params,
)

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# LoRA adapter application (merge-free; models/lora.apply_lora is the
# shared projection helper)
#
# Two shapes of "adapter" flow through the forward passes:
#   - a single unmerged adapter tree (``lora=`` on forward/forward_with_
#     cache): every batch row shares one {"A","B"} node per projection —
#     the trainer's eval-sampling path;
#   - a per-row adapter POOL (``adapter=`` on the slot-batched serving
#     functions): stacked ``(n_adapters_max, ...)`` A/B leaves plus a
#     per-row ``ids`` vector — Punica/S-LoRA-style BGMV, where adapter
#     identity is DATA, so hot-loading adapters never recompiles and one
#     decode program serves arbitrary adapter mixes (id −1 = base model,
#     exact zero delta).
# ---------------------------------------------------------------------------

def _block_adp(lb: Params, s) -> Params:
    """Per-layer adapter argument for ``_block``/the slot loops: the lora
    blocks node (attn/mlp, each projection a {"A","B"}) + the scale."""
    return {"attn": dict(lb["attn"], s=s), "mlp": dict(lb["mlp"], s=s)}


def _aligned_block_adp(lb: Params, s, rows_per_job: int) -> Params:
    """Per-layer adapter argument for the SLOT-ALIGNED pool path: each
    projection node routes through ``models/lora.aligned_lora_delta``
    (one application per job block) instead of the per-row gather. ``lb``
    leaves are the layer's stacked (J, in, r)/(J, r, out) pool panes."""
    out = {}
    for group in ("attn", "mlp"):
        out[group] = {name: {"aligned": (n["A"], n["B"], s, rows_per_job)}
                      for name, n in lb[group].items()}
        out[group]["s"] = None
    return out


def _adapter_rows(pool: Params, scaling: jnp.ndarray, ids: jnp.ndarray):
    """BGMV gather: per-row adapter matrices from the stacked pool.

    ``pool`` mirrors the lora tree with a leading ``(n_adapters_max,)``
    axis on every leaf; ``ids`` (B,) int32 selects one pool row per batch
    row (−1 = base model: the index clamps into range but the gathered
    scale is forced to 0, so the delta is exactly zero regardless of what
    the clamped row holds)."""
    idx = jnp.clip(ids.astype(jnp.int32), 0, scaling.shape[0] - 1)
    s = jnp.where(ids >= 0, jnp.take(scaling, idx, axis=0), 0.0)
    rows = jax.tree_util.tree_map(lambda a: jnp.take(a, idx, axis=0), pool)
    return rows, s


def unstack_lora_blocks(lora: Params, cfg: ModelConfig) -> list:
    """Per-layer views of a stacked lora tree's ``blocks`` node — the
    adapter twin of ``unstack_blocks`` (hoisted out of sampling loops for
    the same re-layout reason)."""
    return [
        jax.tree_util.tree_map(lambda a, l=l: a[l], lora["blocks"])
        for l in range(cfg.n_layers)
    ]


@jax.named_scope("head")
def _head_logits(x: jnp.ndarray, w: jnp.ndarray,
                 node: Optional[Params] = None,
                 scaling=None) -> jnp.ndarray:
    """LM-head projection (+ optional unmerged LoRA delta). The base
    einsum is byte-for-byte the historical head path; the delta rides on
    top in fp32 like ``apply_lora``."""
    logits = jnp.einsum("btd,dv->btv", x, w,
                        preferred_element_type=jnp.float32)
    if node is None:
        return logits
    return logits + lora_delta(x, node, scaling).astype(jnp.float32)


def _logits(params: Params, x: jnp.ndarray, node: Optional[Params] = None,
            scaling=None) -> jnp.ndarray:
    """The output head: its own leaf, or the embedding table read the other
    way (``tie_embeddings``: no ``head`` leaf, and no adapter on it)."""
    if "head" in params:
        return _head_logits(x, params["head"]["weight"], node, scaling)
    with jax.named_scope("head"):
        return jnp.einsum("btd,vd->btv", x, params["tok_emb"]["weight"],
                          preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------

def _linear_init(key, in_dim: int, out_dim: int, dtype, n_layers=None):
    """Truncated-normal fan-in init (GPT-2-style 0.02-capped)."""
    std = min(0.02, in_dim ** -0.5)
    lead = (() if n_layers is None else
            n_layers if isinstance(n_layers, tuple) else (n_layers,))
    shape = lead + (in_dim, out_dim)
    return (jax.random.truncated_normal(key, -3.0, 3.0, shape, jnp.float32)
            * std).astype(dtype)


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Build the full parameter pytree for ``cfg``."""
    L, D, V, T = cfg.n_layers, cfg.emb_dim, cfg.vocab_size, cfg.context_length
    hd, Hq, Hkv, F = cfg.head_dim, cfg.n_heads, cfg.n_kv_groups, cfg.hidden_dim
    dt = cfg.jax_dtype

    keys = jax.random.split(key, 16)
    zeros = lambda *shape: jnp.zeros(shape, dt)
    ones = lambda *shape: jnp.ones(shape, dt)

    attn: Params = {
        "wq": _linear_init(keys[0], D, Hq * hd, dt, L),
        "wk": _linear_init(keys[1], D, Hkv * hd, dt, L),
        "wv": _linear_init(keys[2], D, Hkv * hd, dt, L),
        "wo": _linear_init(keys[3], Hq * hd, D, dt, L),
    }
    if cfg.qkv_bias:
        attn.update(bq=zeros(L, Hq * hd), bk=zeros(L, Hkv * hd),
                    bv=zeros(L, Hkv * hd))
    if cfg.attn_out_bias:
        attn["bo"] = zeros(L, D)

    def norm(n_layers=None):
        n: Params = {"scale": ones(n_layers, D) if n_layers else ones(D)}
        if cfg.norm_bias:
            n["bias"] = zeros(n_layers, D) if n_layers else zeros(D)
        return n

    blocks: Params = {"norm1": norm(L), "attn": attn}
    if not cfg.parallel_block:
        blocks["norm2"] = norm(L)
    if cfg.is_moe:
        blocks["moe"] = init_moe_params(cfg, keys[10], _linear_init)
    else:
        mlp: Params = {
            "up": _linear_init(keys[4], D, F, dt, L),
            "down": _linear_init(keys[5], F, D, dt, L),
        }
        if cfg.activation == "swiglu":
            mlp["gate"] = _linear_init(keys[6], D, F, dt, L)
        if cfg.mlp_bias:
            mlp.update(b_up=zeros(L, F), b_down=zeros(L, D))
        blocks["mlp"] = mlp
    params: Params = {
        "tok_emb": {"weight": (jax.random.normal(keys[7], (V, D), jnp.float32)
                               * 0.02).astype(dt)},
        "blocks": blocks,
        "final_norm": norm(),
    }
    if not cfg.tie_embeddings:
        params["head"] = {"weight": _linear_init(keys[8], D, V, dt)}
    if cfg.positional == "learned":
        params["pos_emb"] = {"weight": (jax.random.normal(keys[9], (T, D),
                                                          jnp.float32)
                                        * 0.02).astype(dt)}
    return params


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------

def _norm(cfg: ModelConfig, p: Params, x: jnp.ndarray) -> jnp.ndarray:
    if cfg.norm == "rmsnorm":
        return rmsnorm(x, p["scale"], eps=cfg.rmsnorm_eps)
    return layernorm(x, p["scale"], p.get("bias"), eps=cfg.layernorm_eps)


@jax.named_scope("mlp")
def _mlp(cfg: ModelConfig, p: Params, x: jnp.ndarray,
         tp_axis: Optional[str] = None,
         adp: Optional[Params] = None) -> jnp.ndarray:
    """MLP. ``tp_axis``: Megatron column-parallel up/gate (+ their biases,
    which are feature-sharded like the weights) and row-parallel down with
    an explicit psum; the replicated down bias is added once after.
    ``adp``: optional unmerged LoRA nodes per projection (+ ``"s"`` scale;
    does not compose with tp — adapters see the FULL weight)."""
    s = adp["s"] if adp is not None else None
    n = (lambda name: adp.get(name)) if adp is not None else (lambda _: None)
    if cfg.activation == "swiglu":
        # silu(gate(x)) * up(x) -> down   (reference common_components.py:95-124)
        g = checkpoint_name(apply_lora(x, p["gate"], n("gate"), s),
                            "gate_out")
        u = checkpoint_name(apply_lora(x, p["up"], n("up"), s), "up_out")
        h = apply_lora(silu(g) * u, p["down"], n("down"), s)
        if tp_axis is not None:
            h = jax.lax.psum(h, tp_axis)
        return h
    h = apply_lora(x, p["up"], n("up"), s)
    if "b_up" in p:
        h = h + p["b_up"]
    h = checkpoint_name(h, "up_out")
    h = gelu(h)
    h = apply_lora(h, p["down"], n("down"), s)
    if tp_axis is not None:
        h = jax.lax.psum(h, tp_axis)
    if "b_down" in p:
        h = h + p["b_down"]
    return h


def _use_fused_dropout(shape) -> bool:
    if jax.default_backend() != "tpu":
        return False
    from building_llm_from_scratch_tpu.ops.fused_dropout import supports_shape

    return supports_shape(shape)


def _use_fused_decode(cfg: ModelConfig, cache: Params, Tq: int) -> bool:
    """THE rule for the pallas fused append+attend decode kernel
    (ops/decode_step.fused_decode_step), shared by one-shot
    ``forward_with_cache`` and the engine's ``decode_slots``: opt-in with
    ``BLLM_FUSED_DECODE=1``, on TPU, for unquantized caches (int8 caches
    keep the XLA path: ``decode_attention`` folds the scale sidecars into
    its einsums, the kernel has no dequant pass) of a shape
    ``supports_shape`` admits.

    Off by default, on the evidence there is. The kernel compiles for
    v5e and matches the XLA path alone (chip_smoke.py's kernels phase),
    but inside a whole GPT2-124M decode program — six layers or more —
    the compiler assigns whole (S, Hkv, Tmax, hd) cache arrays to VMEM
    beside the kernel's own scope and refuses the program ("scoped
    allocation 24.00M, limit 16.00M"; with a raised ``vmem_limit_bytes``
    it only assigns more). The engine took this kernel unconditionally
    before it had ever been compiled for a chip. For one shared scalar
    length the one A/B on record measured it 3% slower on GPT2-124M bs8.
    ROADMAP S1/S5 own the re-measurement."""
    import os

    from building_llm_from_scratch_tpu.ops.decode_step import supports_shape

    pane = cache["k"][0]                       # (B, Hkv, Tmax, hd)
    return (os.environ.get("BLLM_FUSED_DECODE", "0") == "1"
            and jax.default_backend() == "tpu"
            and not _cache_quantized(cache)
            and supports_shape(Tq, pane.shape[2], cfg.head_dim,
                               Hkv=cfg.n_kv_groups, Hq=cfg.n_heads,
                               itemsize=pane.dtype.itemsize))


def _dropout(x: jnp.ndarray, rate: float, rng: Optional[jax.Array],
             deterministic: bool) -> jnp.ndarray:
    if rate <= 0.0 or deterministic:
        return x
    if _use_fused_dropout(x.shape):
        from building_llm_from_scratch_tpu.ops.fused_dropout import (
            fused_dropout,
        )

        return fused_dropout(x, rate, rng)
    keep = jax.random.bernoulli(rng, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), jnp.zeros_like(x))


def _residual_dropout(x: jnp.ndarray, h: jnp.ndarray, rate: float,
                      rng: Optional[jax.Array],
                      deterministic: bool) -> jnp.ndarray:
    """x + dropout(h): the pre-norm residual update (reference
    GPT2.py:79-87). On TPU the mask is drawn in-kernel (fused_dropout.py)
    so it is never generated twice or stored for the backward."""
    if rate <= 0.0 or deterministic:
        return x + h
    if _use_fused_dropout(h.shape):
        from building_llm_from_scratch_tpu.ops.fused_dropout import (
            fused_dropout_add,
        )

        return fused_dropout_add(x, h, rate, rng)
    return x + _dropout(h, rate, rng, deterministic)


@jax.named_scope("attention")
def _qkv_proj(cfg: ModelConfig, p: Params, x: jnp.ndarray,
              rope, positions, adp: Optional[Params] = None):
    """Shared q/k/v projection (+biases, head reshape, RoPE) — the single
    source of truth for the attention parameterization, used by BOTH the
    training path (_attention) and the KV-cache decode body
    (forward_with_cache); divergence here would silently break decode.
    ``adp``: optional unmerged LoRA nodes (wq/wk/wv + ``"s"``), applied
    BEFORE the head reshape and RoPE — exactly where a merged weight's
    delta would land."""
    B, Tq, _ = x.shape
    hd = cfg.head_dim
    s = adp["s"] if adp is not None else None
    n = (lambda name: adp.get(name)) if adp is not None else (lambda _: None)
    q = apply_lora(x, p["wq"], n("wq"), s)
    k = apply_lora(x, p["wk"], n("wk"), s)
    v = apply_lora(x, p["wv"], n("wv"), s)
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    # head counts come from the PROJECTED widths, not the config: under
    # tensor parallelism inside a shard_map each device holds Hq/ntp (and
    # Hkv/ntp) head slices of wq/wk/wv and attends over them locally
    q = q.reshape(B, Tq, -1, hd)
    k = k.reshape(B, Tq, -1, hd)
    v = v.reshape(B, Tq, -1, hd)
    if rope is not None:
        cos, sin = rope
        q = apply_rope(q, cos, sin, positions, cfg.rope_interleaved)
        k = apply_rope(k, cos, sin, positions, cfg.rope_interleaved)
    # names for the selective-save remat policy (forward_hidden): post-RoPE
    # q/k/v are saved so the backward neither re-projects nor re-rotates
    q = checkpoint_name(q, "q")
    k = checkpoint_name(k, "k")
    v = checkpoint_name(v, "v")
    return q, k, v


@jax.named_scope("attention")
def _attn_out_proj(p: Params, out: jnp.ndarray, B: int, Tq: int,
                   tp_axis: Optional[str] = None,
                   adp: Optional[Params] = None) -> jnp.ndarray:
    """Output projection; with ``tp_axis`` (Megatron row-parallel wo inside
    a shard_map) the partial products psum over the model axis and the
    bias — replicated, not sharded — is added exactly once AFTER."""
    out = apply_lora(out.reshape(B, Tq, -1), p["wo"],
                     adp.get("wo") if adp is not None else None,
                     adp["s"] if adp is not None else None)
    if tp_axis is not None:
        out = jax.lax.psum(out, tp_axis)
    if "bo" in p:
        out = out + p["bo"]
    return out


def _attention(cfg: ModelConfig, p: Params, x: jnp.ndarray,
               rope: Optional[Tuple[jnp.ndarray, jnp.ndarray]],
               positions: Optional[jnp.ndarray],
               cache_kv: Optional[Tuple[jnp.ndarray, jnp.ndarray]],
               cache_len: Optional[jnp.ndarray],
               rng: Optional[jax.Array], deterministic: bool,
               sp_mesh=None, sp_inside=None, tp_axis=None, adp=None,
               window: Optional[int] = None):
    """Per-block attention; returns (out, new_cache_kv)."""
    if window is not None and (sp_mesh is not None or sp_inside is not None):
        raise ValueError("the ring schedule of sequence parallelism has no "
                         "window term: a model with 'sliding' layers "
                         "trains without --sp")
    B, Tq, D = x.shape
    hd = cfg.head_dim

    q, k, v = _qkv_proj(cfg, p, x, rope, positions, adp=adp)

    new_cache = None
    if cache_kv is not None:
        # write current k/v into the cache at offset cache_len, attend to the
        # full valid prefix
        ck, cv = cache_kv                        # (B, Tmax, Hkv, hd)
        ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype),
                                          (0, cache_len, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype),
                                          (0, cache_len, 0, 0))
        new_cache = (ck, cv)
        k, v = ck, cv
        kv_length = cache_len + Tq
        q_positions = positions
    else:
        kv_length = None
        q_positions = None

    if sp_inside is not None and cache_kv is None:
        # already INSIDE a shard_map that mapped the seq axis (the explicit
        # bf16_hybrid step): run the local ring body directly
        from building_llm_from_scratch_tpu.ops.ring_attention import (
            _ring_attention_local,
        )
        from building_llm_from_scratch_tpu.parallel.mesh import DATA_AXIS

        axis_name, axis_size = sp_inside
        dropout_on = cfg.drop_rate > 0.0 and not deterministic
        out = _ring_attention_local(
            q, k, v, axis_name=axis_name, axis_size=axis_size,
            scale=1.0 / float(hd) ** 0.5,
            dropout_rate=cfg.drop_rate if dropout_on else 0.0,
            dropout_rng=rng if dropout_on else None,
            shard_fold_axes=(DATA_AXIS,))
    elif sp_mesh is not None and cache_kv is None:
        # sequence parallelism: the ring schedule owns the communication;
        # attention dropout folds shard indices into the mask PRNG (the
        # round-3 restriction is lifted — ring_attention.py)
        from building_llm_from_scratch_tpu.ops.ring_attention import (
            ring_causal_attention,
        )

        dropout_on = cfg.drop_rate > 0.0 and not deterministic
        out = ring_causal_attention(
            q, k, v, sp_mesh,
            dropout_rate=cfg.drop_rate if dropout_on else 0.0,
            dropout_rng=rng if dropout_on else None)
    else:
        with _attention_scope(window is not None):
            out = causal_attention(
                q, k, v,
                q_positions=q_positions,
                kv_length=kv_length,
                dropout_rate=cfg.drop_rate,
                dropout_rng=rng,
                deterministic=deterministic,
                impl=cfg.attn_impl,
                window=window,
            )
    out = checkpoint_name(out, "attn_out")
    out = _attn_out_proj(p, out, B, Tq, tp_axis=tp_axis, adp=adp)
    return out, new_cache


def _layer_rope(cfg: ModelConfig, rope, kind: str):
    """The tables a layer of this kind rotates by, or None."""
    return rope if kind == "sliding" or cfg.full_layers_rope else None


def _layer_window(cfg: ModelConfig, kind: str) -> Optional[int]:
    return cfg.sliding_window if kind == "sliding" else None


def _attention_scope(windowed: bool):
    """The span a layer's attention core runs under, by its kind."""
    return jax.named_scope("window_attention" if windowed else "attention")


def _ffn(cfg: ModelConfig, p: Params, h: jnp.ndarray, *, tp_axis=None,
         adp: Optional[Params] = None, live: Optional[jnp.ndarray] = None,
         expert_rows: Optional[list] = None) -> jnp.ndarray:
    """A block's feed-forward on its normed input: the dense MLP, or the
    expert layer (models/moe.py). ``live`` (B, T) bool marks the real rows
    for the experts; ``expert_rows``, where given, collects each sparse
    layer's rows per held expert."""
    if not cfg.is_moe:
        return _mlp(cfg, p["mlp"], h, tp_axis=tp_axis,
                    adp=adp["mlp"] if adp is not None else None)
    if tp_axis is not None or adp is not None:
        raise ValueError("the expert layer has no tensor-parallel split and "
                         "takes no LoRA adapter")
    out, rows = moe_ffn(cfg, p["moe"], h, live)
    if expert_rows is not None:
        expert_rows.append(rows)
    return out


def _add_branches(cfg: ModelConfig, p: Params, x: jnp.ndarray,
                  h: jnp.ndarray, attn_out: jnp.ndarray, **ffn_kw
                  ) -> jnp.ndarray:
    """The residual updates of the slot loops' blocks, from a block's input
    ``x``, its first norm ``h`` and its projected attention output. Serial:
    the feed-forward reads a second norm of ``x + attention``. Parallel
    (``cfg.parallel_block``): it reads ``h`` too, and both add to ``x``."""
    if cfg.parallel_block:
        return x + attn_out + _ffn(cfg, p, h, **ffn_kw)
    x = x + attn_out
    return x + _ffn(cfg, p, _norm(cfg, p["norm2"], x), **ffn_kw)


def _block(cfg: ModelConfig, p: Params, x: jnp.ndarray,
           rope, positions, cache_kv, cache_len, rng, deterministic,
           sp_mesh=None, sp_inside=None, tp_axis=None, adp=None,
           kind: str = "full"):
    """Pre-norm transformer block (reference GPT2.py:68-88, Llama3.py:159-181)
    of one ``kind`` ('sliding': windowed attention; ``_layer_rope`` says
    which kinds rotate). With ``cfg.parallel_block`` attention and
    feed-forward both read the one norm and both add to the residual.

    ``tp_axis``: Megatron tensor parallelism INSIDE a shard_map — the
    caller feeds head-/feature-sharded wq/wk/wv/up(/gate) and input-sharded
    wo/down slices; this block attends over its local heads and psums the
    two row-parallel projections over the named axis (used by the pipeline
    schedule for pp x tp; the GSPMD tp path shards the same rule table
    outside shard_map instead)."""
    if rng is not None:
        r_attn, r_res1, r_res2 = jax.random.split(rng, 3)
        if tp_axis is not None and not deterministic:
            # attention-weight masks cover LOCAL head slices — fold the
            # model-shard index so global heads get iid masks. Residual
            # dropout keys stay UNfolded: they apply to the replicated
            # post-psum activations, which must mask identically on every
            # model shard or the replicas diverge.
            r_attn = jax.random.fold_in(r_attn,
                                        jax.lax.axis_index(tp_axis))
    else:
        r_attn = r_res1 = r_res2 = None
    n1 = _norm(cfg, p["norm1"], x)
    h, new_cache = _attention(cfg, p["attn"], n1,
                              _layer_rope(cfg, rope, kind), positions,
                              cache_kv, cache_len,
                              r_attn, deterministic, sp_mesh=sp_mesh,
                              sp_inside=sp_inside, tp_axis=tp_axis,
                              adp=adp["attn"] if adp is not None else None,
                              window=_layer_window(cfg, kind))
    if cfg.parallel_block:
        h = h + _ffn(cfg, p, n1, tp_axis=tp_axis, adp=adp)
        return (_residual_dropout(x, h, cfg.drop_rate, r_res1, deterministic),
                new_cache)
    x = _residual_dropout(x, h, cfg.drop_rate, r_res1, deterministic)
    x = checkpoint_name(x, "resid_mid")
    h = _ffn(cfg, p, _norm(cfg, p["norm2"], x), tp_axis=tp_axis, adp=adp)
    x = _residual_dropout(x, h, cfg.drop_rate, r_res2, deterministic)
    return x, new_cache


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------

def _train_scan_unroll(cfg: ModelConfig) -> int:
    """Unroll factor for the training layer scan.

    Full unroll on TPU for models up to 24 layers: the rolled scan forces
    XLA to serialize each layer's weight fetches and residual-save DUS
    against the loop step, and the backward copies whole stacked (L,.,.)
    gradient accumulators every iteration (r5 profile: ~8ms/step of pure
    copies on GPT2-124M bs8). Unrolled, weights prefetch across layers and
    grad accumulation becomes static-offset updates: measured 82.9k ->
    97.5k tok/s/chip (+18%) on the bs8 headline, +2.4% on the rematted
    LLaMA3.2-1B LoRA config. Deeper models keep the O(1)-compile scan
    (compile time for 36+ unrolled big-layer graphs grows superlinearly);
    CPU (test) backend always scans. Override: BLLM_TRAIN_UNROLL=<n>."""
    import os

    env = os.environ.get("BLLM_TRAIN_UNROLL")
    if env:
        return int(env)
    if jax.default_backend() == "tpu" and cfg.n_layers <= 24:
        return cfg.n_layers
    return 1


def _rope_tables(cfg: ModelConfig):
    if not cfg.uses_rope:
        return None
    return precompute_rope_params(
        cfg.head_dim,
        theta_base=cfg.rope_base,
        context_length=cfg.context_length,
        rope_scaling=cfg.rope_scaling,
    )


def _embed(cfg: ModelConfig, params: Params, tokens: jnp.ndarray,
           positions: Optional[jnp.ndarray], rng, deterministic) -> jnp.ndarray:
    x = jnp.take(params["tok_emb"]["weight"], tokens, axis=0)
    if cfg.positional == "learned":
        T = tokens.shape[1]
        pos = positions if positions is not None else jnp.arange(T)
        x = x + jnp.take(params["pos_emb"]["weight"], pos, axis=0)
    return _dropout(x, cfg.drop_rate, rng, deterministic)


def forward_hidden(params: Params, cfg: ModelConfig, tokens: jnp.ndarray, *,
                   rng: Optional[jax.Array] = None,
                   deterministic: bool = True,
                   sp_mesh=None, sp_inside=None,
                   lora: Optional[Params] = None,
                   lora_scaling=1.0,
                   adapter: Optional[Params] = None) -> jnp.ndarray:
    """Forward up to (and including) the final norm — the (B, T, D) hidden
    states BEFORE the output head. The training loss path consumes this
    directly via ops/softmax_xent.py so (B, T, V) fp32 logits never
    materialize; ``forward`` below adds the head for logits consumers
    (generation, tests, golden-logit parity).

    ``lora``: optional unmerged adapter tree (models/lora.py layout),
    applied at every adapted projection via ``apply_lora`` — the
    merge-free path serving shares. Not composable with tp/sp sharding
    (adapters multiply against the full weights).

    ``adapter``: optional per-ROW adapter pool ``{"pool": stacked
    (n, ...) lora tree, "scaling": (n,), "ids": (B,)}`` — the serving
    slot paths' BGMV gather applied to the full-sequence TRAINING
    forward: each batch row multiplies against its own gathered A/B
    (id −1 = zeroed scale = exact base path), so k finetune jobs'
    rows share ONE base forward/backward (training/lora_fusion.py).
    Job identity is data: changing ids never recompiles. Mutually
    exclusive with ``lora``; same tp/sp caveat."""
    if lora is not None and adapter is not None:
        raise ValueError("forward_hidden: pass lora= (one shared adapter) "
                         "or adapter= (per-row pool), not both")
    L = cfg.n_layers
    rope = _rope_tables(cfg)
    if rng is None:
        emb_rng = None
        layer_rngs = jnp.zeros((L, 2), jnp.uint32)
        deterministic = True
    else:
        emb_rng, blocks_rng = jax.random.split(rng)
        layer_rngs = jax.random.split(blocks_rng, L)

    if sp_inside is not None:
        # inside a seq-mapped shard_map, ``tokens`` is this shard's T/S
        # block: RoPE / learned positions must use the GLOBAL offsets
        # my*Tl..(my+1)*Tl-1, not 0..Tl-1
        axis_name, _ = sp_inside
        Tl = tokens.shape[1]
        positions = jax.lax.axis_index(axis_name) * Tl + jnp.arange(Tl)
    else:
        positions = None

    x = _embed(cfg, params, tokens, positions, emb_rng, deterministic)

    aligned_R = (adapter.get("rows_per_job")
                 if adapter is not None else None)
    if adapter is not None and aligned_R is not None:
        # SLOT-ALIGNED pool application (training/lora_fusion.py): the
        # batch's rows are job-contiguous (row block [j*R, (j+1)*R) is
        # job j — the stack_fleet_batch layout), so there is nothing to
        # gather: re-lead the stacked pool itself with the layer axis
        # and apply each job's adapter ONCE per block via
        # models/lora.aligned_lora_delta. Replaces the per-row gather's
        # rows_per_job-fold A/B duplication (and its scatter-add
        # backward) for this layout; ids are not needed — an inactive
        # slot's zero scaling zeroes its block's delta exactly.
        if tokens.shape[0] % int(aligned_R):
            raise ValueError(
                f"aligned adapter: batch rows {tokens.shape[0]} not a "
                f"multiple of rows_per_job={aligned_R}")
        row_blocks = jax.tree_util.tree_map(
            lambda a: jnp.moveaxis(a, 1, 0), adapter["pool"]["blocks"])
        row_s = adapter["scaling"]
    elif adapter is not None:
        # BGMV gather ONCE for the whole batch (the serving-path math,
        # _adapter_rows) — blocks subtree only; the head gathers
        # separately in forward() (gathering the whole pool here would
        # eagerly materialize discarded (B, r, V) head rows on
        # non-jitted calls). Gathered leaves are (B, L, in, r) —
        # re-lead with the layer axis so the scan slices each layer's
        # (B, in, r) per-row matrices
        rows, row_s = _adapter_rows(adapter["pool"]["blocks"],
                                    adapter["scaling"], adapter["ids"])
        row_blocks = jax.tree_util.tree_map(
            lambda a: jnp.moveaxis(a, 1, 0), rows)
    else:
        row_blocks = row_s = None

    def one_layer(carry, layer, kind):
        if lora is not None:
            p, lrng, lb = layer
            adp = _block_adp(lb, lora_scaling)
        elif adapter is not None:
            p, lrng, lb = layer
            adp = (_aligned_block_adp(lb, row_s, int(aligned_R))
                   if aligned_R is not None else _block_adp(lb, row_s))
        else:
            p, lrng = layer
            adp = None
        r = None if deterministic else lrng
        y, _ = _block(cfg, p, carry, rope, positions, None, None, r,
                      deterministic, sp_mesh=sp_mesh, sp_inside=sp_inside,
                      adp=adp, kind=kind)
        return y

    # the scan runs over PERIODS of layers: one layer where all are of one
    # kind, else ``cfg.layer_kinds`` unlike layers in a row, their stacked
    # leaves re-led (L, ...) -> (L / P, P, ...)
    kinds = cfg.layer_kinds or ("full",)
    P = len(kinds)

    def body(carry, period):
        if P == 1:
            return one_layer(carry, period, kinds[0]), None
        for j, kind in enumerate(kinds):
            carry = one_layer(
                carry, jax.tree_util.tree_map(lambda a: a[j], period), kind)
        return carry, None

    if cfg.use_actv_ckpt:
        body = jax.checkpoint(body, prevent_cse=False)
    else:
        # Selective-save remat (round-5 profile-driven): under plain
        # autodiff XLA saved ~460MB/layer of residuals across the scan
        # (six f32[B,T,D] norm intermediates, four bf16[B,T,4D] MLP
        # temps, q/k/v...) — ~5.5GB written fwd + re-read bwd per
        # GPT2-124M bs8 step. Save ONLY the named tensors (post-RoPE
        # q/k/v, the attention kernel's out+lse, the mid-block residual,
        # the MLP up/gate outputs) and recompute the cheap elementwise
        # chains (norms, GELU/SiLU, residual adds) in the backward: no
        # matmul and no attention-kernel recompute, ~4x less scan-carried
        # HBM traffic.
        # Only the fused kernel names its out+lse residuals
        # (fused_attention._fused_fwd_rule) — under the non-fused impls
        # (xla/flash; CPU tests, explicit --attn_impl) the backward
        # recomputes the attention scores/softmax from the saved q/k/v,
        # flash-style: more VPU work than r4's save-everything, far less
        # memory. The TPU default ('auto' -> fused) is unaffected.
        body = jax.checkpoint(
            body, prevent_cse=False,
            policy=jax.checkpoint_policies.save_only_these_names(
                "q", "k", "v", "attn_raw_out", "attn_lse", "attn_out",
                "resid_mid", "up_out", "gate_out"))

    if lora is not None:
        xs = (params["blocks"], layer_rngs, lora["blocks"])
    elif adapter is not None:
        xs = (params["blocks"], layer_rngs, row_blocks)
    else:
        xs = (params["blocks"], layer_rngs)
    if P > 1:
        xs = jax.tree_util.tree_map(
            lambda a: a.reshape((L // P, P) + a.shape[1:]), xs)
    x, _ = jax.lax.scan(body, x, xs,
                        unroll=max(1, _train_scan_unroll(cfg) // P))
    return _norm(cfg, params["final_norm"], x)


def forward(params: Params, cfg: ModelConfig, tokens: jnp.ndarray, *,
            rng: Optional[jax.Array] = None,
            deterministic: bool = True,
            sp_mesh=None, sp_inside=None,
            lora: Optional[Params] = None, lora_scaling=1.0,
            adapter: Optional[Params] = None) -> jnp.ndarray:
    """Training/eval forward over full sequences.

    tokens: (B, T) int32.  Returns fp32 logits (B, T, V).

    ``sp_mesh``: a Mesh whose ``seq`` axis is > 1 switches attention to the
    ring schedule (ops/ring_attention.py) — sequence parallelism for
    long-context training. Everything else (embeddings, norms, MLPs, loss)
    is token-local, so GSPMD shards it over the seq axis from the batch
    sharding alone; only attention needs the explicit ring.

    ``adapter``: per-row adapter pool (see ``forward_hidden``) — the head
    delta rides per-row gathered head matrices, exactly like
    ``decode_slots``.
    """
    x = forward_hidden(params, cfg, tokens, rng=rng,
                       deterministic=deterministic, sp_mesh=sp_mesh,
                       sp_inside=sp_inside, lora=lora,
                       lora_scaling=lora_scaling, adapter=adapter)
    if adapter is not None and adapter.get("rows_per_job") is not None:
        # slot-aligned head delta: one application per job block (see
        # forward_hidden); rides in fp32 like every head delta
        from building_llm_from_scratch_tpu.models.lora import (
            aligned_lora_delta,
        )

        head = adapter["pool"]["head"]["weight"]
        return _head_logits(x, params["head"]["weight"]) + \
            aligned_lora_delta(
                x, head["A"], head["B"], adapter["scaling"],
                int(adapter["rows_per_job"])).astype(jnp.float32)
    if adapter is not None:
        head_rows, head_s = _adapter_rows(
            {"head": adapter["pool"]["head"]}, adapter["scaling"],
            adapter["ids"])
        return _head_logits(x, params["head"]["weight"],
                            head_rows["head"]["weight"], head_s)
    return _logits(params, x,
                   lora["head"]["weight"] if lora is not None else None,
                   lora_scaling)


# ---------------------------------------------------------------------------
# KV-cache decode path
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch_size: int, max_length: int) -> Params:
    """Allocate a static-shape KV cache: a LIST of per-layer (B, Tmax,
    Hkv, hd) buffers per k/v.

    Per-layer buffers instead of one stacked (L, ...) array (round 5): with
    the stacked cache as a while-loop carry, XLA failed to alias the
    dynamic-update-slice writes and copied the ENTIRE cache twice per
    decoded token (r5 profile: 206us of a 1010us step on GPT2-124M bs8
    Tmax=320 — copy-start/copy-done pairs over the full 47MB). With one
    buffer per layer, each layer's update aliases its own small buffer and
    the other L-1 pass through the carry untouched.

    Layout (B, Hkv, Tmax, hd) — attention-native: ``decode_attention``
    batches its einsums over (B, H), so the cache streams without the
    full-buffer re-layout copies the (B, T, H, D) model layout forced
    through ``causal_attention`` (the r5 profile's other 24
    copies/step).

    Allocation itself lives on ``serving.kvcache.KVCachePolicy.alloc``
    — ONE rule shared with the serving slot cache, so the two can never
    drift (layout, per-layer split, dtype policy). The train/one-shot
    path always uses the default policy (model dtype, no sidecars).
    """
    from building_llm_from_scratch_tpu.serving.kvcache import (
        DEFAULT_POLICY,
    )

    cache = DEFAULT_POLICY.alloc(cfg, batch_size, max_length)
    cache["length"] = jnp.zeros((), jnp.int32)
    return cache


def unstack_blocks(params: Params, cfg: ModelConfig) -> list:
    """Split the stacked (L, ...) block params into a list of per-layer
    trees. The decode loop wants this done ONCE outside the sampling
    while-loop: slicing stacked weights inside the loop made XLA re-layout
    wq/wk/wv copies every decoded token (r5 profile: 123us/step of
    loop-invariant weight transposes)."""
    blocks = params["blocks"]
    if not cfg.is_moe:
        return [jax.tree_util.tree_map(lambda a, l=l: a[l], blocks)
                for l in range(cfg.n_layers)]
    # the routed experts stay stacked, the layer's index beside them: each
    # is sliced inside the conditional that runs it (models/moe.py)
    experts = blocks["moe"]["experts"]
    rest = dict(blocks, moe={k: v for k, v in blocks["moe"].items()
                             if k != "experts"})
    out = []
    for l in range(cfg.n_layers):
        layer = jax.tree_util.tree_map(lambda a, l=l: a[l], rest)
        layer["moe"]["experts"] = dict(experts, layer=l)
        out.append(layer)
    return out


def forward_with_cache(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                       cache: Params,
                       blocks_list: Optional[list] = None,
                       lora: Optional[Params] = None,
                       lora_scaling=1.0,
                       lora_blocks_list: Optional[list] = None
                       ) -> Tuple[jnp.ndarray, Params]:
    """Decode forward: process ``tokens`` (B, Tq) given ``cache`` holding
    ``cache['length']`` valid positions; returns (fp32 logits (B, Tq, V),
    updated cache). Static shapes throughout — jit-friendly.

    The layer loop is a plain Python loop (decode bodies are small; the
    r4 scan-unroll measured +14% over the rolled loop, and the explicit
    loop additionally lets per-layer cache buffers alias — see
    ``init_cache``). Pass ``blocks_list`` (from ``unstack_blocks``) when
    calling inside a sampling loop so the per-layer weight slices are
    hoisted out of it.

    Contract: the caller must ensure ``cache['length'] + Tq <= max_length``
    (the cache allocation). Under jit an overflow cannot raise —
    ``dynamic_update_slice`` would clamp the write offset and silently
    overwrite the newest entries. The generation loop sizes its cache to
    cover the full decode so this never triggers.
    """
    rope = _rope_tables(cfg)
    length = cache["length"]
    B, Tq = tokens.shape
    positions = length + jnp.arange(Tq)

    x = _embed(cfg, params, tokens, positions, None, True)

    if blocks_list is None:
        blocks_list = unstack_blocks(params, cfg)
    if lora is not None and lora_blocks_list is None:
        lora_blocks_list = unstack_lora_blocks(lora, cfg)

    use_fused_step = (not cfg.has_window_layers
                      and _use_fused_decode(cfg, cache, Tq))

    new_k, new_v = [], []
    for l, (p, K, V) in enumerate(zip(blocks_list, cache["k"], cache["v"])):
        adp = (_block_adp(lora_blocks_list[l], lora_scaling)
               if lora_blocks_list is not None else None)
        kind = cfg.layer_kind(l)
        h = _norm(cfg, p["norm1"], x)
        q, k, v = _qkv_proj(cfg, p["attn"], h, _layer_rope(cfg, rope, kind),
                            positions,
                            adp=adp["attn"] if adp is not None else None)
        if use_fused_step:
            # fused in-place append + attention (ops/decode_step.py): the
            # pallas input_output_aliases declaration is what finally stops
            # XLA from copying the whole cache every token (r5 profiles)
            from building_llm_from_scratch_tpu.ops.decode_step import (
                fused_decode_step,
            )

            out, K, V = fused_decode_step(q, k.astype(K.dtype),
                                          v.astype(V.dtype), K, V, length)
        else:
            # (B, Tq, Hkv, hd) -> cache-native (B, Hkv, Tq, hd) — tiny
            K = jax.lax.dynamic_update_slice(
                K, k.transpose(0, 2, 1, 3).astype(K.dtype),
                (0, 0, length, 0))
            V = jax.lax.dynamic_update_slice(
                V, v.transpose(0, 2, 1, 3).astype(V.dtype),
                (0, 0, length, 0))
            # (this cache's buffers are all as long as the sequence, so a
            # 'sliding' layer needs its window in the mask and no ring)
            out = decode_attention(q, K, V, q_positions=positions,
                                   kv_length=length + Tq,
                                   window=_layer_window(cfg, kind))
        new_k.append(K)
        new_v.append(V)
        x = _add_branches(
            cfg, p, x, h,
            _attn_out_proj(p["attn"], out, B, Tq,
                           adp=adp["attn"] if adp is not None else None),
            adp=adp)
    x = _norm(cfg, params["final_norm"], x)
    logits = _logits(params, x,
                     lora["head"]["weight"] if lora is not None else None,
                     lora_scaling)
    new_cache = {"k": new_k, "v": new_v, "length": length + Tq}
    return logits, new_cache


# ---------------------------------------------------------------------------
# Slot-batched decode path (serving/engine.py)
#
# The one-shot decode above shares ONE scalar ``length`` across the whole
# batch — every row is the same request family. The continuous-batching
# engine instead keeps a fixed (n_slots, Tmax) cache where every row is an
# INDEPENDENT request at its own sequence length: prefill writes one
# request's prompt k/v into one slot, and a decode tick advances all active
# slots by one token with per-row positions/lengths. Both are static-shape
# programs: XLA compiles one prefill per prompt-length bucket and exactly
# one decode step.
# ---------------------------------------------------------------------------

def init_slot_cache(cfg: ModelConfig, n_slots: int, max_length: int,
                    policy=None) -> Params:
    """Per-layer (n_slots, Hkv, Tmax, hd) k/v buffers; lengths are host
    state (serving/engine.py), not part of the device cache.

    ``policy`` (serving.kvcache.KVCachePolicy) owns layout and dtype:
    the default reproduces the historical model-dtype cache; the int8
    policy allocates int8 k/v plus fp32 per-position scale sidecars
    (``k_scale``/``v_scale`` lists) that the slot paths below fill on
    append and ``decode_attention`` folds back in."""
    from building_llm_from_scratch_tpu.serving.kvcache import (
        DEFAULT_POLICY,
    )

    return (policy or DEFAULT_POLICY).alloc(cfg, n_slots, max_length)


def _slot_adapter_layers(adapter, cfg: ModelConfig):
    """Gather the batch's per-row adapter matrices from the stacked pool
    and return (per-layer adp dicts, head node, scales) for the slot
    loops. ``adapter`` = {"pool": stacked lora tree, "scaling": (N,),
    "ids": (B,)}; ``None`` -> all-None (exact base path)."""
    if adapter is None:
        return None, None, None
    rows, s = _adapter_rows(adapter["pool"], adapter["scaling"],
                            adapter["ids"])
    # rows["blocks"] leaves are (B, L, in, r): slice each layer's view
    # once, trace-time (the gather itself happened once, above)
    layers = [
        _block_adp(jax.tree_util.tree_map(lambda a, l=l: a[:, l],
                                          rows["blocks"]), s)
        for l in range(cfg.n_layers)
    ]
    return layers, rows["head"]["weight"], s


def _cache_quantized(cache: Params) -> bool:
    return "k_scale" in cache


@jax.named_scope("cache_update")
def _slot_write(cache: Params, name: str, pane: jnp.ndarray, offsets: tuple,
                new: Params) -> None:
    """Append one layer's cache write into the ``new`` accumulator:
    plain dynamic-update-slice for float caches; quantize-then-write
    (int8 codes + the fp32 scale sidecar) for int8 caches. ``pane`` is
    cache-native (1, Hkv, T, hd); ``offsets`` the 4-d DUS origin."""
    buf = cache[name][len(new[name])]
    if _cache_quantized(cache):
        from building_llm_from_scratch_tpu.ops.decode_step import quantize_kv

        codes, scale = quantize_kv(pane)
        sbuf = cache[name + "_scale"][len(new[name + "_scale"])]
        new[name + "_scale"].append(
            jax.lax.dynamic_update_slice(sbuf, scale, offsets))
        pane = codes
    new[name].append(
        jax.lax.dynamic_update_slice(buf, pane.astype(buf.dtype), offsets))


def _new_cache_acc(cache: Params) -> Params:
    return {name: [] for name in cache}


def kv_append_path(cache: Params, Tq: int,
                   backend: Optional[str] = None) -> str:
    """THE rule for how ``_slot_append_kv`` writes a tick's keys and
    values, made once, at trace time, on what the code can observe:
    ``"lane_window"`` (ops/decode_step.lane_window_append: one in-place
    kernel call a layer) on a TPU backend for the shapes
    ``supports_lane_append`` admits, ``"scatter"`` (the per-row
    ``slot_cache_append``) for everything else: verify (Tq = k+1), int8
    caches, ``head_dim`` 128, any other backend. The engine reports the
    name (``stats()["kv_append"]``). ``backend`` is for tests, which
    have no TPU to ask about."""
    from building_llm_from_scratch_tpu.ops.decode_step import (
        supports_lane_append,
    )

    pane = cache["k"][0]                       # (S, Hkv, Tmax, hd)
    _, Hkv, Tmax, hd = pane.shape
    if ((backend or jax.default_backend()) == "tpu"
            and not _cache_quantized(cache)
            and supports_lane_append(Tq, Tmax, hd, Hkv=Hkv,
                                     dtype=pane.dtype)):
        return "lane_window"
    return "scatter"


def decode_attention_path(cache: Params, Tq: int, n_heads: int, *,
                          layer: int = 0, ring: bool = False,
                          backend: Optional[str] = None) -> str:
    """THE rule for how ``decode_slots`` attends in layer ``layer``, the
    sibling of ``kv_append_path`` and made the same way: ``"live_blocks"``
    (ops/decode_step.live_block_attention: one kernel call a layer that
    reads, for each row, only the lane blocks its live positions reach) on
    a TPU backend for the shapes ``supports_live_attention`` admits,
    ``"whole_buffer"`` (``decode_attention``: two reductions over the whole
    buffer, the lengths a mask) for everything else: verify (Tq = k+1),
    int8 caches and their scale sidecars, ``head_dim`` 128, a ring
    (``ring``: the layer attends by ``kv_positions`` / ``window``), any
    other backend. The engine reports the name
    (``stats()["decode_attention"]``). ``backend`` is for tests."""
    from building_llm_from_scratch_tpu.ops.decode_step import (
        supports_live_attention,
    )

    pane = cache["k"][layer]                   # (S, Hkv, Tmax, hd)
    S, Hkv, Tmax, hd = pane.shape
    if ((backend or jax.default_backend()) == "tpu" and not ring
            and not _cache_quantized(cache)
            and supports_live_attention(Tq, Tmax, hd, S=S, Hkv=Hkv,
                                        Hq=n_heads, dtype=pane.dtype)):
        return "live_blocks"
    return "whole_buffer"


@jax.named_scope("cache_update")
def _slot_append_kv(cache: Params, new: Params, l: int,
                    K: jnp.ndarray, V: jnp.ndarray,
                    k: jnp.ndarray, v: jnp.ndarray,
                    lengths: jnp.ndarray):
    """Per-row append of one layer's fresh k/v (model layout (S, Tq,
    Hkv, hd)) into the slot cache at each row's offset, quantizing on
    write under the int8 policy (codes + fp32 scale sidecars). THE one
    inner write rule shared by ``decode_slots`` (Tq=1) and
    ``verify_slots`` (Tq=k+1): the speculative path's bit-parity with
    plain decode depends on these two appends never drifting, and the
    two forms of the write (``kv_append_path``) leave the same bits.
    Returns the appended (K, V) buffers (also pushed onto ``new``)."""
    from building_llm_from_scratch_tpu.ops.decode_step import (
        lane_window_append,
        quantize_kv,
        slot_cache_append,
    )

    kt, vt = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    if kv_append_path(cache, k.shape[1]) == "lane_window":
        K, V = lane_window_append(
            K, V, kt, vt, lengths,
            interpret=jax.default_backend() != "tpu")
    else:
        if _cache_quantized(cache):
            kt, ks = quantize_kv(kt)
            vt, vs = quantize_kv(vt)
            new["k_scale"].append(slot_cache_append(
                cache["k_scale"][l], ks, lengths))
            new["v_scale"].append(slot_cache_append(
                cache["v_scale"][l], vs, lengths))
        K = slot_cache_append(K, kt, lengths)
        V = slot_cache_append(V, vt, lengths)
    new["k"].append(K)
    new["v"].append(V)
    return K, V


def _slot_attend(cfg: ModelConfig, cache: Params, new: Params, l: int,
                 q: jnp.ndarray, K: jnp.ndarray, V: jnp.ndarray,
                 lengths: jnp.ndarray, ring_kw: dict) -> jnp.ndarray:
    """A decode tick's attention in layer ``l``: each row's one query (at
    position ``lengths``) against its own appended prefix of (K, V). The
    two forms (``decode_attention_path``) are the same arithmetic: the
    kernel leaves unread what ``decode_attention`` reads and masks to 0."""
    with _attention_scope(bool(ring_kw)):
        if decode_attention_path(cache, 1, cfg.n_heads, layer=l,
                                 ring=bool(ring_kw)) == "live_blocks":
            from building_llm_from_scratch_tpu.ops.decode_step import (
                live_block_attention,
            )

            return live_block_attention(
                q, K, V, lengths + 1,
                interpret=jax.default_backend() != "tpu")
        return decode_attention(q, K, V, q_positions=lengths[:, None],
                                kv_length=lengths + 1, **ring_kw,
                                **_layer_scales(new, l))


def _layer_scales(cache: Params, l: int, slot: Optional[jnp.ndarray] = None
                  ) -> dict:
    """``decode_attention`` kwargs for layer ``l``'s scale sidecars
    (empty when unquantized). ``slot`` slices one row out for the
    single-slot chunk-prefill path."""
    if not _cache_quantized(cache):
        return {}
    ks, vs = cache["k_scale"][l], cache["v_scale"][l]
    if slot is not None:
        ks = jax.lax.dynamic_slice(ks, (slot, 0, 0, 0), (1,) + ks.shape[1:])
        vs = jax.lax.dynamic_slice(vs, (slot, 0, 0, 0), (1,) + vs.shape[1:])
    return {"k_scale": ks, "v_scale": vs}


# A 'sliding' layer's slot buffer is a RING: position p lives at index
# p mod R, R the buffer's length (``KVCachePolicy.ring_length``: window +
# chunk under chunked prefill, so a chunk written BEFORE it attends has
# overwritten nothing its first query still sees; else the slot's full
# length, the ring that never wraps). Masks are by absolute position
# (``ring_positions``), so an index a shorter request has not reached yet
# reads as never written, whatever a longer request left there.

def _ring_chunk(cfg: ModelConfig, kind: str, R: int, chunk_start, C: int):
    """Where a C-token chunk starting at ``chunk_start`` lands in a layer's
    buffer of R positions, and ``decode_attention``'s ring arguments."""
    if kind != "sliding":
        return chunk_start, {}
    if R % C:
        # chunks start at multiples of C, so with C | R none wraps the end
        raise ValueError(f"a ring of {R} positions is not whole chunks of "
                         f"{C}: sliding_window must be a multiple of the "
                         "prefill chunk")
    last = chunk_start + C - 1
    return chunk_start % R, {
        "kv_positions": ring_positions(jnp.reshape(last, (1,)), R),
        "window": cfg.sliding_window}


def _ring_step(cfg: ModelConfig, kind: str, R: int, lengths: jnp.ndarray):
    """The same for a decode tick: each row appends position ``lengths``."""
    if kind != "sliding":
        return lengths, {}
    return lengths % R, {"kv_positions": ring_positions(lengths, R),
                         "window": cfg.sliding_window}


def prefill_into_slot(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                      prompt_len: jnp.ndarray, slot: jnp.ndarray,
                      cache: Params, blocks_list: Optional[list] = None,
                      adapter: Optional[Params] = None
                      ) -> Tuple[jnp.ndarray, Params]:
    """Run one request's prompt (``tokens`` (1, Tpb), right-padded to its
    length bucket) and write its k/v panes into row ``slot`` of the slot
    cache; returns (last-real-position logits (V,), updated cache).

    Attention here is plain causal self-attention over the prompt itself
    (nothing earlier lives in the slot), with ``kv_length=prompt_len``
    masking the pad keys. Pad-position k/v are ZEROED before the write —
    they used to land as garbage masked only by the engine's host-side
    lengths, which was fine while slot contents stayed request-private;
    prefix panes (serving/kvcache.py) make them shareable state, so
    every cache write must be a deterministic function of the prompt.

    ``adapter``: {"pool", "scaling", "ids" (1,)} — the request's LoRA
    adapter applied unmerged at every adapted projection (id −1 = base).
    The prompt's k/v land in the slot ALREADY adapter-transformed, so
    decode ticks attend to a prefix consistent with the same adapter.
    """
    _, Tpb = tokens.shape
    rope = _rope_tables(cfg)
    positions = jnp.arange(Tpb)
    x = _embed(cfg, params, tokens, positions, None, True)
    if blocks_list is None:
        blocks_list = unstack_blocks(params, cfg)
    adp_layers, head_node, head_s = _slot_adapter_layers(adapter, cfg)
    # pad-position zero mask, model layout (1, Tpb, 1, 1)
    valid = (positions < prompt_len)[None, :, None, None]
    new = _new_cache_acc(cache)
    for l, p in enumerate(blocks_list):
        adp = adp_layers[l] if adp_layers is not None else None
        kind = cfg.layer_kind(l)
        window = _layer_window(cfg, kind)
        if Tpb > cache["k"][l].shape[2]:
            raise ValueError(
                f"a {Tpb}-token prompt bucket does not fit layer {l}'s "
                f"ring of {cache['k'][l].shape[2]} positions: rings are "
                "filled by chunked prefill (KVCachePolicy.prefill_chunk)")
        h = _norm(cfg, p["norm1"], x)
        q, k, v = _qkv_proj(cfg, p["attn"], h, _layer_rope(cfg, rope, kind),
                            positions,
                            adp=adp["attn"] if adp is not None else None)
        with _attention_scope(window is not None):
            out = causal_attention(q, k, v, q_positions=positions,
                                   kv_length=prompt_len, window=window)
        # (1, Tpb, Hkv, hd) -> cache-native (1, Hkv, Tpb, hd) pane at
        # (slot, 0, 0, 0); Tpb <= Tmax by the engine's admission check
        k = jnp.where(valid, k, jnp.zeros((), k.dtype))
        v = jnp.where(valid, v, jnp.zeros((), v.dtype))
        _slot_write(cache, "k", k.transpose(0, 2, 1, 3), (slot, 0, 0, 0),
                    new)
        _slot_write(cache, "v", v.transpose(0, 2, 1, 3), (slot, 0, 0, 0),
                    new)
        x = _add_branches(
            cfg, p, x, h,
            _attn_out_proj(p["attn"], out, 1, Tpb,
                           adp=adp["attn"] if adp is not None else None),
            adp=adp, live=valid[:, :, 0, 0])
    x = _norm(cfg, params["final_norm"], x)
    last = jax.lax.dynamic_slice(x, (0, prompt_len - 1, 0),
                                 (1, 1, x.shape[-1]))
    logits = _logits(params, last, head_node, head_s)
    return logits[0, 0], new


def prefill_chunk_into_slot(params: Params, cfg: ModelConfig,
                            tokens: jnp.ndarray, chunk_start: jnp.ndarray,
                            prompt_len: jnp.ndarray, slot: jnp.ndarray,
                            cache: Params,
                            blocks_list: Optional[list] = None,
                            adapter: Optional[Params] = None
                            ) -> Tuple[jnp.ndarray, Params]:
    """Chunked prefill: process ``tokens`` (1, C) — the prompt span
    [chunk_start, chunk_start + C), right-padded past ``prompt_len`` —
    against row ``slot`` whose positions [0, chunk_start) already hold
    valid KV (earlier chunks, or a copied prefix pane,
    serving/kvcache.py). Returns (logits at the clamped position
    ``prompt_len - 1 - chunk_start`` (V,), updated cache).

    The chunk width C is STATIC: every prompt of every length prefills
    through this ONE compiled program (chunk_start/prompt_len/slot are
    data) — both the one-compiled-program invariant and the per-tick
    prefill bound. A 2k-token prompt becomes 2k/C short calls the
    engine interleaves with decode ticks instead of one tick-stalling
    program.

    Masking: the chunk's own k/v zero at pad positions (>= prompt_len)
    BEFORE the cache write, and attention clamps ``kv_length`` to
    ``prompt_len`` so the zeros are never attended either. Pad QUERY
    rows compute garbage that stays in their own (position-wise) lanes;
    the logits read is clamped to a valid row.
    """
    _, C = tokens.shape
    rope = _rope_tables(cfg)
    positions = chunk_start + jnp.arange(C)
    x = _embed(cfg, params, tokens, positions, None, True)
    if blocks_list is None:
        blocks_list = unstack_blocks(params, cfg)
    adp_layers, head_node, head_s = _slot_adapter_layers(adapter, cfg)
    valid = (positions < prompt_len)[None, :, None, None]
    kv_len = jnp.reshape(jnp.minimum(chunk_start + C, prompt_len), (1,))
    q_pos = positions[None, :]                       # (1, C) per-row form
    new = _new_cache_acc(cache)
    for l, p in enumerate(blocks_list):
        adp = adp_layers[l] if adp_layers is not None else None
        kind = cfg.layer_kind(l)
        h = _norm(cfg, p["norm1"], x)
        q, k, v = _qkv_proj(cfg, p["attn"], h, _layer_rope(cfg, rope, kind),
                            positions,
                            adp=adp["attn"] if adp is not None else None)
        k = jnp.where(valid, k, jnp.zeros((), k.dtype))
        v = jnp.where(valid, v, jnp.zeros((), v.dtype))
        write_at, ring_kw = _ring_chunk(cfg, kind, cache["k"][l].shape[2],
                                        chunk_start, C)
        _slot_write(cache, "k", k.transpose(0, 2, 1, 3),
                    (slot, 0, write_at, 0), new)
        _slot_write(cache, "v", v.transpose(0, 2, 1, 3),
                    (slot, 0, write_at, 0), new)
        # attend over THIS slot's full row, freshly including the chunk:
        # earlier chunks / the copied prefix pane are the context
        K_row = jax.lax.dynamic_slice(
            new["k"][l], (slot, 0, 0, 0), (1,) + new["k"][l].shape[1:])
        V_row = jax.lax.dynamic_slice(
            new["v"][l], (slot, 0, 0, 0), (1,) + new["v"][l].shape[1:])
        with _attention_scope(bool(ring_kw)):
            out = decode_attention(q, K_row, V_row, q_positions=q_pos,
                                   kv_length=kv_len, **ring_kw,
                                   **_layer_scales(new, l, slot))
        x = _add_branches(
            cfg, p, x, h,
            _attn_out_proj(p["attn"], out, 1, C,
                           adp=adp["attn"] if adp is not None else None),
            adp=adp, live=valid[:, :, 0, 0])
    x = _norm(cfg, params["final_norm"], x)
    idx = jnp.clip(prompt_len - 1 - chunk_start, 0, C - 1)
    last = jax.lax.dynamic_slice(x, (0, idx, 0), (1, 1, x.shape[-1]))
    logits = _logits(params, last, head_node, head_s)
    return logits[0, 0], new


def _use_bgmv(adapter, cfg: ModelConfig) -> bool:
    """Route per-row adapter deltas through the fused pallas BGMV kernel
    (ops/decode_step.lora_bgmv). Opt-in via BLLM_BGMV=1 on TPU — like
    BLLM_FUSED_DECODE, kept off by default until a hardware A/B proves it
    — and only when EVERY adapted projection's (in, rank, out) is
    kernel-eligible; the XLA gather+einsum path is the reference."""
    import os as _os

    if adapter is None or jax.default_backend() != "tpu":
        return False
    if _os.environ.get("BLLM_BGMV", "0") != "1":
        return False
    from building_llm_from_scratch_tpu.ops.decode_step import (
        supports_lora_shape,
    )

    r = adapter["pool"]["blocks"]["attn"]["wq"]["A"].shape[-1]
    D, F = cfg.emb_dim, cfg.hidden_dim
    wq, wkv = cfg.n_heads * cfg.head_dim, cfg.n_kv_groups * cfg.head_dim
    dims = [(D, wq), (D, wkv), (wq, D), (D, F), (F, D)]
    return all(supports_lora_shape(i, r, o) for i, o in dims)


def _bgmv_block_adp(pool_blocks_l, ids, scaling) -> Params:
    """Per-layer adp dict whose nodes route through the fused kernel:
    each projection carries its (N, in, r)/(N, r, out) pool panes — the
    kernel gathers per-row inside, driven by ``ids``."""
    def node(n):
        return {"bgmv": (n["A"], n["B"], ids, scaling)}

    out = {}
    for group in ("attn", "mlp"):
        out[group] = {name: node(n)
                      for name, n in pool_blocks_l[group].items()}
        out[group]["s"] = None
    return out


def decode_slots(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                 lengths: jnp.ndarray, cache: Params,
                 blocks_list: Optional[list] = None,
                 adapter: Optional[Params] = None,
                 live: Optional[jnp.ndarray] = None,
                 expert_rows: Optional[list] = None
                 ) -> Tuple[jnp.ndarray, Params]:
    """One decode tick for the whole slot batch: ``tokens`` (S, 1) are each
    slot's last accepted token, ``lengths`` (S,) its valid cache prefix.
    Appends each row's k/v at ITS offset (``_slot_append_kv``: one in-place
    ``lane_window_append`` a layer where ``kv_append_path`` admits it, else
    ``slot_cache_append``'s scatter; the pallas fused step where
    ``_use_fused_decode`` says so) and attends each row's own prefix
    (``decode_attention_path``: the live-block kernel where the gate admits
    it, else ``decode_attention`` with per-row masks); returns
    (fp32 logits (S, V), updated cache). Free/finished slots compute
    garbage rows the engine ignores — the shapes never change, so XLA
    compiles exactly one decode program.

    ``adapter``: {"pool", "scaling", "ids" (S,)} — per-SLOT LoRA adapters
    applied as a batched gather + einsum (BGMV) fused into the existing
    projections. Adapter identity is a data dimension: any mix of ids
    (−1 = base model) runs through this same one compiled program, so
    hot-loading/evicting adapters never recompiles.

    ``live`` (S,) bool, for a sparse model: the rows that decode a token.
    The others (free slots, slots in mid-prefill) are routed to no expert,
    so they read no expert's weights and count for none. ``expert_rows``:
    a list that collects each sparse layer's rows per held expert (H,).
    """
    rope = _rope_tables(cfg)
    S = tokens.shape[0]
    lengths = lengths.astype(jnp.int32)
    positions = lengths[:, None]                       # (S, 1)
    x = _embed(cfg, params, tokens, positions, None, True)
    if blocks_list is None:
        blocks_list = unstack_blocks(params, cfg)

    use_fused_step = (not cfg.has_window_layers
                      and _use_fused_decode(cfg, cache, 1))

    if _use_bgmv(adapter, cfg):
        ids = adapter["ids"].astype(jnp.int32)
        pool_blocks = adapter["pool"]["blocks"]
        adp_layers = [
            _bgmv_block_adp(
                jax.tree_util.tree_map(lambda a, l=l: a[:, l], pool_blocks),
                ids, adapter["scaling"])
            for l in range(cfg.n_layers)
        ]
        # head delta stays on the gathered path (vocab width is not
        # kernel-eligible); the gather is tiny at (S, D, r)/(S, r, V)
        head_rows, head_s = _adapter_rows(
            {"head": adapter["pool"]["head"]}, adapter["scaling"], ids)
        head_node = head_rows["head"]["weight"]
    else:
        adp_layers, head_node, head_s = _slot_adapter_layers(adapter, cfg)

    new = _new_cache_acc(cache)
    for l, (p, K, V) in enumerate(zip(blocks_list, cache["k"], cache["v"])):
        adp = adp_layers[l] if adp_layers is not None else None
        kind = cfg.layer_kind(l)
        h = _norm(cfg, p["norm1"], x)
        q, k, v = _qkv_proj(cfg, p["attn"], h, _layer_rope(cfg, rope, kind),
                            positions,
                            adp=adp["attn"] if adp is not None else None)
        if use_fused_step:
            from building_llm_from_scratch_tpu.ops.decode_step import (
                fused_decode_step,
            )

            out, K, V = fused_decode_step(q, k.astype(K.dtype),
                                          v.astype(V.dtype), K, V, lengths)
            new["k"].append(K)
            new["v"].append(V)
        else:
            write_at, ring_kw = _ring_step(cfg, kind, K.shape[2], lengths)
            K, V = _slot_append_kv(cache, new, l, K, V, k, v, write_at)
            out = _slot_attend(cfg, cache, new, l, q, K, V, lengths, ring_kw)
        x = _add_branches(
            cfg, p, x, h,
            _attn_out_proj(p["attn"], out, S, 1,
                           adp=adp["attn"] if adp is not None else None),
            adp=adp, live=None if live is None else live[:, None],
            expert_rows=expert_rows)
    x = _norm(cfg, params["final_norm"], x)
    logits = _logits(params, x, head_node, head_s)
    return logits[:, 0], new


def verify_slots(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                 lengths: jnp.ndarray, cache: Params,
                 blocks_list: Optional[list] = None,
                 adapter: Optional[Params] = None
                 ) -> Tuple[jnp.ndarray, Params]:
    """Speculative verify: the Tq = k+1 sibling of ``decode_slots``.

    ``tokens`` (S, Tq) is each slot's last accepted token followed by its
    k drafted candidates; ``lengths`` (S,) the valid cache prefix per row.
    ONE forward scores all Tq positions: position j's logits condition on
    [cache, tokens[:, :j+1]], so they are the model's true next-token
    distribution exactly when the drafts before j were all accepted — the
    accept rule (generate.accept_draft_tokens) commits only such
    prefixes. Appends all Tq candidate k/v panes at per-row offsets (the
    same ``_slot_append_kv`` rule decode uses: Tq > 1 always takes
    ``slot_cache_append``'s per-row scatter, quantize-on-write
    under the int8 policy); the engine advances ``lengths`` by the
    ACCEPTED count only, so a rejected tail's entries sit past the valid
    prefix — masked by ``kv_length`` everywhere and overwritten by the
    next tick's append. No rollback copy exists because none is needed.

    Per-query causality rides the existing ``decode_attention`` per-row
    masks: query j at absolute position lengths+j attends keys at
    positions <= lengths+j, i.e. the real prefix plus the drafts before
    it — never the drafts after it. k is STATIC: every acceptance count
    0..k+1 flows through this one compiled program, preserving the
    engine's one-compiled-program invariant.

    Free/mid-prefill slots ride as ignored rows exactly as in
    ``decode_slots``: their appends land at the row's next write
    position and are overwritten before anything reads them.

    Returns (fp32 logits (S, Tq, V), updated cache).
    """
    rope = _rope_tables(cfg)
    S, Tq = tokens.shape
    lengths = lengths.astype(jnp.int32)
    # position CLAMP: a row near capacity has draft positions past
    # context_length-1; unclamped they would index past the positional
    # tables (jnp.take's out-of-bounds fill is NaN) and the NaN v-pane
    # poisons every query through the value einsum's 0*NaN. Clamped
    # positions only ever affect TAIL candidates that can never be
    # committed (prompt + budget <= max_len by admission), so every
    # committable position keeps its exact positional encoding.
    positions = jnp.minimum(
        lengths[:, None] + jnp.arange(Tq)[None, :],
        cfg.context_length - 1)                                # (S, Tq)
    x = _embed(cfg, params, tokens, positions, None, True)
    if blocks_list is None:
        blocks_list = unstack_blocks(params, cfg)

    # adapter application mirrors decode_slots' gathered path (the pallas
    # BGMV kernel is single-token-only; a Tq-wide variant is a TPU
    # follow-up — the XLA gather+einsum is the reference either way)
    adp_layers, head_node, head_s = _slot_adapter_layers(adapter, cfg)

    new = _new_cache_acc(cache)
    for l, (p, K, V) in enumerate(zip(blocks_list, cache["k"], cache["v"])):
        adp = adp_layers[l] if adp_layers is not None else None
        h = _norm(cfg, p["norm1"], x)
        q, k, v = _qkv_proj(cfg, p["attn"], h, rope, positions,
                            adp=adp["attn"] if adp is not None else None)
        K, V = _slot_append_kv(cache, new, l, K, V, k, v, lengths)
        out = decode_attention(q, K, V, q_positions=positions,
                               kv_length=lengths + Tq,
                               **_layer_scales(new, l))
        x = x + _attn_out_proj(p["attn"], out, S, Tq,
                               adp=adp["attn"] if adp is not None else None)
        x = x + _mlp(cfg, p["mlp"], _norm(cfg, p["norm2"], x),
                     adp=adp["mlp"] if adp is not None else None)
    x = _norm(cfg, params["final_norm"], x)
    logits = _head_logits(x, params["head"]["weight"], head_node, head_s)
    return logits, new


# ---------------------------------------------------------------------------
# Paged slot paths (KVCachePolicy.paged; serving/engine.py)
#
# Same programs as the contiguous slot paths above with ONE layout change:
# a row no longer owns a contiguous (Tmax,) lane — a per-slot int32 page
# table maps each row's logical positions onto fixed-size pages of a
# shared pool (cache leaves are (n_pages, Hkv, page_tokens, hd)). The
# table rides every call as traced DATA against static shapes (the
# adapter-pool trick), so page churn — prefix hits, frees, eviction,
# oversubscription — never recompiles anything.
#
# Bit-parity with the contiguous layout is by construction: appends write
# identical values at identical logical positions (the int8 quantization
# grouping — per written position per head — is unchanged), the gather
# view reassembles each row into the exact (S, Hkv, cache_len, ...)
# buffer ``decode_attention`` saw before, and every position where the
# two layouts could disagree (stale pool bytes vs. a row's leftover lane
# garbage) is masked by ``kv_length`` in both — masked weights are
# exactly zero and pool contents are always finite, so masked values
# never reach the output.
#
# Table entry 0 is the TRASH PAGE: unallocated logical positions (a free
# row's garbage-lane append, a final chunk's pad tail past the prompt)
# scatter there and are only ever read masked. Duplicate scatter indices
# therefore only ever collide on the trash page or on pad zeros — the
# nondeterminism XLA allows for them can never reach an unmasked read.
# ---------------------------------------------------------------------------

def _paged_scatter(cache: Params, name: str, vals: jnp.ndarray,
                   phys: jnp.ndarray, off: jnp.ndarray, new: Params) -> None:
    """Scatter ``vals`` (R, Hkv, hd) — R written logical positions — into
    the pool leaf at rows ``phys`` (R,) page ids / ``off`` (R,) in-page
    offsets, quantizing on write under the int8 policy exactly like
    ``_slot_write`` (same per-position per-head scale grouping, so codes
    and sidecars are bitwise identical to the contiguous layout's)."""
    buf = cache[name][len(new[name])]
    if _cache_quantized(cache):
        from building_llm_from_scratch_tpu.ops.decode_step import quantize_kv

        codes, scale = quantize_kv(vals)
        sbuf = cache[name + "_scale"][len(new[name + "_scale"])]
        new[name + "_scale"].append(sbuf.at[phys, :, off].set(scale))
        vals = codes
    new[name].append(buf.at[phys, :, off].set(vals.astype(buf.dtype)))


def _paged_append_kv(cache: Params, new: Params, l: int,
                     k: jnp.ndarray, v: jnp.ndarray,
                     lengths: jnp.ndarray, page_table: jnp.ndarray,
                     cache_len: int) -> None:
    """Paged sibling of ``_slot_append_kv``: append one layer's fresh
    k/v (model layout (S, Tq, Hkv, hd)) at each row's logical offsets,
    routed through the page table. Positions clamp to ``cache_len - 1``
    (only ever binding for garbage lanes that are masked everywhere,
    mirroring ``verify_slots``' position clamp)."""
    S, Tq = k.shape[:2]
    P = cache["k"][l].shape[2]
    pos = jnp.minimum(lengths[:, None] + jnp.arange(Tq)[None, :],
                      cache_len - 1)                        # (S, Tq)
    phys = jnp.take_along_axis(page_table, pos // P, axis=1).reshape(-1)
    off = (pos % P).reshape(-1)
    _paged_scatter(cache, "k", k.reshape(S * Tq, *k.shape[2:]), phys, off,
                   new)
    _paged_scatter(cache, "v", v.reshape(S * Tq, *v.shape[2:]), phys, off,
                   new)


def _paged_view(leaf: jnp.ndarray, page_table: jnp.ndarray,
                cache_len: int) -> jnp.ndarray:
    """Gather a (rows, Hkv, cache_len, ...) row-major view out of the
    pool leaf (n_pages, Hkv, P, ...) through the page table (rows, M):
    the XLA reference for page-table attention — downstream
    ``decode_attention`` is completely unchanged, which is what pins
    bit-parity. The TPU pallas kernel (ops/decode_step.paged_gather_kv)
    computes the same gather without materializing it per layer."""
    g = leaf[page_table]                    # (rows, M, Hkv, P, ...)
    g = jnp.moveaxis(g, 2, 1)               # (rows, Hkv, M, P, ...)
    shape = g.shape
    g = g.reshape(shape[0], shape[1], shape[2] * shape[3], *shape[4:])
    return g[:, :, :cache_len]


def _paged_layer_kv(new: Params, l: int, page_table: jnp.ndarray,
                    cache_len: int):
    """(K, V, scale kwargs) row views for layer ``l`` AFTER its paged
    append — the paged sibling of slicing ``new['k'][l]`` directly plus
    ``_layer_scales``."""
    K = _paged_view(new["k"][l], page_table, cache_len)
    V = _paged_view(new["v"][l], page_table, cache_len)
    scales = {}
    if "k_scale" in new:
        scales = {
            "k_scale": _paged_view(new["k_scale"][l], page_table, cache_len),
            "v_scale": _paged_view(new["v_scale"][l], page_table, cache_len),
        }
    return K, V, scales


def _use_paged_attn(cache: Params, cfg: ModelConfig) -> bool:
    """Route decode attention through the pallas page-gather kernel
    (ops/decode_step.paged_decode_attention). Opt-in via BLLM_PAGED_ATTN=1
    on TPU — the same off-until-hardware-A/B discipline as
    BLLM_FUSED_DECODE/BLLM_BGMV — and only for unquantized pools of
    kernel-eligible shape; the XLA gather view is the reference."""
    import os as _os

    if jax.default_backend() != "tpu" or _cache_quantized(cache):
        return False
    if _os.environ.get("BLLM_PAGED_ATTN", "0") != "1":
        return False
    from building_llm_from_scratch_tpu.ops.decode_step import (
        supports_paged_shape,
    )

    return supports_paged_shape(1, cache["k"][0].shape[2], cfg.head_dim)


def paged_decode_slots(params: Params, cfg: ModelConfig,
                       tokens: jnp.ndarray, lengths: jnp.ndarray,
                       page_table: jnp.ndarray, cache: Params,
                       blocks_list: Optional[list] = None,
                       adapter: Optional[Params] = None, *,
                       cache_len: int) -> Tuple[jnp.ndarray, Params]:
    """Paged sibling of ``decode_slots``: one decode tick over the slot
    batch with every cache read/write routed through ``page_table``
    ((S, max_pages) int32, traced data). ``cache_len`` is the static
    logical row length (the engine's ``_cache_len``), identical to the
    contiguous buffer width — so the reassembled row views, masks, and
    therefore logits are bit-identical to the contiguous program's."""
    rope = _rope_tables(cfg)
    S = tokens.shape[0]
    lengths = lengths.astype(jnp.int32)
    page_table = page_table.astype(jnp.int32)
    positions = lengths[:, None]                       # (S, 1)
    x = _embed(cfg, params, tokens, positions, None, True)
    if blocks_list is None:
        blocks_list = unstack_blocks(params, cfg)
    adp_layers, head_node, head_s = _slot_adapter_layers(adapter, cfg)
    use_paged_attn = _use_paged_attn(cache, cfg)

    new = _new_cache_acc(cache)
    for l, p in enumerate(blocks_list):
        adp = adp_layers[l] if adp_layers is not None else None
        h = _norm(cfg, p["norm1"], x)
        q, k, v = _qkv_proj(cfg, p["attn"], h, rope, positions,
                            adp=adp["attn"] if adp is not None else None)
        _paged_append_kv(cache, new, l, k, v, lengths, page_table,
                         cache_len)
        if use_paged_attn:
            from building_llm_from_scratch_tpu.ops.decode_step import (
                paged_decode_attention,
            )

            out = paged_decode_attention(q, new["k"][l], new["v"][l],
                                         page_table, lengths)
        else:
            K, V, scales = _paged_layer_kv(new, l, page_table, cache_len)
            out = decode_attention(q, K, V, q_positions=positions,
                                   kv_length=lengths + 1, **scales)
        x = x + _attn_out_proj(p["attn"], out, S, 1,
                               adp=adp["attn"] if adp is not None else None)
        x = x + _mlp(cfg, p["mlp"], _norm(cfg, p["norm2"], x),
                     adp=adp["mlp"] if adp is not None else None)
    x = _norm(cfg, params["final_norm"], x)
    logits = _head_logits(x, params["head"]["weight"], head_node, head_s)
    return logits[:, 0], new


def paged_verify_slots(params: Params, cfg: ModelConfig,
                       tokens: jnp.ndarray, lengths: jnp.ndarray,
                       page_table: jnp.ndarray, cache: Params,
                       blocks_list: Optional[list] = None,
                       adapter: Optional[Params] = None, *,
                       cache_len: int) -> Tuple[jnp.ndarray, Params]:
    """Paged sibling of ``verify_slots`` (Tq = k+1 speculative verify):
    candidate k/v scatter at per-row logical offsets through the table,
    rejected tails sit past ``kv_length`` exactly as before — masked
    everywhere and overwritten by the next tick's append."""
    rope = _rope_tables(cfg)
    S, Tq = tokens.shape
    lengths = lengths.astype(jnp.int32)
    page_table = page_table.astype(jnp.int32)
    positions = jnp.minimum(
        lengths[:, None] + jnp.arange(Tq)[None, :],
        cfg.context_length - 1)                                # (S, Tq)
    x = _embed(cfg, params, tokens, positions, None, True)
    if blocks_list is None:
        blocks_list = unstack_blocks(params, cfg)
    adp_layers, head_node, head_s = _slot_adapter_layers(adapter, cfg)

    new = _new_cache_acc(cache)
    for l, p in enumerate(blocks_list):
        adp = adp_layers[l] if adp_layers is not None else None
        h = _norm(cfg, p["norm1"], x)
        q, k, v = _qkv_proj(cfg, p["attn"], h, rope, positions,
                            adp=adp["attn"] if adp is not None else None)
        _paged_append_kv(cache, new, l, k, v, lengths, page_table,
                         cache_len)
        K, V, scales = _paged_layer_kv(new, l, page_table, cache_len)
        out = decode_attention(q, K, V, q_positions=positions,
                               kv_length=lengths + Tq, **scales)
        x = x + _attn_out_proj(p["attn"], out, S, Tq,
                               adp=adp["attn"] if adp is not None else None)
        x = x + _mlp(cfg, p["mlp"], _norm(cfg, p["norm2"], x),
                     adp=adp["mlp"] if adp is not None else None)
    x = _norm(cfg, params["final_norm"], x)
    logits = _head_logits(x, params["head"]["weight"], head_node, head_s)
    return logits, new


def paged_prefill_chunk_into_slot(params: Params, cfg: ModelConfig,
                                  tokens: jnp.ndarray,
                                  chunk_start: jnp.ndarray,
                                  prompt_len: jnp.ndarray,
                                  slot: jnp.ndarray,
                                  page_table: jnp.ndarray, cache: Params,
                                  blocks_list: Optional[list] = None,
                                  adapter: Optional[Params] = None, *,
                                  cache_len: int
                                  ) -> Tuple[jnp.ndarray, Params]:
    """Paged sibling of ``prefill_chunk_into_slot``: the chunk's C
    positions scatter into row ``slot``'s pages, and attention gathers
    that one row's view through its table lane. Pad positions past the
    prompt write zeros (the same determinism rule as contiguous); any
    position past the row's allocated frontier lands on the trash page
    — never read unmasked either way."""
    _, C = tokens.shape
    rope = _rope_tables(cfg)
    positions = chunk_start + jnp.arange(C)
    x = _embed(cfg, params, tokens, positions, None, True)
    if blocks_list is None:
        blocks_list = unstack_blocks(params, cfg)
    adp_layers, head_node, head_s = _slot_adapter_layers(adapter, cfg)
    valid = (positions < prompt_len)[None, :, None, None]
    kv_len = jnp.reshape(jnp.minimum(chunk_start + C, prompt_len), (1,))
    q_pos = positions[None, :]                       # (1, C) per-row form
    page_table = page_table.astype(jnp.int32)
    P = cache["k"][0].shape[2]
    row_tab = jax.lax.dynamic_slice(
        page_table, (slot, 0), (1, page_table.shape[1]))     # (1, M)
    pos = jnp.minimum(positions, cache_len - 1)              # (C,)
    phys = row_tab[0, pos // P]
    off = pos % P
    new = _new_cache_acc(cache)
    for l, p in enumerate(blocks_list):
        adp = adp_layers[l] if adp_layers is not None else None
        h = _norm(cfg, p["norm1"], x)
        q, k, v = _qkv_proj(cfg, p["attn"], h, rope, positions,
                            adp=adp["attn"] if adp is not None else None)
        k = jnp.where(valid, k, jnp.zeros((), k.dtype))
        v = jnp.where(valid, v, jnp.zeros((), v.dtype))
        _paged_scatter(cache, "k", k[0], phys, off, new)
        _paged_scatter(cache, "v", v[0], phys, off, new)
        K_row, V_row, scales = _paged_layer_kv(new, l, row_tab, cache_len)
        out = decode_attention(q, K_row, V_row, q_positions=q_pos,
                               kv_length=kv_len, **scales)
        x = x + _attn_out_proj(p["attn"], out, 1, C,
                               adp=adp["attn"] if adp is not None else None)
        x = x + _mlp(cfg, p["mlp"], _norm(cfg, p["norm2"], x),
                     adp=adp["mlp"] if adp is not None else None)
    x = _norm(cfg, params["final_norm"], x)
    idx = jnp.clip(prompt_len - 1 - chunk_start, 0, C - 1)
    last = jax.lax.dynamic_slice(x, (0, idx, 0), (1, 1, x.shape[-1]))
    logits = _head_logits(last, params["head"]["weight"], head_node, head_s)
    return logits[0, 0], new
