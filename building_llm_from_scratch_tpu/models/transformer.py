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
of the KINDS 'sliding' and 'full' (``cfg.layer_kinds``) share one leaf
shape, so they stack like any others; the kind decides the layer's mask,
its positions and, in the slot cache, whether its buffer is a ring. A
'linear' layer (ops/linear_attention.py) has another mixer with other
leaves, so the MIXERS are stacked by kind: ``blocks["attn"]`` over the
attention layers alone (with ``"wg"`` under ``cfg.attn_out_gate``) and

      "linear":  {"wq", "wk", "wv": (Ll, D, W), "wo": (Ll, W, D),
                  "conv": (Ll, K, 3W), "A_log": (Ll, H), "dt_bias": (Ll, W),
                  "w_fa", "w_ga": (Ll, D, r), "w_fb", "w_gb": (Ll, r, W),
                  "w_b": (Ll, D, H), "o_norm": {"scale": (Ll, hd)}}

over the Ll linear ones (W = H * hd); an 'ssm' layer
(ops/selective_scan.py) a third,

      "ssm":     {"w_in": (Ls, D, 2I), "w_out": (Ls, I, D),
                  "conv": (Ls, K, I), "conv_b": (Ls, I),
                  "w_x": (Ls, I, R + 2N), "w_dt": (Ls, R, I),
                  "dt_bias", "D": (Ls, I), "A_log": (Ls, N, I),
                  "dt_norm": {"scale": (Ls, R)},
                  "b_norm", "c_norm": {"scale": (Ls, N)}}

over the Ls state-space ones; norms and feed-forwards stay (L, ...).
``_layer_of`` / ``_by_period`` give a layer its own.
"""

from __future__ import annotations

from functools import cached_property, partial
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
from building_llm_from_scratch_tpu.ops.linear_attention import (
    causal_conv,
    chunked_delta_rule,
    l2norm,
    linear_attention_path,
    recurrent_step,
    recurrent_step_rows,
)
from building_llm_from_scratch_tpu.ops.norms import layernorm, rmsnorm
from building_llm_from_scratch_tpu.ops.selective_scan import (
    live_rows_table,
    selective_scan,
    selective_scan_kernel,
    selective_scan_path,
    selective_step,
    selective_step_rows,
    supports_step_rows,
)
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
    """Per-layer adapter argument for ``_block``/``_slot_pass``: the lora
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


def _init_linear_params(cfg: ModelConfig, key: jax.Array, Ll: int) -> Params:
    """The 'linear' layers' mixers. ``A_log`` and ``dt_bias`` are drawn from
    the family's ranges (a decay rate A in [1, 16] a head, a time step dt in
    [0.001, 0.1] a channel, kept as log A and softplus^-1 dt), so slow and
    fast channels both exist from the start."""
    D, dt = cfg.emb_dim, cfg.jax_dtype
    H, W, r = cfg.linear_heads, cfg.linear_width, cfg.linear_gate_rank
    keys = jax.random.split(key, 12)
    lin = lambda i, a, b: _linear_init(keys[i], a, b, dt, Ll)
    step = jnp.exp(jax.random.uniform(
        keys[10], (Ll, W), jnp.float32, jnp.log(0.001), jnp.log(0.1)))
    return {
        "wq": lin(0, D, W), "wk": lin(1, D, W), "wv": lin(2, D, W),
        "wo": lin(3, W, D),
        "conv": lin(4, cfg.linear_conv, 3 * W),
        "A_log": jnp.log(jax.random.uniform(
            keys[9], (Ll, H), jnp.float32, 1.0, 16.0)).astype(dt),
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
        "w_fa": lin(5, D, r), "w_fb": lin(6, r, W),
        "w_ga": lin(7, D, r), "w_gb": lin(8, r, W),
        "w_b": lin(11, D, H),
        "o_norm": {"scale": jnp.ones((Ll, cfg.linear_head_dim), dt)},
    }


def _init_ssm_params(cfg: ModelConfig, key: jax.Array, Ls: int) -> Params:
    """The 'ssm' layers' mixers, by the family's init: a channel's N decay
    rates A = 1..N (kept as log A), a time step dt in [0.001, 0.1] a channel
    (kept as softplus^-1 dt), the skip D = 1."""
    D, dt = cfg.emb_dim, cfg.jax_dtype
    I, N, R = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_dt_rank
    keys = jax.random.split(key, 6)
    lin = lambda i, a, b: _linear_init(keys[i], a, b, dt, Ls)
    ones = lambda n: {"scale": jnp.ones((Ls, n), dt)}
    step = jnp.exp(jax.random.uniform(
        keys[5], (Ls, I), jnp.float32, jnp.log(0.001), jnp.log(0.1)))
    return {
        "w_in": lin(0, D, 2 * I), "w_out": lin(1, I, D),
        "conv": lin(2, cfg.ssm_conv, I), "conv_b": jnp.zeros((Ls, I), dt),
        "w_x": lin(3, I, R + 2 * N), "w_dt": lin(4, R, I),
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
        "A_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1.0, N + 1))[:, None], (Ls, N, I)).astype(dt),
        "D": jnp.ones((Ls, I), dt),
        "dt_norm": ones(R), "b_norm": ones(N), "c_norm": ones(N),
    }


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Build the full parameter pytree for ``cfg``."""
    L, D, V, T = cfg.n_layers, cfg.emb_dim, cfg.vocab_size, cfg.context_length
    hd, Hq, Hkv, F = cfg.head_dim, cfg.n_heads, cfg.n_kv_groups, cfg.hidden_dim
    dt = cfg.jax_dtype

    keys = jax.random.split(key, 16)
    zeros = lambda *shape: jnp.zeros(shape, dt)
    ones = lambda *shape: jnp.ones(shape, dt)

    Ll, Ls = len(cfg.layers_of("linear")), len(cfg.layers_of("ssm"))
    La = L - Ll - Ls               # the mixers are stacked by kind
    attn: Params = {
        "wq": _linear_init(keys[0], D, Hq * hd, dt, La),
        "wk": _linear_init(keys[1], D, Hkv * hd, dt, La),
        "wv": _linear_init(keys[2], D, Hkv * hd, dt, La),
        "wo": _linear_init(keys[3], Hq * hd, D, dt, La),
    }
    if cfg.qkv_bias:
        attn.update(bq=zeros(La, Hq * hd), bk=zeros(La, Hkv * hd),
                    bv=zeros(La, Hkv * hd))
    if cfg.attn_out_bias:
        attn["bo"] = zeros(La, D)
    if cfg.attn_out_gate:
        attn["wg"] = _linear_init(keys[11], D, Hq * hd, dt, La)

    def norm(n_layers=None):
        n: Params = {"scale": ones(n_layers, D) if n_layers else ones(D)}
        if cfg.norm_bias:
            n["bias"] = zeros(n_layers, D) if n_layers else zeros(D)
        return n

    blocks: Params = {"norm1": norm(L), "attn": attn}
    if Ll:
        blocks["linear"] = _init_linear_params(cfg, keys[12], Ll)
    if Ls:
        blocks["ssm"] = _init_ssm_params(cfg, keys[13], Ls)
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
    training path (_attention) and every cached program's body
    (_slot_pass); divergence here would silently break decode.
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
               rng: Optional[jax.Array], deterministic: bool,
               sp_mesh=None, sp_inside=None, tp_axis=None, adp=None,
               window: Optional[int] = None) -> jnp.ndarray:
    """Per-block causal self-attention over the sequence itself (the
    cached programs attend through ``_slot_pass``'s access objects)."""
    if window is not None and (sp_mesh is not None or sp_inside is not None):
        raise ValueError("the ring schedule of sequence parallelism has no "
                         "window term: a model with 'sliding' layers "
                         "trains without --sp")
    B, Tq, D = x.shape
    hd = cfg.head_dim

    q, k, v = _qkv_proj(cfg, p, x, rope, positions, adp=adp)

    if sp_inside is not None:
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
    elif sp_mesh is not None:
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
                dropout_rate=cfg.drop_rate,
                dropout_rng=rng,
                deterministic=deterministic,
                impl=cfg.attn_impl,
                window=window,
            )
    out = checkpoint_name(out, "attn_out")
    if cfg.attn_out_gate:
        out = _attn_gate(p, out, x)
    return _attn_out_proj(p, out, B, Tq, tp_axis=tp_axis, adp=adp)


@jax.named_scope("attention_gate")
def _attn_gate(p: Params, out: jnp.ndarray, h: jnp.ndarray) -> jnp.ndarray:
    """``cfg.attn_out_gate``: attention's output (B, T, Hq, hd) times
    sigmoid(h @ wg), elementwise, before the output projection."""
    return out * jax.nn.sigmoid(h @ p["wg"]).reshape(out.shape).astype(
        out.dtype)


@jax.named_scope("linear_gates")
def _linear_gates(cfg: ModelConfig, p: Params, h: jnp.ndarray):
    """A 'linear' layer's data-dependent rates, float32: the log-decay of
    each key channel ``g = -exp(A_log) * softplus((h w_fa) w_fb + dt_bias)``
    (B, T, H, hd), the step size ``beta`` (B, T, H) and the output gate
    (B, T, H, hd)."""
    B, T, _ = h.shape
    f32, heads = jnp.float32, (B, T, cfg.linear_heads, cfg.linear_head_dim)
    rate = jax.nn.softplus(((h @ p["w_fa"]) @ p["w_fb"]).astype(f32)
                           + p["dt_bias"].astype(f32)).reshape(heads)
    g = -jnp.exp(p["A_log"].astype(f32))[:, None] * rate
    beta = jax.nn.sigmoid((h @ p["w_b"]).astype(f32))
    if cfg.linear_neg_eigval:
        beta = 2.0 * beta
    gate = jax.nn.sigmoid(((h @ p["w_ga"]) @ p["w_gb"]).astype(f32))
    return g, beta, gate.reshape(heads)


def _linear_mixer(cfg: ModelConfig, p: Params, h: jnp.ndarray, through_state,
                  valid: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """A 'linear' layer's mixer on its normed input ``h`` (B, T, D): q, k, v
    through the short convolution, the gated delta rule, a norm and a gate
    on each head's output, the output projection. ``through_state(run)``
    hands ``run`` the convolution's tail and the state these tokens start
    from and keeps what it returns: ``run(tail, state, n_valid=None,
    rows=None) -> (o, tail, state)``. Positions that ``valid`` (B, T) bool
    leaves out (padding, a row that does not decode) move no state; with
    ``rows`` (``_RowsKV.live_rows``: a tick that walks its decoding rows in
    place, ``state_step_path``) the step reads and writes the named rows'
    states alone."""
    B, T, _ = h.shape
    H, hd = cfg.linear_heads, cfg.linear_head_dim
    with jax.named_scope("linear_proj"):
        pre = jnp.concatenate([h @ p["wq"], h @ p["wk"], h @ p["wv"]],
                              axis=-1)
    g, beta, gate = _linear_gates(cfg, p, h)
    if valid is not None:
        g = jnp.where(valid[:, :, None, None], g, 0.0)
        beta = jnp.where(valid[:, :, None], beta, 0.0)

    def run(tail, state, n_valid=None, rows=None):
        x, tail = causal_conv(pre, tail, p["conv"], n_valid)
        q, k, v = (a.reshape(B, T, H, hd) for a in jnp.split(x, 3, axis=-1))
        q, k = l2norm(q) * hd ** -0.5, l2norm(k)
        if linear_attention_path(T) == "step":
            with jax.named_scope("linear_attention"):
                token = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
                if rows is not None:
                    o, state = recurrent_step_rows(*token, state, rows)
                else:
                    o, state = recurrent_step(*token, state)
            return o[:, None], tail, state
        o, state = chunked_delta_rule(q, k, v, g, beta, state)
        return o, tail, state

    o = through_state(run)
    with jax.named_scope("linear_proj"):
        y = rmsnorm(o, p["o_norm"]["scale"], eps=cfg.rmsnorm_eps) * gate
        return y.astype(h.dtype).reshape(B, T, H * hd) @ p["wo"]


def _ssm_mixer(cfg: ModelConfig, p: Params, h: jnp.ndarray, through_state,
               valid: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """An 'ssm' layer's mixer (Mamba-1, with the Jamba family's norms) on its
    normed input ``h`` (B, T, D): ``[u, z] = h w_in``; u through the short
    convolution (with its bias) and SiLU; step, B and C read from u, each
    through an RMSNorm of its own; ``delta = softplus(dt w_dt + dt_bias)``;
    the selective scan over the channels' states (ops/selective_scan.py);
    ``(y * silu(z)) w_out``. ``through_state(run)`` and ``valid`` as
    ``_linear_mixer``'s: a position left out has ``delta = 0`` and moves no
    state. The state, the exponential, the softplus, the three norms and the
    recurrence are float32; the tail is the activations' type."""
    T = h.shape[1]
    I, N, R = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_dt_rank
    f32 = jnp.float32
    with jax.named_scope("ssm_proj"):
        u, z = jnp.split(h @ p["w_in"], 2, axis=-1)

    def run(tail, state, n_valid=None, rows=None):
        with jax.named_scope("ssm_conv"):
            x, tail = causal_conv(u, tail, p["conv"], n_valid, p["conv_b"])
        with jax.named_scope("ssm_proj"):
            dt, Bm, Cm = (
                rmsnorm(a.astype(f32), p[name]["scale"].astype(f32),
                        eps=cfg.rmsnorm_eps)
                for a, name in zip(
                    jnp.split(x.astype(h.dtype) @ p["w_x"], (R, R + N),
                              axis=-1),
                    ("dt_norm", "b_norm", "c_norm")))
            delta = jax.nn.softplus((dt.astype(h.dtype) @ p["w_dt"]).astype(
                f32) + p["dt_bias"].astype(f32))
            if valid is not None:
                delta = jnp.where(valid[:, :, None], delta, 0.0)
        A, D = -jnp.exp(p["A_log"].astype(f32)), p["D"].astype(f32)
        with jax.named_scope("selective_scan"):
            path = selective_scan_path(T, I, N)
            if path == "step":
                token = (x[:, 0], delta[:, 0], A, Bm[:, 0], Cm[:, 0], D)
                if rows is not None:
                    y, state = selective_step_rows(
                        *token, state, rows,
                        interpret=jax.default_backend() != "tpu")
                else:
                    y, state = selective_step(*token, state)
                return y[:, None], tail, state
            scan = (selective_scan_kernel if path == "kernel"
                    else selective_scan)
            y, state = scan(x, delta, A, Bm, Cm, D, state)
            return y, tail, state

    y = through_state(run)
    with jax.named_scope("ssm_proj"):
        return (y * jax.nn.silu(z.astype(f32))).astype(h.dtype) @ p["w_out"]


#: the mixers whose memory of a sequence is a tail and a state, by kind; each
#: ``mixer(cfg, p, h, through_state, valid)``
_STATE_MIXERS = {"linear": _linear_mixer, "ssm": _ssm_mixer}


def _fresh_state(cfg: ModelConfig, kind: str, B: int, dtype):
    """The tail and the state before a sequence: zeros."""
    tail, state = cfg.state_shapes(kind)
    return (jnp.zeros((B,) + tail, dtype),
            jnp.zeros((B,) + state, jnp.float32))


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
    """The residual updates of ``_slot_pass``'s blocks, from a block's input
    ``x``, its first norm ``h`` and its projected attention output. Serial:
    the feed-forward reads a second norm of ``x + attention``. Parallel
    (``cfg.parallel_block``): it reads ``h`` too, and both add to ``x``."""
    if cfg.parallel_block:
        return x + attn_out + _ffn(cfg, p, h, **ffn_kw)
    x = x + attn_out
    return x + _ffn(cfg, p, _norm(cfg, p["norm2"], x), **ffn_kw)


def _block(cfg: ModelConfig, p: Params, x: jnp.ndarray,
           rope, positions, rng, deterministic,
           sp_mesh=None, sp_inside=None, tp_axis=None, adp=None,
           kind: str = "full") -> jnp.ndarray:
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
    if kind in _STATE_MIXERS:
        # a whole sequence from the zero state; nothing is kept
        h = _STATE_MIXERS[kind](cfg, p[kind], n1, lambda run: run(
            *_fresh_state(cfg, kind, x.shape[0], x.dtype))[0])
    else:
        h = _attention(cfg, p["attn"], n1, _layer_rope(cfg, rope, kind),
                       positions, r_attn, deterministic, sp_mesh=sp_mesh,
                       sp_inside=sp_inside, tp_axis=tp_axis,
                       adp=adp["attn"] if adp is not None else None,
                       window=_layer_window(cfg, kind))
    if cfg.parallel_block:
        h = h + _ffn(cfg, p, n1, tp_axis=tp_axis, adp=adp)
        return _residual_dropout(x, h, cfg.drop_rate, r_res1, deterministic)
    x = _residual_dropout(x, h, cfg.drop_rate, r_res1, deterministic)
    x = checkpoint_name(x, "resid_mid")
    h = _ffn(cfg, p, _norm(cfg, p["norm2"], x), tp_axis=tp_axis, adp=adp)
    return _residual_dropout(x, h, cfg.drop_rate, r_res2, deterministic)


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
        return _block(cfg, p, carry, rope, positions, r, deterministic,
                      sp_mesh=sp_mesh, sp_inside=sp_inside, adp=adp,
                      kind=kind)

    # the scan runs over PERIODS of layers: one layer where all are of one
    # kind, else ``cfg.layer_kinds`` unlike layers in a row, their stacked
    # leaves re-led (L, ...) -> (L / P, P, ...) (``_by_period``)
    kinds = cfg.layer_kinds or ("full",)
    P = len(kinds)

    def body(carry, period):
        if P == 1:
            return one_layer(carry, period, kinds[0]), None
        blocks, *rest = period
        for j, kind in enumerate(kinds):
            carry = one_layer(carry, (
                _period_layer(cfg, blocks, j),
                *jax.tree_util.tree_map(lambda a: a[j], rest)), kind)
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
        xs = (_by_period(cfg, xs[0]), *jax.tree_util.tree_map(
            lambda a: a.reshape((L // P, P) + a.shape[1:]), xs[1:]))
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
        return [_layer_of(cfg, blocks, l) for l in range(cfg.n_layers)]
    # the routed experts stay stacked, the layer's index beside them: each
    # is sliced inside the conditional that runs it (models/moe.py)
    experts = blocks["moe"]["experts"]
    rest = dict(blocks, moe={k: v for k, v in blocks["moe"].items()
                             if k != "experts"})
    out = []
    for l in range(cfg.n_layers):
        layer = _layer_of(cfg, rest, l)
        layer["moe"]["experts"] = dict(experts, layer=l)
        out.append(layer)
    return out


#: the groups of ``params["blocks"]`` that hold mixers, stacked by kind
_MIXERS = ("attn", "linear", "ssm")


def _mixer_of(kind: str) -> str:
    """The group of ``params["blocks"]`` that holds a layer's mixer."""
    return kind if kind in _STATE_MIXERS else "attn"


def _layer_of(cfg: ModelConfig, blocks: Params, l: int) -> Params:
    """Layer ``l``'s view of the stacked ``blocks``: leaf ``[l]``, and of
    the mixers, stacked by kind, its own at its index among its kind."""
    if not cfg.has_state_layers:
        return jax.tree_util.tree_map(lambda a: a[l], blocks)
    mixer = _mixer_of(cfg.layer_kind(l))
    at = sum(_mixer_of(cfg.layer_kind(i)) == mixer for i in range(l))
    index = lambda i: lambda tree: jax.tree_util.tree_map(
        lambda a: a[i], tree)
    return {name: index(at if name == mixer else l)(sub)
            for name, sub in blocks.items()
            if name == mixer or name not in _MIXERS}


def _by_period(cfg: ModelConfig, blocks: Params) -> Params:
    """The stacked ``blocks`` for a scan over periods of P layers: (L, ...)
    -> (L / P, P, ...), a mixer's leaves (stacked over its own kind's
    layers) -> (L / P, that kind's layers a period, ...)."""
    kinds = cfg.layer_kinds
    n = {m: sum(_mixer_of(k) == m for k in kinds) for m in _MIXERS}
    return {name: jax.tree_util.tree_map(
        lambda a, p=n.get(name, len(kinds)): a.reshape(
            (cfg.n_layers // len(kinds), p) + a.shape[1:]), sub)
        for name, sub in blocks.items()}


def _period_layer(cfg: ModelConfig, period: Params, j: int) -> Params:
    """The ``j``-th layer of one period of ``_by_period``'s view."""
    one = cfg.replace(n_layers=len(cfg.layer_kinds))
    return _layer_of(one, period, j)


# ---------------------------------------------------------------------------
# The slot pass: ONE block loop for every cached program
#
# ``forward_with_cache`` shares ONE scalar ``length`` across the whole
# batch — every row is the same request family. The continuous-batching
# engine (serving/engine.py) instead keeps a fixed (n_slots, Tmax) cache
# where every row is an INDEPENDENT request at its own sequence length:
# prefill writes one request's prompt k/v into one slot, and a decode tick
# advances all active slots by one token with per-row positions/lengths.
# All are static-shape programs (one prefill per prompt-length bucket or
# ONE chunk program, and exactly one tick program) of the same transformer;
# only where a layer WRITES its keys and values and how it ATTENDS over
# them differ, and an access object (``_SlotKV``) owns both.
# ---------------------------------------------------------------------------

def init_slot_cache(cfg: ModelConfig, n_slots: int, max_length: int,
                    policy=None) -> Params:
    """Per-layer (n_slots, Hkv, Tmax, hd) k/v buffers; lengths are host
    state (serving/engine.py), not part of the device cache.

    ``policy`` (serving.kvcache.KVCachePolicy) owns layout and dtype:
    the default reproduces the historical model-dtype cache; the int8
    policy allocates int8 k/v plus fp32 per-position scale sidecars
    (``k_scale``/``v_scale`` lists) that the access objects below fill on
    append and ``decode_attention`` folds back in."""
    from building_llm_from_scratch_tpu.serving.kvcache import (
        DEFAULT_POLICY,
    )

    return (policy or DEFAULT_POLICY).alloc(cfg, n_slots, max_length)


def _use_bgmv(adapter, cfg: ModelConfig) -> bool:
    """Route per-row adapter deltas through the fused pallas BGMV kernel
    (ops/decode_step.lora_bgmv). Opt-in via BLLM_BGMV=1 on TPU, kept off
    by default until a hardware A/B proves it, and only when EVERY adapted
    projection's (in, rank, out) is kernel-eligible; the XLA gather+einsum
    path is the reference."""
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


def _slot_adapter_layers(adapter, cfg: ModelConfig, Tq: int):
    """``_slot_pass``'s adapter nodes (per-layer adp dicts, head node, head
    scale) from ``adapter``; ``None`` -> all-None (exact base path). A POOL,
    {"pool": stacked lora tree, "scaling": (N,), "ids": (B,)}: the batch's
    per-row matrices are gathered from it ONCE, here; under ``_use_bgmv``
    (the kernel is single-token-only) each projection gets its pool panes
    instead and the kernel gathers inside. ONE shared adapter
    (``forward_with_cache``), {"lora_blocks": per-layer lora nodes or None,
    "head": its node or None, "scaling"}: every row the same nodes."""
    if adapter is None:
        return None, None, None
    if "lora_blocks" in adapter:
        s = adapter["scaling"]
        layers = adapter["lora_blocks"]
        return (None if layers is None else [_block_adp(lb, s)
                                             for lb in layers],
                adapter["head"], s)
    scaling, L = adapter["scaling"], cfg.n_layers
    # a stacked tree's leaves are (rows, L, ...): slice each layer's view
    # once, trace-time (a gather happens once, before)
    layer = lambda tree, l: jax.tree_util.tree_map(lambda a: a[:, l], tree)
    if Tq == 1 and _use_bgmv(adapter, cfg):
        ids = adapter["ids"].astype(jnp.int32)
        layers = [_bgmv_block_adp(layer(adapter["pool"]["blocks"], l), ids,
                                  scaling) for l in range(L)]
        # head delta stays on the gathered path (vocab width is not
        # kernel-eligible); the gather is tiny at (S, D, r)/(S, r, V)
        rows, s = _adapter_rows({"head": adapter["pool"]["head"]}, scaling,
                                ids)
    else:
        rows, s = _adapter_rows(adapter["pool"], scaling, adapter["ids"])
        layers = [_block_adp(layer(rows["blocks"], l), s) for l in range(L)]
    return layers, rows["head"]["weight"], s


def _slot_pass(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
               kv: "_SlotKV", *, blocks_list: Optional[list] = None,
               adapter: Optional[Params] = None,
               expert_rows: Optional[list] = None
               ) -> Tuple[jnp.ndarray, Params]:
    """THE cached forward: ``tokens`` (B, Tq) at ``kv.positions`` through
    every block, each layer's keys and values written and read through
    ``kv``; returns (fp32 logits (B, Tq, V), or (B, 1, V) at the one
    position ``kv.logits_at``; the updated cache ``kv.result()``).

    The layer loop is a plain Python loop (decode bodies are small; the
    r4 scan-unroll measured +14% over the rolled loop, and the explicit
    loop additionally lets per-layer cache buffers alias — see
    ``init_cache``). ``blocks_list`` (from ``unstack_blocks``) hoists the
    per-layer weight slices out of a caller's sampling loop. ``adapter``:
    see ``_slot_adapter_layers``. ``kv.live`` and ``expert_rows`` are the
    expert layer's (``_ffn``).

    The ORDER in which this body first asks ``kv`` for its positions, its
    live mask and its logits position is the order in which the programs
    create those operations, and XLA names instructions in that order:
    ``scripts/serving_hlo.py`` holds the cells' five programs to identical
    text across a refactor, so move a line here only with that check."""
    B, Tq = tokens.shape
    rope = _rope_tables(cfg)
    positions = kv.positions
    x = _embed(cfg, params, tokens, positions, None, True)
    if blocks_list is None:
        blocks_list = unstack_blocks(params, cfg)
    adp_layers, head_node, head_s = _slot_adapter_layers(adapter, cfg, Tq)
    live = kv.live
    for l, p in enumerate(blocks_list):
        adp = adp_layers[l] if adp_layers is not None else None
        attn_adp = adp["attn"] if adp is not None else None
        kind = cfg.layer_kind(l)
        h = _norm(cfg, p["norm1"], x)
        if kind in _STATE_MIXERS:
            mixed = _STATE_MIXERS[kind](cfg, p[kind], h,
                                        partial(kv.through_state, l),
                                        valid=live)
        else:
            q, k, v = _qkv_proj(cfg, p["attn"], h,
                                _layer_rope(cfg, rope, kind), positions,
                                adp=attn_adp)
            out = kv.append_and_attend(l, kind, q, k, v)
            if cfg.attn_out_gate:
                out = _attn_gate(p["attn"], out, h)
            mixed = _attn_out_proj(p["attn"], out, B, Tq, adp=attn_adp)
        kv.close_layer(l)
        x = _add_branches(cfg, p, x, h, mixed, adp=adp, live=live,
                          expert_rows=expert_rows)
    x = _norm(cfg, params["final_norm"], x)
    if kv.logits_at is not None:
        x = jax.lax.dynamic_slice(x, (0, kv.logits_at, 0),
                                  (1, 1, x.shape[-1]))
    return _logits(params, x, head_node, head_s), kv.result()


# -- where the keys and values live ----------------------------------------

def _cache_quantized(cache: Params) -> bool:
    return "k_scale" in cache


def kv_append_path(cache: Params, Tq: int,
                   backend: Optional[str] = None) -> str:
    """THE rule for how ``_RowsKV`` writes a tick's keys and values, made
    once, at trace time, on what the code can observe: ``"lane_window"``
    (ops/decode_step.lane_window_append: one in-place kernel call a layer)
    on a TPU backend for the shapes ``supports_lane_append`` admits,
    ``"scatter"`` (the per-row ``slot_cache_append``) for everything else:
    verify (Tq = k+1), int8 caches, ``head_dim`` 128 (positions on the
    sublanes there: ``live_block_attention`` reads that layout, the append
    has no kernel for it yet), any other backend.
    The engine reports the name (``stats()["kv_append"]``). ``backend`` is
    for tests, which have no TPU to ask about."""
    from building_llm_from_scratch_tpu.ops.decode_step import (
        supports_lane_append,
    )

    # (S, Hkv, Tmax, hd): the first layer that holds keys and values
    pane = next(a for a in cache["k"] if a is not None)
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
    """THE rule for how ``_RowsKV`` attends in layer ``layer``, the sibling
    of ``kv_append_path`` and made the same way: ``"live_blocks"``
    (ops/decode_step.live_block_attention: one kernel call a layer that
    reads, for each row, only the key blocks its live positions reach) on
    a TPU backend for the shapes ``supports_live_attention`` admits: a
    float cache in either layout the runtime keeps one in, ``head_dim``
    under 128 (blocks of lanes; no ring) or 128 (blocks of sublanes; a
    ring, ``ring``: the layer attends by ``kv_positions`` / ``window``, is
    read by position, and a row that does not decode not at all).
    ``"whole_buffer"`` (``decode_attention``: two reductions over the whole
    buffer, the lengths a mask) for everything else: verify (Tq = k+1),
    int8 caches and their scale sidecars, a ring at ``head_dim`` under 128,
    any other backend. The engine reports the name
    (``stats()["decode_attention"]``). ``backend`` is for tests."""
    from building_llm_from_scratch_tpu.ops.decode_step import (
        supports_live_attention,
    )

    pane = cache["k"][layer]                   # (S, Hkv, Tmax, hd)
    S, Hkv, Tmax, hd = pane.shape
    if ((backend or jax.default_backend()) == "tpu"
            and not _cache_quantized(cache)
            and supports_live_attention(Tq, Tmax, hd, S=S, Hkv=Hkv,
                                        Hq=n_heads, dtype=pane.dtype,
                                        ring=ring)):
        return "live_blocks"
    return "whole_buffer"


def state_step_path(cache: Params, kind: str, Tq: int, *, layer: int,
                    rows_named: bool, backend: Optional[str] = None) -> str:
    """THE rule for how ``_RowsKV`` steps the state of layer ``layer`` (of
    ``kind`` 'ssm' or 'linear'), the fourth of the family and made the same
    way: ``"live_rows"`` (the step walks the rows that decode, in place in
    the donated buffer, and reads and writes no other row's state: an 'ssm'
    layer ops/selective_scan.selective_step_rows, one kernel call a layer
    over a table made once a tick; a 'linear' layer
    ops/linear_attention.recurrent_step_rows, one trip a row) on a TPU
    backend for a one-token tick that names its rows (``rows_named``:
    ``live`` was handed in) at shapes the walk takes (an 'ssm' state of whole
    groups of 1024 channels and whole tiles of at most 32 states:
    ``supports_step_rows``; any 'linear' state). ``"whole_buffer"`` (the step
    over every row and a select that keeps the old state of those that do not
    decode) for everything else: any other backend, a width that is not whole
    groups, a tick that names no rows, a verify tick (which ``_RowsKV``
    refuses anyway). The engine reports the name (``stats()["state_step"]``).
    ``backend`` is for tests."""
    if ((backend or jax.default_backend()) != "tpu" or Tq != 1
            or not rows_named):
        return "whole_buffer"
    if kind == "ssm":
        _, N, I = cache["state"][layer].shape
        return "live_rows" if supports_step_rows(I, N) else "whole_buffer"
    return "live_rows" if kind == "linear" else "whole_buffer"


def chunk_attention_path(cache: Params, C: int, n_heads: int, *,
                         layer: int = 0,
                         backend: Optional[str] = None) -> str:
    """THE rule for how ``_ChunkKV`` attends in layer ``layer``, the third of
    the family and made the same way: ``"live_blocks"``
    (ops/chunk_attention.chunk_live_attention: one kernel call a layer over
    the key blocks that hold the row's live positions, a ring's by position)
    on a TPU backend for the shapes ``supports_chunk_attention`` admits,
    ``"materialised"`` (``decode_attention`` over the row sliced out: every
    score written out, whole buffers) for everything else: int8 caches and
    their scale sidecars, ``head_dim`` under 128 (the runtime keeps
    positions on the lanes there: the other layout), a chunk or a buffer
    that is not whole key blocks, any other backend. A page table never
    asks (``_ChunkKV`` reads through it before it gets here, as ``_RowsKV``
    does). A mesh is no refusal: the kernel shard_maps itself over the
    ambient one like its siblings (``mesh_kernel``: heads over the model
    axis, whole over any other, so a sequence-sharded chunk is gathered for
    it). Nor is a ring: the kernel reads one by position. The engine
    reports the name (``stats()["chunk_attention"]``). ``backend`` is for
    tests, which have no TPU to ask about."""
    from building_llm_from_scratch_tpu.ops.chunk_attention import (
        supports_chunk_attention,
    )

    pane = cache["k"][layer]                   # (S, Hkv, Tmax, hd)
    _, Hkv, Tmax, hd = pane.shape
    if ((backend or jax.default_backend()) == "tpu"
            and not _cache_quantized(cache)
            and supports_chunk_attention(C, Tmax, hd, Hkv=Hkv, Hq=n_heads,
                                         dtype=pane.dtype)):
        return "live_blocks"
    return "materialised"


@jax.named_scope("cache_update")
def _slot_write(cache: Params, name: str, pane: jnp.ndarray, offsets: tuple,
                new: Params) -> None:
    """Append one layer's cache write into the ``new`` accumulator:
    plain dynamic-update-slice for float caches; quantize-then-write
    (int8 codes + the fp32 scale sidecar) for int8 caches. ``pane`` is
    cache-native (B, Hkv, T, hd); ``offsets`` the 4-d DUS origin."""
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


def _refuse_ring(kind: str, what: str) -> None:
    """The layouts that have no ring say so (``configs.UNSUPPORTED`` has
    the engine refuse them first, by what the config is)."""
    if kind == "sliding":
        raise ValueError(f"{what} has no ring for a 'sliding' layer")


def _zero_pads(valid: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray):
    """Pad-position k/v are ZEROED before any cache write. They used to land
    as garbage masked only by the engine's host-side lengths, which was
    fine while slot contents stayed request-private; prefix panes
    (serving/kvcache.py) make them shareable state, so every cache write
    must be a deterministic function of the prompt."""
    return (jnp.where(valid, k, jnp.zeros((), k.dtype)),
            jnp.where(valid, v, jnp.zeros((), v.dtype)))


class _SlotKV:
    """The span of a cache that one pass covers: where its keys and values
    live and how a query reads them, the one thing the cached programs
    differ in. ``_slot_pass`` calls ``append_and_attend`` once a layer, in
    layer order, with the layer's fresh q/k/v in model layout (B, Tq, H,
    hd); the object writes k/v into its accumulator ``new``, which
    ``result()`` returns as the updated cache, and returns the attention
    output (B, Tq, Hq, hd). Everything about a cache's layout is in its
    object and nowhere else: the write (pane, per-row scatter, lane kernel,
    page table; int8 quantise-on-write with its sidecars), the read, the
    ring arithmetic, the pad-zeroing. A later layout (a latent cache, a
    ``head_dim``-128 append in place, an append that rides the attention
    call) is one more of these, or a change inside one (as the sublane
    form of the tick's attention was: PR 43).

    With it the span's geometry, which the same facts decide: ``positions``
    of the pass's tokens, ``live`` ((B, Tq) bool or None: the positions that
    are real, for the expert layer) and ``logits_at`` (the one position
    whose logits a prefill serves; None: all). Each is computed when
    ``_slot_pass`` first asks (``cached_property``), not at construction:
    see its note on the order of operations."""

    valid = None        # (1, T, 1, 1): the real positions of a padded span
    logits_at = None

    def __init__(self, cfg: ModelConfig, cache: Params):
        self.cfg, self.cache = cfg, cache
        self.new: Params = {name: [] for name in cache if name != "length"}

    def append_and_attend(self, l: int, kind: str, q, k, v) -> jnp.ndarray:
        raise NotImplementedError

    def through_state(self, l: int, run) -> jnp.ndarray:
        """A 'linear' or 'ssm' layer's slot memory is no keys and values but
        the K-1 tokens its convolution still needs (``cache["conv"][l]``,
        (rows, K-1, channels)) and a recurrent state (``cache["state"][l]``,
        float32: (rows, H, hd, hd), or (rows, N, I) in an 'ssm' layer:
        ``cfg.state_shapes``). The object hands ``run(tail, state, n_valid) ->
        (o, tail, state)`` (``_STATE_MIXERS``) what this pass's rows start from
        (zeros where a sequence starts, whatever the slot held), keeps what
        comes back for the rows that are real, and returns ``o``."""
        raise NotImplementedError

    def close_layer(self, l: int) -> None:
        """After layer ``l``: what it did not write (a 'linear' layer its
        keys and values, any other a state) is None in ``new``, so every
        list stays indexed by layer."""
        for bufs in self.new.values():
            if len(bufs) == l:
                bufs.append(None)

    def _keep_state(self, tail, state) -> None:
        self.new["conv"].append(tail)
        self.new["state"].append(state)

    def _slot_rows(self, l: int, slot, fresh, n_valid, run) -> jnp.ndarray:
        """``through_state`` for the one row ``slot``: it starts from zeros
        where ``fresh``, and the row is written back in place."""
        row = lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=0)
        tail, state = self.cache["conv"][l], self.cache["state"][l]
        o, new_tail, new_state = run(
            *(jnp.where(fresh, jnp.zeros((), a.dtype), row(a))
              for a in (tail, state)), n_valid)
        with jax.named_scope("cache_update"):
            self._keep_state(*(
                jax.lax.dynamic_update_slice_in_dim(a, b.astype(a.dtype),
                                                    slot, axis=0)
                for a, b in ((tail, new_tail), (state, new_state))))
        return o

    def result(self) -> Params:
        return self.new

    @property
    def live(self):
        return None if self.valid is None else self.valid[:, :, 0, 0]

    def _write_panes(self, k, v, offsets: tuple) -> None:
        # (B, T, Hkv, hd) -> cache-native (B, Hkv, T, hd) panes — tiny
        _slot_write(self.cache, "k", k.transpose(0, 2, 1, 3), offsets,
                    self.new)
        _slot_write(self.cache, "v", v.transpose(0, 2, 1, 3), offsets,
                    self.new)


class _SharedLengthKV(_SlotKV):
    """``forward_with_cache``: ONE scalar length (``cache['length']``) for
    the whole batch; k/v land at it in every row and each query attends the
    prefix to itself. This cache's buffers are all as long as the sequence,
    so a 'sliding' layer needs its window in the mask and no ring."""

    def __init__(self, cfg, cache, Tq: int, valid_len=None):
        super().__init__(cfg, cache)
        self.Tq, self.valid_len = Tq, valid_len

    @cached_property
    def positions(self):
        return self.cache["length"] + jnp.arange(self.Tq)

    @property
    def live(self):
        """The first ``valid_len`` of the Tq tokens are real (a prompt
        right-padded to its bucket): only they move a state."""
        if self.valid_len is None:
            return None
        rows = self.cache["state"][self.cfg.state_layers[0]].shape[0]
        return jnp.broadcast_to(jnp.arange(self.Tq) < self.valid_len,
                                (rows, self.Tq))

    def append_and_attend(self, l, kind, q, k, v):
        length = self.cache["length"]
        self._write_panes(k, v, (0, 0, length, 0))
        return decode_attention(q, self.new["k"][l], self.new["v"][l],
                                q_positions=self.positions,
                                kv_length=length + self.Tq,
                                window=_layer_window(self.cfg, kind))

    def through_state(self, l, run):
        o, tail, state = run(self.cache["conv"][l], self.cache["state"][l],
                             self.valid_len)
        self._keep_state(tail, state)
        return o

    def result(self):
        return dict(self.new, length=self.cache["length"] + self.Tq)


class _PromptKV(_SlotKV):
    """One slot's whole prompt, right-padded to its bucket of Tpb
    (``prefill_into_slot``): attention is plain causal self-attention over
    the prompt itself (nothing earlier lives in the slot), with
    ``kv_length=prompt_len`` masking the pad keys; the zeroed k/v
    (``_zero_pads``) land as one pane at row ``slot``."""

    def __init__(self, cfg, cache, Tpb: int, slot, prompt_len):
        super().__init__(cfg, cache)
        self.Tpb, self.slot, self.prompt_len = Tpb, slot, prompt_len

    @cached_property
    def positions(self):
        return jnp.arange(self.Tpb)

    @cached_property
    def valid(self):
        # pad-position zero mask, model layout (1, Tpb, 1, 1)
        return (self.positions < self.prompt_len)[None, :, None, None]

    @cached_property
    def logits_at(self):
        return self.prompt_len - 1

    def append_and_attend(self, l, kind, q, k, v):
        R = self.cache["k"][l].shape[2]
        if self.Tpb > R:
            raise ValueError(
                f"a {self.Tpb}-token prompt bucket does not fit layer {l}'s "
                f"ring of {R} positions: rings are "
                "filled by chunked prefill (KVCachePolicy.prefill_chunk)")
        window = _layer_window(self.cfg, kind)
        with _attention_scope(window is not None):
            out = causal_attention(q, k, v, q_positions=self.positions,
                                   kv_length=self.prompt_len, window=window)
        # a pane at (slot, 0, 0, 0); Tpb <= Tmax by the engine's admission
        self._write_panes(*_zero_pads(self.valid, k, v),
                          (self.slot, 0, 0, 0))
        return out

    def through_state(self, l, run):
        return self._slot_rows(l, self.slot, True, self.prompt_len, run)


class _ChunkKV(_SlotKV):
    """One slot's prompt span [chunk_start, chunk_start + C), right-padded
    past ``prompt_len`` (``prefill_chunk_into_slot``): the zeroed chunk
    lands in row ``slot`` (in a ring: at ``chunk_start mod R``), then the
    chunk attends over THAT row, freshly including itself: earlier chunks /
    a copied prefix pane are the context (``chunk_attention_path`` chooses
    the read's form: one kernel over the row's live key blocks, or
    ``decode_attention`` over the row sliced out). The logits read is
    clamped to a valid row of the chunk. With ``table`` the row is reached through its
    lane of the page table (below): the C positions scatter into its pages
    and attention gathers that one row's view; any position past the row's
    allocated frontier lands on the trash page — never read unmasked."""

    def __init__(self, cfg, cache, C: int, slot, chunk_start, prompt_len,
                 table=None, cache_len=None):
        super().__init__(cfg, cache)
        self.C, self.slot = C, slot
        self.chunk_start, self.prompt_len = chunk_start, prompt_len
        self.table, self.cache_len = table, cache_len

    @cached_property
    def positions(self):
        return self.chunk_start + jnp.arange(self.C)

    @cached_property
    def masks(self):
        """``valid`` (1, C, 1, 1) marks the span's real positions (model
        layout, for the pad-zeroing), ``kv_len`` (1,) clamps attention to
        the prompt so the zeros are never attended either, and the per-row
        form (1, C) of the query positions. Pad QUERY rows compute garbage
        that stays in their own (position-wise) lanes."""
        valid = (self.positions < self.prompt_len)[None, :, None, None]
        kv_len = jnp.reshape(jnp.minimum(self.chunk_start + self.C,
                                         self.prompt_len), (1,))
        return valid, kv_len, self.positions[None, :]

    @property
    def valid(self):
        return self.masks[0]

    @cached_property
    def logits_at(self):
        return jnp.clip(self.prompt_len - 1 - self.chunk_start, 0,
                        self.C - 1)

    @cached_property
    def pages(self):
        """The row's table lane (1, M) and where the chunk's positions land
        in it: page ids and in-page offsets (C,)."""
        P = self.cache["k"][0].shape[2]
        row_tab = jax.lax.dynamic_slice(
            self.table.astype(jnp.int32), (self.slot, 0),
            (1, self.table.shape[1]))
        pos = jnp.minimum(self.positions, self.cache_len - 1)
        return row_tab, row_tab[0, pos // P], pos % P

    def through_state(self, l, run):
        # a request's first chunk starts from zeros whatever the slot held;
        # the tail kept ends at the chunk's last real token
        return self._slot_rows(l, self.slot, self.chunk_start == 0,
                               self.masks[1][0] - self.chunk_start, run)

    def append_and_attend(self, l, kind, q, k, v):
        valid, kv_len, q_pos = self.masks
        k, v = _zero_pads(valid, k, v)
        if self.table is not None:
            _refuse_ring(kind, "the paged pool")
            row_tab, phys, off = self.pages
            _paged_scatter(self.cache, "k", k[0], phys, off, self.new)
            _paged_scatter(self.cache, "v", v[0], phys, off, self.new)
            return _paged_attend(self.new, l, q, row_tab, self.cache_len,
                                 q_pos, kv_len)
        write_at, ring_kw = _ring_chunk(
            self.cfg, kind, self.cache["k"][l].shape[2], self.chunk_start,
            self.C)
        self._write_panes(k, v, (self.slot, 0, write_at, 0))
        if chunk_attention_path(self.cache, self.C, self.cfg.n_heads,
                                layer=l) == "live_blocks":
            from building_llm_from_scratch_tpu.ops.chunk_attention import (
                chunk_live_attention,
            )

            with _attention_scope(bool(ring_kw)):
                return chunk_live_attention(
                    q, self.new["k"][l], self.new["v"][l], self.slot,
                    self.chunk_start, kv_len[0],
                    window=ring_kw.get("window"),
                    interpret=jax.default_backend() != "tpu")
        K_row, V_row = (jax.lax.dynamic_slice(
            a, (self.slot, 0, 0, 0), (1,) + a.shape[1:])
            for a in (self.new["k"][l], self.new["v"][l]))
        with _attention_scope(bool(ring_kw)):
            return decode_attention(
                q, K_row, V_row, q_positions=q_pos, kv_length=kv_len,
                **ring_kw, **_layer_scales(self.new, l, self.slot))


class _RowsKV(_SlotKV):
    """Every row of the contiguous slot cache at once (``decode_slots``,
    Tq = 1; ``verify_slots``, Tq = k+1): each row appends its Tq fresh
    positions at ITS length and each query attends its own row's prefix to
    itself. THE one write rule and the one read rule of both: the
    speculative path's bit-parity with plain decode depends on the two
    never drifting. The two trace-time rules choose each one's form. With
    ``table`` ((S, max_pages) int32) rows are reached through the page
    table (below): candidate k/v scatter at per-row logical offsets,
    rejected tails sit past ``kv_length`` exactly as in the slot cache."""

    def __init__(self, cfg, cache, Tq: int, lengths, live=None, table=None,
                 cache_len=None):
        super().__init__(cfg, cache)
        self.Tq, self.lengths, self._live = Tq, lengths.astype(jnp.int32), live
        self.table = None if table is None else table.astype(jnp.int32)
        self.cache_len = cache_len

    @cached_property
    def positions(self):
        """Each row's Tq positions from its length on, (S, Tq). At Tq > 1
        they are CLAMPED: a row near capacity has draft positions past
        context_length-1; unclamped they would index past the positional
        tables (jnp.take's out-of-bounds fill is NaN) and the NaN v-pane
        poisons every query through the value einsum's 0*NaN. Clamped
        positions only ever affect TAIL candidates that can never be
        committed (prompt + budget <= max_len by admission), so every
        committable position keeps its exact positional encoding."""
        positions = self.lengths[:, None]
        if self.Tq > 1:
            positions = jnp.minimum(positions + jnp.arange(self.Tq)[None, :],
                                    self.cfg.context_length - 1)
        return positions

    @property
    def live(self):          # (S,) rows that decode -> their (S, Tq) positions
        return (None if self._live is None else jnp.broadcast_to(
            self._live[:, None], (self._live.shape[0], self.Tq)))

    def append_and_attend(self, l, kind, q, k, v):
        if self.table is not None:
            _refuse_ring(kind, "the paged pool")
            return self._through_pages(l, q, k, v)
        if self.Tq > 1:
            # k+1 positions may wrap a ring, and rejected ones cannot be
            # taken back from it
            _refuse_ring(kind, "a verify tick")
        write_at, ring_kw = _ring_step(
            self.cfg, kind, self.cache["k"][l].shape[2], self.lengths)
        K, V = self._append(l, k, v, write_at)
        return self._attend(l, q, K, V, ring_kw)

    @cached_property
    def live_rows(self):
        """The table of the rows that decode (``live_rows_table``) for the
        steps that walk them: made once a tick, not once a layer."""
        return live_rows_table(self._live)

    def through_state(self, l, run):
        """Every row one token on; a row that does not decode (free, or
        between two of its prefill chunks) keeps its tail and its state bit
        for bit: ``state_step_path`` says whether because the step walks the
        decoding rows alone, or by a select over the whole buffer."""
        if self.Tq > 1:
            raise ValueError("a verify tick has no way back from a state "
                             "its rejected drafts have moved")
        tail, state = self.cache["conv"][l], self.cache["state"][l]
        walk = state_step_path(
            self.cache, self.cfg.layer_kind(l), self.Tq, layer=l,
            rows_named=self._live is not None) == "live_rows"
        o, new_tail, new_state = run(
            tail, state, None, self.live_rows if walk else None)
        if self._live is not None:
            rows = lambda a: self._live[(slice(None),) + (None,) * (a.ndim - 1)]
            new_tail = jnp.where(rows(tail), new_tail, tail)
            if not walk:
                new_state = jnp.where(rows(state), new_state, state)
        self._keep_state(new_tail, new_state)
        return o

    @jax.named_scope("cache_update")
    def _append(self, l, k, v, write_at):
        """Per-row append of one layer's fresh k/v at each row's offset,
        quantizing on write under the int8 policy (codes + fp32 scale
        sidecars); the two forms of the write (``kv_append_path``) leave the
        same bits. Returns the appended (K, V) buffers."""
        from building_llm_from_scratch_tpu.ops.decode_step import (
            lane_window_append,
            quantize_kv,
            slot_cache_append,
        )

        cache, new = self.cache, self.new
        K, V = cache["k"][l], cache["v"][l]
        kt, vt = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
        if kv_append_path(cache, self.Tq) == "lane_window":
            K, V = lane_window_append(
                K, V, kt, vt, write_at,
                interpret=jax.default_backend() != "tpu")
        else:
            if _cache_quantized(cache):
                kt, ks = quantize_kv(kt)
                vt, vs = quantize_kv(vt)
                new["k_scale"].append(slot_cache_append(
                    cache["k_scale"][l], ks, write_at))
                new["v_scale"].append(slot_cache_append(
                    cache["v_scale"][l], vs, write_at))
            K = slot_cache_append(K, kt, write_at)
            V = slot_cache_append(V, vt, write_at)
        new["k"].append(K)
        new["v"].append(V)
        return K, V

    def _attend(self, l, q, K, V, ring_kw):
        """Each row's queries (at ``positions``) against its own appended
        prefix of (K, V). The two forms (``decode_attention_path``) are the
        same arithmetic: the kernel leaves unread what ``decode_attention``
        reads and masks to 0, and the rows that do not decode, whose
        outputs nothing reads."""
        with _attention_scope(bool(ring_kw)):
            if decode_attention_path(self.cache, self.Tq, self.cfg.n_heads,
                                     layer=l, ring=bool(ring_kw)
                                     ) == "live_blocks":
                from building_llm_from_scratch_tpu.ops.decode_step import (
                    live_block_attention,
                )

                return live_block_attention(
                    q, K, V, self.lengths + 1, live=self._live,
                    window=ring_kw.get("window"),
                    interpret=jax.default_backend() != "tpu")
            return decode_attention(q, K, V, q_positions=self.positions,
                                    kv_length=self.lengths + self.Tq,
                                    **ring_kw, **_layer_scales(self.new, l))

    def _through_pages(self, l, q, k, v):
        """The same append and read through ``table``. Write positions
        clamp to ``cache_len - 1`` (only ever binding for garbage lanes
        that are masked everywhere, mirroring ``positions``' clamp)."""
        S, Tq = k.shape[:2]
        P = self.cache["k"][l].shape[2]
        pos = jnp.minimum(self.lengths[:, None] + jnp.arange(Tq)[None, :],
                          self.cache_len - 1)                   # (S, Tq)
        phys = jnp.take_along_axis(self.table, pos // P, axis=1).reshape(-1)
        off = (pos % P).reshape(-1)
        for name, fresh in (("k", k), ("v", v)):
            _paged_scatter(self.cache, name,
                           fresh.reshape(S * Tq, *fresh.shape[2:]), phys,
                           off, self.new)
        if _use_paged_attn(self.cache, self.cfg, Tq):
            from building_llm_from_scratch_tpu.ops.decode_step import (
                paged_decode_attention,
            )

            return paged_decode_attention(q, self.new["k"][l],
                                          self.new["v"][l], self.table,
                                          self.lengths)
        return _paged_attend(self.new, l, q, self.table, self.cache_len,
                             self.positions, self.lengths + Tq)


# -- through a page table (KVCachePolicy.paged) ------------------------------
#
# The same programs with ONE layout change: a row no longer owns a
# contiguous (Tmax,) lane — a per-slot int32 page table maps each row's
# logical positions onto fixed-size pages of a shared pool (cache leaves are
# (n_pages, Hkv, page_tokens, hd)). The table rides every call as traced
# DATA against static shapes (the adapter-pool trick), so page churn —
# prefix hits, frees, eviction, oversubscription — never recompiles
# anything. ``cache_len`` is the static logical row length (the engine's
# ``_cache_len``), identical to the contiguous buffer width.
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


def _paged_view(leaf: jnp.ndarray, page_table: jnp.ndarray,
                cache_len: int) -> jnp.ndarray:
    """Gather a (rows, Hkv, cache_len, ...) row-major view out of the
    pool leaf (n_pages, Hkv, P, ...) through the page table (rows, M):
    the XLA reference for page-table attention — downstream
    ``decode_attention`` is completely unchanged, which is what pins
    bit-parity. The TPU pallas kernel
    (ops/decode_step.paged_decode_attention) computes the same gather
    without materializing it per layer."""
    g = leaf[page_table]                    # (rows, M, Hkv, P, ...)
    g = jnp.moveaxis(g, 2, 1)               # (rows, Hkv, M, P, ...)
    shape = g.shape
    g = g.reshape(shape[0], shape[1], shape[2] * shape[3], *shape[4:])
    return g[:, :, :cache_len]


def _use_paged_attn(cache: Params, cfg: ModelConfig, Tq: int) -> bool:
    """Route decode attention through the pallas page-gather kernel
    (ops/decode_step.paged_decode_attention). Opt-in via BLLM_PAGED_ATTN=1
    on TPU — the same off-until-hardware-A/B discipline as BLLM_BGMV — and
    only for unquantized pools of kernel-eligible shape; the XLA gather
    view is the reference."""
    import os as _os

    if jax.default_backend() != "tpu" or _cache_quantized(cache):
        return False
    if _os.environ.get("BLLM_PAGED_ATTN", "0") != "1":
        return False
    from building_llm_from_scratch_tpu.ops.decode_step import (
        supports_paged_shape,
    )

    return supports_paged_shape(Tq, cache["k"][0].shape[2], cfg.head_dim)


def _paged_attend(new: Params, l: int, q, table, cache_len: int,
                  q_positions, kv_length) -> jnp.ndarray:
    """Attend over layer ``l``'s row views AFTER its paged append (the
    paged sibling of reading ``new['k'][l]`` plus ``_layer_scales``)."""
    view = lambda name: _paged_view(new[name][l], table, cache_len)
    scales = ({"k_scale": view("k_scale"), "v_scale": view("v_scale")}
              if _cache_quantized(new) else {})
    with _attention_scope(False):
        return decode_attention(q, view("k"), view("v"),
                                q_positions=q_positions,
                                kv_length=kv_length, **scales)


# -- the entry points: one access object, one pass --------------------------

def forward_with_cache(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                       cache: Params,
                       blocks_list: Optional[list] = None,
                       lora: Optional[Params] = None,
                       lora_scaling=1.0,
                       lora_blocks_list: Optional[list] = None,
                       valid_len=None) -> Tuple[jnp.ndarray, Params]:
    """Decode forward: process ``tokens`` (B, Tq) given ``cache`` holding
    ``cache['length']`` valid positions; returns (fp32 logits (B, Tq, V),
    updated cache). Static shapes throughout — jit-friendly. Pass
    ``blocks_list`` (from ``unstack_blocks``; ``lora_blocks_list`` from
    ``unstack_lora_blocks``) when calling inside a sampling loop so the
    per-layer weight slices are hoisted out of it. ``valid_len`` (a model
    with 'linear' layers, a prompt right-padded to its bucket): the first
    that many tokens are real; the padding writes keys and values that the
    caller's reset of ``length`` masks, but a state it must not move.

    Contract: the caller must ensure ``cache['length'] + Tq <= max_length``
    (the cache allocation). Under jit an overflow cannot raise —
    ``dynamic_update_slice`` would clamp the write offset and silently
    overwrite the newest entries. The generation loop sizes its cache to
    cover the full decode so this never triggers.
    """
    adapter = None
    if lora is not None or lora_blocks_list is not None:
        if lora_blocks_list is None:
            lora_blocks_list = unstack_lora_blocks(lora, cfg)
        adapter = {"lora_blocks": lora_blocks_list, "scaling": lora_scaling,
                   "head": lora["head"]["weight"] if lora is not None
                   else None}
    return _slot_pass(params, cfg, tokens,
                      _SharedLengthKV(cfg, cache, tokens.shape[1], valid_len),
                      blocks_list=blocks_list, adapter=adapter)


def prefill_into_slot(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                      prompt_len: jnp.ndarray, slot: jnp.ndarray,
                      cache: Params, blocks_list: Optional[list] = None,
                      adapter: Optional[Params] = None
                      ) -> Tuple[jnp.ndarray, Params]:
    """Run one request's prompt (``tokens`` (1, Tpb), right-padded to its
    length bucket) and write its k/v panes into row ``slot`` of the slot
    cache (``_PromptKV``); returns (last-real-position logits (V,),
    updated cache).

    ``adapter``: {"pool", "scaling", "ids" (1,)} — the request's LoRA
    adapter applied unmerged at every adapted projection (id −1 = base).
    The prompt's k/v land in the slot ALREADY adapter-transformed, so
    decode ticks attend to a prefix consistent with the same adapter.
    """
    kv = _PromptKV(cfg, cache, tokens.shape[1], slot, prompt_len)
    logits, new = _slot_pass(params, cfg, tokens, kv,
                             blocks_list=blocks_list, adapter=adapter)
    return logits[0, 0], new


def prefill_chunk_into_slot(params: Params, cfg: ModelConfig,
                            tokens: jnp.ndarray, chunk_start: jnp.ndarray,
                            prompt_len: jnp.ndarray, slot: jnp.ndarray,
                            cache: Params,
                            blocks_list: Optional[list] = None,
                            adapter: Optional[Params] = None, *,
                            page_table: Optional[jnp.ndarray] = None,
                            cache_len: Optional[int] = None
                            ) -> Tuple[jnp.ndarray, Params]:
    """Chunked prefill: process ``tokens`` (1, C) — the prompt span
    [chunk_start, chunk_start + C), right-padded past ``prompt_len`` —
    against row ``slot`` whose positions [0, chunk_start) already hold
    valid KV (earlier chunks, or a copied prefix pane,
    serving/kvcache.py). Returns (logits at the clamped position
    ``prompt_len - 1 - chunk_start`` (V,), updated cache). With
    ``page_table`` ((n_slots, max_pages) int32, and ``cache_len``) the row
    is reached through it, else it is the slot cache's own (``_ChunkKV``).

    The chunk width C is STATIC: every prompt of every length prefills
    through this ONE compiled program (chunk_start/prompt_len/slot are
    data) — both the one-compiled-program invariant and the per-tick
    prefill bound. A 2k-token prompt becomes 2k/C short calls the
    engine interleaves with decode ticks instead of one tick-stalling
    program.
    """
    kv = _ChunkKV(cfg, cache, tokens.shape[1], slot, chunk_start, prompt_len,
                  page_table, cache_len)
    logits, new = _slot_pass(params, cfg, tokens, kv,
                             blocks_list=blocks_list, adapter=adapter)
    return logits[0, 0], new


def verify_slots(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                 lengths: jnp.ndarray, cache: Params,
                 blocks_list: Optional[list] = None,
                 adapter: Optional[Params] = None,
                 live: Optional[jnp.ndarray] = None,
                 expert_rows: Optional[list] = None, *,
                 page_table: Optional[jnp.ndarray] = None,
                 cache_len: Optional[int] = None
                 ) -> Tuple[jnp.ndarray, Params]:
    """The tick over the whole slot batch, Tq >= 1 tokens a row:
    ``tokens`` (S, Tq) is each slot's last accepted token followed, under
    speculation, by its k drafted candidates; ``lengths`` (S,) the valid
    cache prefix per row. Returns (fp32 logits (S, Tq, V), updated cache).
    With ``page_table`` ((S, max_pages) int32, and ``cache_len``) rows are
    reached through it, else they are the slot cache's own (``_RowsKV``).

    ONE forward scores all Tq positions: position j's logits condition on
    [cache, tokens[:, :j+1]], so they are the model's true next-token
    distribution exactly when the drafts before j were all accepted — the
    accept rule (generate.accept_draft_tokens) commits only such
    prefixes. All Tq candidate k/v are appended at per-row offsets; the
    engine advances ``lengths`` by the ACCEPTED count only, so a rejected
    tail's entries sit past the valid prefix — masked by ``kv_length``
    everywhere and overwritten by the next tick's append. No rollback copy
    exists because none is needed.

    Per-query causality rides ``decode_attention``'s per-row masks: query
    j at absolute position lengths+j attends keys at positions <=
    lengths+j, i.e. the real prefix plus the drafts before it — never the
    drafts after it. k is STATIC: every acceptance count 0..k+1 flows
    through this one compiled program, and free or finished slots compute
    garbage rows the engine ignores (their appends land at the row's next
    write position and are overwritten before anything reads them), so the
    shapes never change and XLA compiles exactly one tick program.

    ``adapter``: {"pool", "scaling", "ids" (S,)} — per-SLOT LoRA adapters,
    their identity DATA (the note at the top of this file): any mix of ids
    runs through this one compiled program.

    ``live`` (S,) bool, for a sparse model: the rows that decode. The
    others (free slots, slots in mid-prefill) are routed to no expert, so
    they read no expert's weights and count for none. ``expert_rows``: a
    list that collects each sparse layer's rows per held expert (H,).
    """
    kv = _RowsKV(cfg, cache, tokens.shape[1], lengths, live, page_table,
                 cache_len)
    return _slot_pass(params, cfg, tokens, kv, blocks_list=blocks_list,
                      adapter=adapter, expert_rows=expert_rows)


def decode_slots(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                 *args, **kw) -> Tuple[jnp.ndarray, Params]:
    """One decode tick: ``verify_slots`` (its arguments) at Tq = 1
    (``tokens`` (S, 1) are each slot's last accepted token), where the
    append is one in-place ``lane_window_append`` a layer and the attention
    the live-block kernel (in the layout ``head_dim`` gives the cache)
    wherever the two rules admit them; returns (fp32 logits (S, V), updated
    cache)."""
    logits, new = verify_slots(params, cfg, tokens, *args, **kw)
    return logits[:, 0], new
