"""Model configuration registry for the TPU-native framework.

Capability parity with the reference's config system:
  - GPT-2 size table       (reference: Models/GPT2/config.py:30-35)
  - LLaMA family configs   (reference: Models/Llama/config.py:8-91)
  - context-length clamp with RoPE theta rescaling
                           (reference: Models/Llama/config.py:117-124,
                            Models/Llama/common_components.py:38-51)
  - dtype injection + debug tiny-model override
                           (reference: build_components.py:67-80)

Unlike the reference (per-model config dicts consumed by three near-duplicate
model classes), every architecture here is a single frozen ``ModelConfig``
consumed by ONE shared transformer implementation
(models/transformer.py). The dataclass is hashable so it can be a static
argument to ``jax.jit``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp

# ---------------------------------------------------------------------------
# dtype mapping (reference: utils.py:30-41)
# ---------------------------------------------------------------------------

DTYPE_MAP = {
    "fp32": jnp.float32,
    "fp16": jnp.float16,
    "bf16": jnp.bfloat16,
}

DTYPE_BYTES = {"fp32": 4, "fp16": 2, "bf16": 2}


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """LLaMA-3.1-style RoPE frequency smoothing parameters.

    Mirrors the ``rope_freq`` dicts of the reference
    (Models/Llama/config.py:43-48,63-68) as a hashable dataclass.
    """

    factor: float
    low_freq_factor: float
    high_freq_factor: float
    original_context_length: int


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """A single architecture description covering GPT-2 and all LLaMA variants.

    The reference implements three near-duplicate attention/block/model stacks
    (Models/GPT2/GPT2.py:6, Models/Llama/Llama2.py:61, Models/Llama/Llama3.py:108);
    here the differences collapse into data:

      norm        'layernorm' (GPT-2) | 'rmsnorm' (LLaMA)
      positional  'learned'   (GPT-2) | 'rope'    (LLaMA)
      activation  'gelu'      (GPT-2) | 'swiglu'  (LLaMA)
      n_kv_groups n_heads == MHA (GPT-2, LLaMA-2) | < n_heads == GQA (LLaMA-3)
    """

    name: str
    vocab_size: int
    context_length: int
    emb_dim: int
    n_heads: int
    n_layers: int
    hidden_dim: int                      # FFN hidden width
    n_kv_groups: int                     # == n_heads for full MHA
    norm: str = "layernorm"              # 'layernorm' | 'rmsnorm'
    positional: str = "learned"          # 'learned' | 'rope' | 'none'
    activation: str = "gelu"             # 'gelu' | 'swiglu'
    qkv_bias: bool = False               # GPT-2 --load_weights sets True
    attn_out_bias: bool = False          # GPT-2 uses biased out-proj
    mlp_bias: bool = False               # GPT-2 uses biased MLP linears
    norm_bias: bool = False              # LayerNorm bias (GPT-2)
    rope_base: float = 10_000.0
    rope_scaling: Optional[RopeScaling] = None
    drop_rate: float = 0.0
    eos_id: int = 50256
    eos_text: str = "<|endoftext|>"
    dtype: str = "fp32"                  # params + activations
    rmsnorm_eps: float = 1e-5
    layernorm_eps: float = 1e-5
    use_actv_ckpt: bool = False          # jax.remat on the scanned block body
    attn_impl: str = "auto"              # 'auto' | 'xla' | 'pallas'
    # -- blocks beyond the dense pre-norm one (all off by default) ---------
    attn_head_dim: int = 0               # 0 = emb_dim // n_heads
    parallel_block: bool = False         # one norm; attention and FFN both
    #                                      read it, both add to the residual
    tie_embeddings: bool = False         # logits = h @ tok_emb.T, no head leaf
    #: the kinds of one period of layers, repeated down the depth:
    #: 'sliding' (attends to the last ``sliding_window`` positions, itself
    #: included) | 'full' | 'linear' (no keys and values: a gated delta
    #: rule over a recurrent state, below) | 'ssm' (none either: a
    #: selective state space, below). Empty = every layer full.
    layer_kinds: Tuple[str, ...] = ()
    sliding_window: int = 0
    rope_interleaved: bool = False       # rotate pairs (2i, 2i+1), not halves
    full_layers_rope: bool = True        # False: 'full' layers carry no positions
    #: sparse experts (0 = the dense MLP): a float32 sigmoid router over
    #: ``n_routed_experts``, the ``n_experts_per_tok`` largest renormalised,
    #: beside ``n_shared_experts`` always-on experts whose outputs are
    #: averaged; every expert a SwiGLU of width ``hidden_dim``.
    n_routed_experts: int = 0
    n_experts_per_tok: int = 0
    n_shared_experts: int = 0
    #: the global ids of the routed experts THIS chip holds (empty = all):
    #: routing is over all, the routed sum over the held ones only
    experts_held: Tuple[int, ...] = ()
    #: a 'full' or 'sliding' layer's output times sigmoid(n @ wg), the
    #: query's width, before the output projection
    attn_out_gate: bool = False
    #: 'linear' layers (ops/linear_attention.py): ``linear_heads`` heads of
    #: ``linear_head_dim`` (keys and values alike), a causal depthwise
    #: convolution of ``linear_conv`` taps on q, k and v, a per-channel
    #: decay and an output gate each factorised through ``linear_gate_rank``,
    #: a step size sigmoid(.) in (0, 1), or (0, 2) with ``linear_neg_eigval``
    linear_heads: int = 0
    linear_head_dim: int = 0
    linear_conv: int = 4
    linear_gate_rank: int = 0
    linear_neg_eigval: bool = False
    #: 'ssm' layers (ops/selective_scan.py; Mamba-1 with the Jamba family's
    #: norms on dt, B and C): ``ssm_inner`` channels, each a diagonal
    #: recurrence over ``ssm_state`` float32 states, behind a causal
    #: depthwise convolution of ``ssm_conv`` taps (with a bias; the
    #: projections have none); step, B and C are read from the channels
    #: through ``ssm_dt_rank`` + 2 x ``ssm_state`` columns
    ssm_inner: int = 0
    ssm_state: int = 0
    ssm_dt_rank: int = 0
    ssm_conv: int = 4

    def __post_init__(self):
        # a JSON file hands lists; the config must stay hashable
        for name in ("layer_kinds", "experts_held"):
            object.__setattr__(self, name, tuple(getattr(self, name)))
        if self.layer_kinds:
            if set(self.layer_kinds) - {"sliding", "full", "linear", "ssm"}:
                raise ValueError(f"layer_kinds {self.layer_kinds}: each is "
                                 "'sliding', 'full', 'linear' or 'ssm'")
            if self.n_layers % len(self.layer_kinds):
                raise ValueError(
                    f"n_layers {self.n_layers} is not whole periods of "
                    f"{len(self.layer_kinds)} layers")
            if "sliding" in self.layer_kinds and self.sliding_window < 1:
                raise ValueError("'sliding' layers need sliding_window >= 1")
            if self.has_linear_layers and not (
                    self.linear_heads and self.linear_head_dim
                    and self.linear_gate_rank and self.linear_conv > 1):
                raise ValueError(
                    "'linear' layers need linear_heads, linear_head_dim, "
                    "linear_gate_rank and linear_conv >= 2")
            if "ssm" in self.layer_kinds and not (
                    self.ssm_inner and self.ssm_state and self.ssm_dt_rank
                    and self.ssm_conv > 1):
                raise ValueError(
                    "'ssm' layers need ssm_inner, ssm_state, ssm_dt_rank "
                    "and ssm_conv >= 2")
        if self.n_routed_experts:
            held = self.held_experts
            if not (0 < self.n_experts_per_tok <= self.n_routed_experts):
                raise ValueError("n_experts_per_tok must be in "
                                 "[1, n_routed_experts]")
            if (len(set(held)) != len(held) or min(held) < 0
                    or max(held) >= self.n_routed_experts):
                raise ValueError(f"experts_held {held}: distinct ids under "
                                 f"{self.n_routed_experts}")

    @property
    def head_dim(self) -> int:
        return self.attn_head_dim or self.emb_dim // self.n_heads

    @property
    def held_experts(self) -> Tuple[int, ...]:
        return self.experts_held or tuple(range(self.n_routed_experts))

    @property
    def is_moe(self) -> bool:
        return self.n_routed_experts > 0

    def layer_kind(self, layer: int) -> str:
        if not self.layer_kinds:
            return "full"
        return self.layer_kinds[layer % len(self.layer_kinds)]

    @property
    def has_window_layers(self) -> bool:
        return "sliding" in self.layer_kinds

    @property
    def has_linear_layers(self) -> bool:
        return "linear" in self.layer_kinds

    def layers_of(self, *kinds: str) -> Tuple[int, ...]:
        """The layers of these kinds, in order."""
        return tuple(l for l in range(self.n_layers)
                     if self.layer_kind(l) in kinds)

    @property
    def has_state_layers(self) -> bool:
        return bool(self.state_layers)

    @property
    def state_layers(self) -> Tuple[int, ...]:
        """The layers whose memory of a sequence is a recurrent state and
        a convolution's tail, not keys and values: 'linear' and 'ssm'."""
        return self.layers_of("linear", "ssm")

    def state_shapes(self, kind: str) -> Tuple[Tuple[int, ...],
                                               Tuple[int, ...]]:
        """One row's memory in a layer of this kind: (the convolution's
        tail (K-1, channels), in the activation type; the state, float32:
        a matrix a head, or an 'ssm' layer's N states a channel with the
        channels last, on a TPU's lanes)."""
        if kind == "ssm":
            return ((self.ssm_conv - 1, self.ssm_inner),
                    (self.ssm_state, self.ssm_inner))
        H, hd = self.linear_heads, self.linear_head_dim
        return (self.linear_conv - 1, 3 * H * hd), (H, hd, hd)

    @property
    def linear_width(self) -> int:
        return self.linear_heads * self.linear_head_dim

    @property
    def jax_dtype(self):
        return DTYPE_MAP[self.dtype]

    @property
    def uses_rope(self) -> bool:
        return self.positional == "rope"

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def num_params(self, exclude_embeddings: bool = False,
                   active: bool = False) -> int:
        """Analytic parameter count (used for memory estimates, parity with
        reference utils.py:112-129 which counts live tensors): the parameters
        HELD here (a sparse model's held experts, not the published count).
        ``active``: those one token multiplies against here instead (a
        sparse model's router, shared experts and, of its
        ``n_experts_per_tok`` routed experts, the share held here, as an
        even router spreads them)."""
        d, v, t = self.emb_dim, self.vocab_size, self.context_length
        hd, nh, nkv, f = self.head_dim, self.n_heads, self.n_kv_groups, self.hidden_dim
        emb = v * d + (t * d if self.positional == "learned" else 0)
        qkv = d * (nh * hd) + 2 * d * (nkv * hd)
        if self.qkv_bias:
            qkv += nh * hd + 2 * nkv * hd
        attn_out = (nh * hd) * d + (d if self.attn_out_bias else 0)
        if self.attn_out_gate:
            attn_out += d * (nh * hd)
        # a 'linear' layer's mixer: q, k, v, out; the conv's taps; A_log a
        # head, dt_bias a channel; the step size; decay and output gate
        # through their rank; the scale of the norm on a head's output
        w, r = self.linear_width, self.linear_gate_rank
        linear = (4 * d * w + self.linear_conv * 3 * w + self.linear_heads
                  + w + d * self.linear_heads + 2 * (d * r + r * w)
                  + self.linear_head_dim)
        n_linear = len(self.layers_of("linear"))
        # an 'ssm' layer's mixer: in (u and z) and out; the conv's taps and
        # bias; dt, B and C read from the channels, a norm's scale on each;
        # dt back to the channels with its bias; A_log a channel and state;
        # the skip D
        i, n, r = self.ssm_inner, self.ssm_state, self.ssm_dt_rank
        ssm = (3 * d * i + (self.ssm_conv + 1) * i + i * (r + 2 * n)
               + (r + 2 * n) + r * i + i + i * n + i)
        n_ssm = len(self.layers_of("ssm"))
        if self.is_moe:
            held = len(self.held_experts)
            n_routed = (self.n_experts_per_tok * held / self.n_routed_experts
                        if active else held)
            mlp = (d * self.n_routed_experts
                   + round(3 * d * f * (n_routed + self.n_shared_experts)))
        elif self.activation == "swiglu":
            mlp = 3 * d * f
        else:
            mlp = 2 * d * f + ((f + d) if self.mlp_bias else 0)
        norm_w = d * (2 if self.norm_bias else 1)
        per_layer = mlp + (1 if self.parallel_block else 2) * norm_w
        final_norm = d * (2 if self.norm_bias else 1)
        head = 0 if self.tie_embeddings else d * v
        total = (per_layer * self.n_layers + linear * n_linear + ssm * n_ssm
                 + (qkv + attn_out) * (self.n_layers - n_linear - n_ssm)
                 + final_norm + head)
        if not exclude_embeddings or self.tie_embeddings:
            total += emb
        return total


#: what a model with window layers ("window"), sparse experts ("moe") or
#: layers that hold a recurrent state ("state": 'linear' and 'ssm' alike)
#: does not run through yet: (feature, the property that refuses it, why).
#: ONE list, asked by the flags' checks (``args.perform_checks``) and by the
#: serving engine at construction, by what the config IS, never by its name
#: (PERF.md section 7 lists them; ROADMAP R1 / R2 / R4 say what each takes)
UNSUPPORTED = (
    ("paged", "window",
     "the paged pool maps a slot's positions onto pages one to one and has "
     "no ring: serve it on the slot cache"),
    ("prefix_cache", "window",
     "a prefix pane is a slot's first positions and a ring keeps only its "
     "last: serve it without --serve_prefix_cache"),
    ("int8_cache", "window",
     "the int8 cache's scale sidecars are not ring-indexed: serve it with "
     "the model's own cache type"),
    ("speculation", "window",
     "a verify tick appends k+1 positions that may wrap a ring and rejected "
     "ones cannot be taken back from it: serve it with spec_k 0"),
    ("speculation", "moe",
     "a verify tick would route its rejected drafts to experts and count "
     "them in the experts' rows: serve it with spec_k 0"),
    ("tensor_parallel", "either",
     "experts and rings have no tensor-parallel split yet: run it on one "
     "chip, or under dp, fsdp or zero1, which shard it like any leaf "
     "(replicas behind a router are fine)"),
    ("pipeline_parallel", "either",
     "a pipeline stage runs layers of one kind and no expert layer: run "
     "it under dp, fsdp or zero1"),
    ("sequence_parallel", "either",
     "the ring schedule of a sequence split has no window term and the "
     "expert dispatch no split over it"),
    ("lora", "moe",
     "LoRA adapters attach to the dense MLP's projections and the expert "
     "layer has none: run it without adapters"),
    ("paged", "state",
     "a page holds positions and a recurrent state has none: serve it on "
     "the slot cache"),
    ("prefix_cache", "state",
     "a prefix pane is a slot's first positions; the state after them "
     "would have to be kept beside it and is not: serve it without "
     "--serve_prefix_cache"),
    ("int8_cache", "state",
     "the recurrent state is float32 and has no int8 form: serve it with "
     "the model's own cache type"),
    ("speculation", "state",
     "a rejected draft has already moved the recurrent state and there is "
     "no copy to go back to: serve it with spec_k 0"),
    ("tensor_parallel", "state",
     "the state's heads or channels have no tensor-parallel split yet: run "
     "it on one chip, or under dp, fsdp or zero1"),
    ("pipeline_parallel", "state",
     "a pipeline stage runs layers of one kind: run it under dp, fsdp or "
     "zero1"),
    ("sequence_parallel", "state",
     "a sequence split would hand the state from shard to shard and no "
     "schedule does"),
    ("lora", "state",
     "LoRA adapters attach to attention's projections and a layer that "
     "holds a state has others: run it without adapters"),
)


def refuse_unsupported(cfg: "ModelConfig", **features) -> None:
    """``features``: a name of ``UNSUPPORTED`` -> whether the caller is
    about to use it. Raises one sentence for the first that ``cfg``'s window
    layers, sparse experts or recurrent states do not support: nothing is
    silently wrong."""
    unknown = set(features) - {name for name, _, _ in UNSUPPORTED}
    if unknown:
        raise TypeError(f"refuse_unsupported: no such feature {unknown}")
    has = {"window": cfg.has_window_layers, "moe": cfg.is_moe,
           "state": cfg.has_state_layers}
    has["either"] = has["window"] or has["moe"]
    for name, needs, why in UNSUPPORTED:
        if features.get(name) and has[needs]:
            kinds = " and ".join(
                what for on, what in (
                    (has["window"], "window layers"),
                    (has["moe"], "sparse experts"),
                    (cfg.has_linear_layers, "linear-attention layers"),
                    ("ssm" in cfg.layer_kinds, "state-space layers"))
                if on)
            raise ValueError(f"{cfg.name} ({kinds}): {why}")


# ---------------------------------------------------------------------------
# RoPE theta rescale (reference: Models/Llama/common_components.py:38-51)
# ---------------------------------------------------------------------------

def rescale_theta(theta_old: float, context_length_old: int,
                  context_length_new: int) -> float:
    """Linearly rescale RoPE base frequency when the context length changes."""
    return theta_old * (context_length_new / context_length_old)


# ---------------------------------------------------------------------------
# GPT-2 registry (reference: Models/GPT2/config.py:6-35)
# ---------------------------------------------------------------------------

def _gpt2(name: str, emb_dim: int, n_heads: int, n_layers: int) -> ModelConfig:
    return ModelConfig(
        name=name,
        vocab_size=50257,
        context_length=1024,
        emb_dim=emb_dim,
        n_heads=n_heads,
        n_layers=n_layers,
        hidden_dim=4 * emb_dim,
        n_kv_groups=n_heads,
        norm="layernorm",
        positional="learned",
        activation="gelu",
        qkv_bias=False,
        attn_out_bias=True,
        mlp_bias=True,
        norm_bias=True,
        drop_rate=0.1,
        eos_id=50256,
        eos_text="<|endoftext|>",
    )


GPT2_CONFIGS = {
    "124M": _gpt2("gpt2-124M", 768, 12, 12),
    "355M": _gpt2("gpt2-355M", 1024, 16, 24),
    "774M": _gpt2("gpt2-774M", 1280, 20, 36),
    "1.5B": _gpt2("gpt2-1.5B", 1600, 25, 48),
}


# ---------------------------------------------------------------------------
# LLaMA registry (reference: Models/Llama/config.py:8-91)
# ---------------------------------------------------------------------------
# NOTE (reference defect §2.3 #4): LLAMA2_CONFIG_7B has no eos_id/eos_text in
# the reference even though the trainer requires both. We supply LLaMA-2's
# actual sentencepiece ids (eos=2, '</s>') so the llama2 path works.

LLAMA2_CONFIG_7B = ModelConfig(
    name="llama2-7B",
    vocab_size=32_000,
    context_length=4096,
    emb_dim=4096,
    n_heads=32,
    n_layers=32,
    hidden_dim=11_008,
    n_kv_groups=32,                      # full MHA
    norm="rmsnorm",
    positional="rope",
    activation="swiglu",
    rope_base=10_000.0,
    eos_id=2,
    eos_text="</s>",
    dtype="bf16",
)

LLAMA3_CONFIG_8B = ModelConfig(
    name="llama3-8B",
    vocab_size=128_256,
    context_length=8192,
    emb_dim=4096,
    n_heads=32,
    n_layers=32,
    hidden_dim=14_336,
    n_kv_groups=8,
    norm="rmsnorm",
    positional="rope",
    activation="swiglu",
    rope_base=500_000.0,
    eos_id=128_001,
    eos_text="<|end_of_text|>",
    dtype="bf16",
)

LLAMA31_CONFIG_8B = LLAMA3_CONFIG_8B.replace(
    name="llama3_1-8B",
    context_length=131_072,
    rope_scaling=RopeScaling(
        factor=8.0, low_freq_factor=1.0, high_freq_factor=4.0,
        original_context_length=8192,
    ),
)

LLAMA32_CONFIG_1B = ModelConfig(
    name="llama3_2-1B",
    vocab_size=128_256,
    context_length=131_072,
    emb_dim=2048,
    n_heads=32,
    n_layers=16,
    hidden_dim=8192,
    n_kv_groups=8,
    norm="rmsnorm",
    positional="rope",
    activation="swiglu",
    rope_base=500_000.0,
    rope_scaling=RopeScaling(
        factor=32.0, low_freq_factor=1.0, high_freq_factor=4.0,
        original_context_length=8192,
    ),
    eos_id=128_001,
    eos_text="<|end_of_text|>",
    dtype="bf16",
)


# The long-context pretrain tier (PR 20): a ~350M GQA model whose
# NATIVE context is 32k — not a clamped-down big model. Sized so the
# sequence dimension dominates activation memory (seq 32768 >> emb
# 1024), which is exactly the regime sequence-parallel training
# (--sp, ops/ring_attention.py) exists for: one device cannot hold a
# 32k activation pane, sp shards it. rope_base 500k follows the
# llama3 long-context recipe; no rope_scaling because 32k IS the
# training context, not an extension of a shorter one. Train it with
# ``--model longctx --num_params 32k --target_context_length 0`` (0
# keeps the native 32k) or via ``bench.py pretrain_longctx``.
LONGCTX_CONFIG_32K = ModelConfig(
    name="longctx-32k",
    vocab_size=50_257,
    context_length=32_768,
    emb_dim=1024,
    n_heads=16,
    n_layers=24,
    hidden_dim=4096,
    n_kv_groups=4,
    norm="rmsnorm",
    positional="rope",
    activation="swiglu",
    rope_base=500_000.0,
    eos_id=50_256,
    eos_text="<|endoftext|>",
    dtype="bf16",
)


# Command A+ (CohereLabs/command-a-plus-05-2026, model_type cohere2_moe;
# the language model): every layer a PARALLEL block (one bias-free
# LayerNorm feeds attention and the feed-forward, both add to the
# residual); layers in periods of four, three sliding-window (4096, RoPE
# theta 50000 over interleaved pairs) then one full-attention layer with no
# positions; every feed-forward 128 sigmoid-routed experts (top-8,
# renormalised) beside 4 shared experts whose outputs are averaged; tied
# embedding. 218B parameters, 25B active: no one chip holds it. A chip of
# an expert-parallel deployment is this config with ``experts_held`` (and,
# in the benchmark's file, fewer layers and a slice of the vocabulary).
COMMAND_A_PLUS_CONFIG = ModelConfig(
    name="command-a-plus-05-2026",
    vocab_size=262_144,
    context_length=200_000,
    emb_dim=4096,
    n_heads=128,
    n_layers=32,
    hidden_dim=4096,                     # one expert's width
    n_kv_groups=8,
    attn_head_dim=128,
    norm="layernorm",
    positional="rope",
    activation="swiglu",
    rope_base=50_000.0,
    rope_interleaved=True,
    full_layers_rope=False,
    parallel_block=True,
    tie_embeddings=True,
    layer_kinds=("sliding", "sliding", "sliding", "full"),
    sliding_window=4096,
    n_routed_experts=128,
    n_experts_per_tok=8,
    n_shared_experts=4,
    eos_id=255_001,
    eos_text="<|END_OF_TURN_TOKEN|>",
    dtype="bf16",
)


# Solar-Open2-250B (upstage/Solar-Open2-250B, model_type solar_open2): 48
# serial RMSNorm blocks in periods of four, one gated NoPE GQA layer (64
# query heads over 8 key-value heads of 128, the output times a sigmoid gate
# of the query's width) then three gated-delta-rule linear layers (64 heads
# of 128, conv 4, per-channel decay, step size in (0, 2)); no positions
# anywhere; every feed-forward 320 sigmoid-routed experts of width 1280
# (top-8, renormalised) beside one shared expert; embedding and head untied.
# 250B parameters, 15B active: a chip of an expert-parallel deployment is
# this config with ``experts_held`` (and, in the benchmark's file, fewer
# layers and a slice of the vocabulary).
SOLAR_OPEN2_CONFIG = ModelConfig(
    name="solar-open2-250b",
    vocab_size=196_608,
    context_length=1_048_576,
    emb_dim=4096,
    n_heads=64,
    n_layers=48,
    hidden_dim=1280,                     # one expert's width
    n_kv_groups=8,
    attn_head_dim=128,
    norm="rmsnorm",
    positional="none",
    activation="swiglu",
    layer_kinds=("full", "linear", "linear", "linear"),
    attn_out_gate=True,
    linear_heads=64,
    linear_head_dim=128,
    linear_conv=4,
    linear_gate_rank=128,
    linear_neg_eigval=True,
    n_routed_experts=320,
    n_experts_per_tok=8,
    n_shared_experts=1,
    eos_id=2,
    eos_text="<|endoftext|>",
    dtype="bf16",
)


# AI21-Jamba2-3B (ai21labs/AI21-Jamba2-3B, model_type jamba): 28 serial
# RMSNorm blocks (eps 1e-6) in periods of fourteen, thirteen Mamba-1
# selective-state-space layers (5120 channels of 16 states behind a 4-tap
# convolution; dt, B and C each through a norm of its own) around one NoPE
# multi-query attention layer (20 query heads, ONE key-value head of 128) at
# index 7; no positions anywhere; every feed-forward a dense SwiGLU of 8192
# (``num_experts`` 1); head tied to the embedding. 3.03B parameters: one chip
# holds it whole.
JAMBA2_3B_CONFIG = ModelConfig(
    name="ai21-jamba2-3b",
    vocab_size=65_536,
    context_length=262_144,
    emb_dim=2560,
    n_heads=20,
    n_layers=28,
    hidden_dim=8192,
    n_kv_groups=1,
    attn_head_dim=128,
    norm="rmsnorm",
    rmsnorm_eps=1e-6,
    positional="none",
    activation="swiglu",
    tie_embeddings=True,
    layer_kinds=("ssm",) * 7 + ("full",) + ("ssm",) * 6,
    ssm_inner=5120,
    ssm_state=16,
    ssm_dt_rank=160,
    ssm_conv=4,
    eos_id=2,
    eos_text="<|endoftext|>",
    dtype="bf16",
)


# Supported model types and their sizes (reference: utils.py:44-50)
MODEL_PARAMS_MAPPING = {
    "GPT2": ["124M", "355M", "774M", "1.5B"],
    "llama2": ["7B"],
    "llama3": ["8B"],
    "llama3_1": ["8B"],
    "llama3_2": ["1B"],
    "longctx": ["32k"],
    "command_a_plus": ["218B"],
    "solar_open2": ["250B"],
    "jamba2": ["3B"],
}

_LLAMA_REGISTRY = {
    ("command_a_plus", "218B"): COMMAND_A_PLUS_CONFIG,
    ("solar_open2", "250B"): SOLAR_OPEN2_CONFIG,
    ("jamba2", "3B"): JAMBA2_3B_CONFIG,
    ("llama2", "7B"): LLAMA2_CONFIG_7B,
    ("llama3", "8B"): LLAMA3_CONFIG_8B,
    ("llama3_1", "8B"): LLAMA31_CONFIG_8B,
    ("llama3_2", "1B"): LLAMA32_CONFIG_1B,
    ("longctx", "32k"): LONGCTX_CONFIG_32K,
}


def get_config_gpt2(num_params: str) -> ModelConfig:
    """Reference: Models/GPT2/config.py:38-50."""
    num_params = str(num_params)
    if num_params not in GPT2_CONFIGS:
        raise ValueError(
            f"GPT-2 config for model '{num_params}' not found. "
            f"Available options: {list(GPT2_CONFIGS.keys())}"
        )
    return GPT2_CONFIGS[num_params]


def get_config_llama(num_params: str, model_name: str,
                     target_context_length: Optional[int] = 1024) -> ModelConfig:
    """Look up a LLaMA config, optionally clamping context length.

    Reference (Models/Llama/config.py:97-126) force-downscales every LLaMA
    context to 1024 with a linear theta rescale; we reproduce that default but
    make it parameterizable (pass ``None`` to keep the native context), and we
    do NOT mutate a shared registry entry (reference defect §2.3 #5).
    """
    key = (model_name, str(num_params))
    if key not in _LLAMA_REGISTRY:
        raise ValueError(
            f"A {model_name} model with {num_params} parameters does not exist."
        )
    cfg = _LLAMA_REGISTRY[key]
    if target_context_length and cfg.context_length != target_context_length:
        cfg = cfg.replace(
            # a model with window layers keeps its theta: its rotated
            # layers never see past the window, whatever the context
            rope_base=(cfg.rope_base if cfg.has_window_layers
                       else rescale_theta(cfg.rope_base, cfg.context_length,
                                          target_context_length)),
            context_length=target_context_length,
        )
    return cfg


def get_config(model: str, num_params: str, *,
               dtype: Optional[str] = None,
               qkv_bias: Optional[bool] = None,
               use_actv_ckpt: bool = False,
               debug: bool = False,
               target_context_length: Optional[int] = 1024) -> ModelConfig:
    """Unified config builder (reference: build_components.py:50-82).

    Applies dtype injection (build_components.py:67), qkv_bias override used
    when loading GPT-2 HF weights (build_components.py:69-70), and the
    ``--debug`` tiny-model shrink (build_components.py:72-80).
    """
    if model == "GPT2":
        cfg = get_config_gpt2(num_params)
    else:
        cfg = get_config_llama(num_params, model,
                               target_context_length=target_context_length)
    if dtype is not None:
        cfg = cfg.replace(dtype=dtype)
    if qkv_bias is not None:
        cfg = cfg.replace(qkv_bias=qkv_bias)
    if use_actv_ckpt:
        cfg = cfg.replace(use_actv_ckpt=True)
    if debug:
        # Tiny-model override (reference build_components.py:72-80: ctx 10,
        # emb 32, 2 layers, 2 heads). We keep head_dim even for RoPE.
        tiny = dict(context_length=16, emb_dim=32, n_layers=2, n_heads=2,
                    n_kv_groups=min(cfg.n_kv_groups, 2), hidden_dim=64)
        if cfg.layer_kinds or cfg.is_moe:
            # the same block at a size the CPU holds: two whole periods (one
            # where a period is longer than eight layers), rings that wrap
            # inside the 64 positions, 8 experts top-2
            P = max(1, len(cfg.layer_kinds))
            tiny.update(
                context_length=64, n_heads=4, attn_head_dim=16,
                n_layers=P if P > 8 else 2 * P, sliding_window=8,
                vocab_size=512, eos_id=511)
            if cfg.is_moe:
                tiny.update(
                    n_routed_experts=8, n_experts_per_tok=2,
                    n_shared_experts=min(cfg.n_shared_experts, 2),
                    experts_held=())
            if cfg.has_linear_layers:
                tiny.update(linear_heads=4, linear_head_dim=16,
                            linear_gate_rank=8)
            if "ssm" in cfg.layer_kinds:
                tiny.update(ssm_inner=64, ssm_state=8, ssm_dt_rank=4)
        cfg = cfg.replace(**tiny)
    return cfg


def get_model_config(model: str, num_params: str, **kw) -> ModelConfig:
    """Alias kept for API discoverability."""
    return get_config(model, num_params, **kw)
