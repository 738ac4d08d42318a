"""Analytic model FLOPs + MFU against TPU-generation peak compute.

MFU (model FLOPs utilization) is the throughput number the TPU systems
literature reports (PaLM App. B; the Gemma-on-TPU and LoRAFusion comparison
studies in PAPERS.md attribute wins the same way): achieved model FLOPs/s
over the chip's peak, counting only the FLOPs the MODEL requires — remat
recompute does not inflate it.

FLOPs/token uses the standard decomposition:

    6 * N_matmul  +  12 * n_layers * emb_dim * seq_len

where ``N_matmul`` is the parameter count EXCLUDING embedding lookups
(gathers, no FLOPs) but INCLUDING the output head, 6 = fwd(2) + bwd(4)
multiply-accumulates per parameter per token, and the second term is the
attention score/value matmuls (QK^T and AV, fwd+bwd, PaLM's ``12 L H Q T``
convention — no causal discount).

Peak FLOPs come from a small per-generation table keyed on
``device.device_kind`` (bf16 dense peak per chip). Unknown kinds — CPU test
meshes in particular — report ``None`` and the callers print "n/a" rather
than a made-up number.
"""

from __future__ import annotations

from typing import Optional

from building_llm_from_scratch_tpu.configs import ModelConfig

#: Per-chip public specs by device_kind substring (lowercased):
#: (peak bf16 dense FLOPs/s, HBM bytes/s). The ONE table — bench.py's
#: roofline math and the trainer's MFU both read it, so a new TPU
#: generation is one line here, not a hunt for private copies.
#: Order matters: first match wins, so longer/more specific keys go first
#: (jax reports v5e as "TPU v5 lite" and v5p as plain "TPU v5").
DEVICE_SPECS = (
    ("v6e", (918e12, 1640e9)),        # Trillium
    ("v6 lite", (918e12, 1640e9)),
    ("v6", (918e12, 1640e9)),
    ("v5p", (459e12, 2765e9)),
    ("v5e", (197e12, 819e9)),
    ("v5 lite", (197e12, 819e9)),
    ("v5litepod", (197e12, 819e9)),
    ("v5", (459e12, 2765e9)),
    ("v4", (275e12, 1228e9)),
    ("v3", (123e12, 900e9)),
    ("v2", (45e12, 700e9)),
)

#: Back-compat view: (key, peak FLOPs) pairs.
TPU_PEAK_FLOPS = tuple((k, spec[0]) for k, spec in DEVICE_SPECS)


def flops_per_token(cfg: ModelConfig, seq_len: Optional[int] = None) -> int:
    """Analytic train-step FLOPs per token (fwd+bwd) for this config."""
    t = cfg.context_length if seq_len is None else seq_len
    # a sparse model multiplies a token against its active parameters only
    n_matmul = cfg.num_params(exclude_embeddings=True, active=cfg.is_moe)
    attention = 12 * cfg.n_layers * cfg.n_heads * cfg.head_dim * t
    return 6 * n_matmul + attention


def device_specs(device=None) -> Optional[tuple]:
    """(peak bf16 FLOPs, HBM bytes/s) for one chip, or None when unknown
    (CPU/GPU test backends). Never initializes a backend the caller
    hasn't."""
    if device is None:
        try:
            import jax

            device = jax.local_devices()[0]
        except Exception:
            return None
    kind = str(getattr(device, "device_kind", "")).lower()
    if "tpu" not in kind and not kind.startswith("v"):
        return None
    for key, spec in DEVICE_SPECS:
        if key in kind:
            return spec
    return None


def device_peak_flops(device=None) -> Optional[float]:
    """Peak bf16 FLOPs for one chip, or None when unknown."""
    spec = device_specs(device)
    return spec[0] if spec is not None else None


def mfu_from_flops(tokens_per_s: float, flops_per_token: float,
                   n_devices: Optional[int] = None,
                   peak: Optional[float] = None) -> Optional[float]:
    """MFU for an arbitrary FLOPs/token figure — the shared denominator
    math for the analytic estimate AND the HLO-measured cross-check
    (obs/compile.py's ``cost_analysis`` FLOPs). None when the chip peak
    is unknown or inputs are degenerate."""
    if peak is None:
        peak = device_peak_flops()
    if peak is None or tokens_per_s <= 0 or not flops_per_token:
        return None
    if n_devices is None:
        import jax

        n_devices = jax.local_device_count()
    return tokens_per_s * flops_per_token / (peak * max(1, n_devices))


def compute_mfu(tokens_per_s: float, cfg: ModelConfig,
                n_devices: Optional[int] = None,
                peak: Optional[float] = None,
                seq_len: Optional[int] = None) -> Optional[float]:
    """MFU in [0, 1] for a measured throughput, or None when the peak is
    unknown.

    ``tokens_per_s`` and ``n_devices`` must describe the same scope: the
    trainer passes its PER-PROCESS throughput with
    ``jax.local_device_count()``, which equals the global ratio on
    symmetric pods.
    """
    return mfu_from_flops(tokens_per_s, flops_per_token(cfg, seq_len),
                          n_devices=n_devices, peak=peak)


def format_mfu(mfu: Optional[float]) -> str:
    """Log-line rendering: '41.4% MFU' or 'MFU n/a' off-TPU."""
    return "MFU n/a" if mfu is None else f"{100.0 * mfu:.1f}% MFU"
