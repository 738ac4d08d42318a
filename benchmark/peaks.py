"""The peak table and the arithmetic of least work, kept with the yardstick.

Copied from ``obs/mfu.py`` (``DEVICE_SPECS``, ``flops_per_token``), which a
later PR may delete. A ``device_kind`` that is not in the table is an error,
never a default.
"""

from __future__ import annotations

from typing import Any, Dict

#: published peaks of one chip. Source: Google Cloud documentation,
#: "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s).
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise SystemExit(f"device kind '{device_kind}' is not in the "
                         f"benchmark's peak table {sorted(PEAKS)}")
    return PEAKS[device_kind]


def n_matmul_params(model: Dict[str, Any]) -> int:
    """Parameters that take part in a matrix product per token: the blocks'
    projections and the output head (embedding rows are gathered, not
    multiplied)."""
    d, f, v = model["emb_dim"], model["hidden_dim"], model["vocab_size"]
    hd = d // model["n_heads"]
    qkv = d * model["n_heads"] * hd + 2 * d * model["n_kv_groups"] * hd
    mlp = (3 if model["activation"] == "swiglu" else 2) * d * f
    return model["n_layers"] * (qkv + model["n_heads"] * hd * d + mlp) + d * v


def train_flops_per_token(model: Dict[str, Any], seq_len: int) -> float:
    """Forward + backward operations one trained token requires: 6 per
    matmul parameter, plus causal attention's QK^T and PV (2 products of
    2*T*d each forward, half of it masked away, times 3 for the backward).
    Recomputation is not counted."""
    attn = 6 * model["n_layers"] * seq_len * model["emb_dim"]
    return 6.0 * n_matmul_params(model) + attn


def n_params(model: Dict[str, Any]) -> int:
    d, v, t = model["emb_dim"], model["vocab_size"], model["context_length"]
    bias = 0
    if model["attn_out_bias"]:
        bias += d
    if model["mlp_bias"]:
        bias += model["hidden_dim"] + d
    if model["qkv_bias"]:
        hd = d // model["n_heads"]
        bias += (model["n_heads"] + 2 * model["n_kv_groups"]) * hd
    norms = (2 * model["n_layers"] + 1) * d * (2 if model["norm_bias"] else 1)
    pos = t * d if model["positional"] == "learned" else 0
    return (n_matmul_params(model) + model["n_layers"] * bias + norms
            + v * d + pos)


def decode_weight_bytes(model: Dict[str, Any], bytes_per_param: int) -> int:
    """Least weight bytes one decode tick reads: every block matrix, bias and
    norm and the head once; of the embedding tables only the gathered rows,
    which are left out (under 0.01%)."""
    emb = model["vocab_size"] * model["emb_dim"]
    pos = (model["context_length"] * model["emb_dim"]
           if model["positional"] == "learned" else 0)
    return (n_params(model) - emb - pos) * bytes_per_param


def kv_bytes_per_position(model: Dict[str, Any], bytes_per_el: int) -> int:
    hd = model["emb_dim"] // model["n_heads"]
    return 2 * model["n_layers"] * model["n_kv_groups"] * hd * bytes_per_el
