"""The least bytes a decode tick has to move in a sparse model whose layers
are of two kinds, softmax attention over keys and values and linear attention
over a recurrent state, with an untied head; kept with the yardstick
(``moe_decode_bytes.py`` counts one mixer shape and a tied head). Decode is
bound by bytes: every weight a live row multiplies against is read once a
tick whatever the rows, each decoding row reads the keys and values its
attention layers attend to, and reads AND writes the state and the
convolution's tail of each of its linear layers."""

from __future__ import annotations

from typing import Any, Dict


def _el(model: Dict[str, Any]) -> int:
    return 2 if model["dtype"] in ("bf16", "fp16") else 4


def expert_bytes(model: Dict[str, Any]) -> int:
    """One expert: gate, up and down."""
    return 3 * model["emb_dim"] * model["hidden_dim"] * _el(model)


def _layers(model: Dict[str, Any], linear: bool) -> int:
    kinds = model["layer_kinds"]
    return sum((kinds[l % len(kinds)] == "linear") == linear
               for l in range(model["n_layers"]))


def attention_mixer_params(model: Dict[str, Any]) -> int:
    """q, out and (``attn_out_gate``) the gate, as wide as the queries;
    k and v as wide as the key-value heads."""
    d, hd = model["emb_dim"], model["attn_head_dim"]
    wide = 3 if model.get("attn_out_gate") else 2
    return d * hd * (wide * model["n_heads"] + 2 * model["n_kv_groups"])


def linear_mixer_params(model: Dict[str, Any]) -> int:
    """q, k, v, out; the convolution's taps; A_log a head and dt_bias a
    channel; the step size; decay and output gate through their rank; the
    scale of the norm on a head's output."""
    d, H, r = model["emb_dim"], model["linear_heads"], \
        model["linear_gate_rank"]
    W = H * model["linear_head_dim"]
    return (4 * d * W + model["linear_conv"] * 3 * W + H + W + d * H
            + 2 * (d * r + r * W) + model["linear_head_dim"])


def dense_bytes_per_tick(model: Dict[str, Any]) -> int:
    """Everything outside the routed experts, once a tick: each layer's two
    norms, its mixer by kind, its router and shared experts; the final norm
    and the head (the embedding rows a tick gathers are counted a row:
    ``tick_bytes``)."""
    d = model["emb_dim"]
    every = ((2 * d + d * model["n_routed_experts"]) * _el(model)
             + model["n_shared_experts"] * expert_bytes(model))
    return (model["n_layers"] * every
            + _layers(model, False) * attention_mixer_params(model)
            * _el(model)
            + _layers(model, True) * linear_mixer_params(model) * _el(model)
            + (d + d * model["vocab_size"]) * _el(model))


def kv_bytes_per_position(model: Dict[str, Any]) -> int:
    """Keys and values of one position of ONE attention layer."""
    return 2 * model["n_kv_groups"] * model["attn_head_dim"] * _el(model)


def state_bytes_per_row(model: Dict[str, Any]) -> int:
    """ONE linear layer's memory of one row, read and written: the float32
    state a head and the convolution's tail in the activation type."""
    H, hd = model["linear_heads"], model["linear_head_dim"]
    return 2 * (H * hd * hd * 4
                + (model["linear_conv"] - 1) * 3 * H * hd * _el(model))


def tick_bytes(model: Dict[str, Any], experts_touched: float,
               kv_positions: float, state_rows: float,
               rows: float) -> float:
    """The tick record's fields: ``experts_touched`` (held experts, counted
    a layer, that got a row), ``kv_positions`` (live positions summed over
    the decoding rows and the attention layers), ``state_rows`` (decoding
    rows x linear layers), ``rows`` (decoding rows: an embedding row each)."""
    return (dense_bytes_per_tick(model)
            + experts_touched * expert_bytes(model)
            + kv_positions * kv_bytes_per_position(model)
            + state_rows * state_bytes_per_row(model)
            + rows * model["emb_dim"] * _el(model))
