"""The least bytes a decode tick of a sparse model with window layers has to
move, kept with the yardstick (``benchmark/peaks.py`` counts the dense
families). Decode is bound by bytes: every weight a live row multiplies
against is read once a tick whatever the rows, and each row reads the keys
and values its layers attend to."""

from __future__ import annotations

from typing import Any, Dict


def _el(model: Dict[str, Any]) -> int:
    return 2 if model["dtype"] in ("bf16", "fp16") else 4


def expert_bytes(model: Dict[str, Any]) -> int:
    """One expert: gate, up and down."""
    return 3 * model["emb_dim"] * model["hidden_dim"] * _el(model)


def dense_bytes_per_tick(model: Dict[str, Any]) -> int:
    """Everything outside the routed experts, once a tick: each layer's
    norm, attention, router and shared experts, the final norm, and the
    tied embedding read as the head (the few rows gathered from it as
    embeddings are in it already)."""
    d, hd = model["emb_dim"], model["attn_head_dim"]
    attn = 2 * d * hd * (model["n_heads"] + model["n_kv_groups"])
    layer = (d + attn + d * model["n_routed_experts"]) * _el(model) \
        + model["n_shared_experts"] * expert_bytes(model)
    return (model["n_layers"] * layer
            + (d + model["vocab_size"] * d) * _el(model))


def kv_bytes_per_position(model: Dict[str, Any]) -> int:
    """Keys and values of one position of ONE layer."""
    return 2 * model["n_kv_groups"] * model["attn_head_dim"] * _el(model)


def tick_bytes(model: Dict[str, Any], experts_touched: float,
               kv_positions: float) -> float:
    """``experts_touched``: held experts, counted a layer, that got a row
    (the tick record's field); ``kv_positions``: live positions summed over
    the decoding rows and the layers, a window layer counting no more than
    its window (the tick record's ``kv_positions``)."""
    return (dense_bytes_per_tick(model)
            + experts_touched * expert_bytes(model)
            + kv_positions * kv_bytes_per_position(model))
