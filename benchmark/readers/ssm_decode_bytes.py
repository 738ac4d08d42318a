"""The least bytes a decode tick has to move in a dense model whose layers
are of two kinds, softmax attention over keys and values and a selective
state space over a recurrent state, with the head tied to the embedding;
kept with the yardstick (``hybrid_decode_bytes.py`` counts a gated delta
rule, sparse experts and an untied head). Decode is bound by bytes: every
weight outside the embedding is read once a tick whatever the rows, the
embedding once as the head, each decoding row reads the keys and values its
attention layers attend to, and reads AND writes the state and the
convolution's tail of each of its state-space layers."""

from __future__ import annotations

from typing import Any, Dict


def _el(model: Dict[str, Any]) -> int:
    return 2 if model["dtype"] in ("bf16", "fp16") else 4


def layers(model: Dict[str, Any], kind: str) -> int:
    kinds = model["layer_kinds"]
    return sum(kinds[l % len(kinds)] == kind
               for l in range(model["n_layers"]))


def attention_mixer_params(model: Dict[str, Any]) -> int:
    """q and out as wide as the queries, k and v as the key-value heads; no
    bias, no gate."""
    d, hd = model["emb_dim"], model["attn_head_dim"]
    return 2 * d * hd * (model["n_heads"] + model["n_kv_groups"])


def ssm_mixer_params(model: Dict[str, Any]) -> int:
    """in (u and z) and out; the convolution's taps and bias; dt, B and C
    read from the channels, a norm's scale on each; dt back to the channels
    with its bias; A_log a channel and state; the skip D."""
    d, i, n, r = (model["emb_dim"], model["ssm_inner"], model["ssm_state"],
                  model["ssm_dt_rank"])
    return (3 * d * i + (model["ssm_conv"] + 1) * i + (i + 1) * (r + 2 * n)
            + r * i + i + i * n + i)


def block_params(model: Dict[str, Any]) -> int:
    """Every layer's two norms and dense SwiGLU, its mixer by kind, and the
    final norm: everything outside the embedding."""
    d = model["emb_dim"]
    return (model["n_layers"] * (2 * d + 3 * d * model["hidden_dim"])
            + layers(model, "full") * attention_mixer_params(model)
            + layers(model, "ssm") * ssm_mixer_params(model) + d)


def dense_bytes_per_tick(model: Dict[str, Any]) -> int:
    """Everything outside the embedding once, and the embedding once as the
    tied head (the rows a tick gathers from it are counted a row:
    ``tick_bytes``)."""
    return (block_params(model)
            + model["vocab_size"] * model["emb_dim"]) * _el(model)


def kv_bytes_per_position(model: Dict[str, Any]) -> int:
    """Keys and values of one position of ONE attention layer."""
    return 2 * model["n_kv_groups"] * model["attn_head_dim"] * _el(model)


def state_bytes_per_row(model: Dict[str, Any]) -> int:
    """ONE state-space layer's memory of one row, read and written: the
    float32 state and the convolution's tail in the activation type."""
    i = model["ssm_inner"]
    return 2 * (i * model["ssm_state"] * 4
                + (model["ssm_conv"] - 1) * i * _el(model))


def tick_bytes(model: Dict[str, Any], kv_positions: float,
               state_rows: float, rows: float) -> float:
    """The tick record's fields: ``kv_positions`` (live positions summed
    over the decoding rows and the attention layers), ``state_rows``
    (decoding rows x state-space layers), ``rows`` (decoding rows: an
    embedding row each)."""
    return (dense_bytes_per_tick(model)
            + kv_positions * kv_bytes_per_position(model)
            + state_rows * state_bytes_per_row(model)
            + rows * model["emb_dim"] * _el(model))
