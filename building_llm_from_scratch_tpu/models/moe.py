"""Sparse experts: a float32 sigmoid router over ALL the model's routed
experts, the part of the routed sum that the experts HELD here give, and the
shared experts whose outputs are averaged.

    s = sigmoid(n @ Wr)                      over n_routed_experts
    T = the n_experts_per_tok largest;  w_e = s_e / sum_{e' in T} s_e'
    E(n) = (silu(n @ Wg) * (n @ Wu)) @ Wd    routed and shared alike
    F = sum_{e in T, e held} w_e E_e(n) + mean_s S_s(n)

This is what expert parallelism asks of one chip: ``cfg.experts_held`` names
the experts whose weights are here (default: all), routing and the weights
``w`` are over all, and what the absent experts would have added is left out
(their chips add it, after an exchange this file does not have).

Parameter tree of one layer (``params["blocks"]["moe"]``, each leaf stacked
on the leading layer axis like every block leaf):

    router  (D, n_routed_experts)
    experts {"gate": (H, D, F), "up": (H, D, F), "down": (H, F, D)}  H held
    shared  {"gate": (S, D, F), "up": (S, D, F), "down": (S, F, D)}

The slot loops hand ``experts`` over still stacked, with the layer's index
beside it (``{"gate": (L, H, D, F), ..., "layer": l}``,
``transformer.unstack_blocks``), so that the one slice an expert's product
reads is taken where it is used.

Dispatch: no capacity factor and no dropped row. For each held expert the
rows that chose it are sorted to the front and run in blocks of
``ROW_BLOCK`` rows; a block none of them reaches is skipped by a
``lax.cond``, so an expert no row chose costs no weight read and no product.
A batch of at most ``ROW_BLOCK`` rows (a decode tick) is its own one block,
with nothing to sort.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from building_llm_from_scratch_tpu.configs import ModelConfig
from building_llm_from_scratch_tpu.ops.activations import silu

Params = Dict[str, Any]

#: rows of one expert product. 128 rows of 4096 against one expert of
#: width 4096 take about as long on a v5e as reading that expert's 100 MB
ROW_BLOCK = 128


def init_moe_params(cfg: ModelConfig, key: jax.Array, linear_init) -> Params:
    """``linear_init(key, in_dim, out_dim, dtype, lead)`` draws one
    ``lead + (in_dim, out_dim)`` leaf."""
    L, D, F, dt = cfg.n_layers, cfg.emb_dim, cfg.hidden_dim, cfg.jax_dtype
    keys = jax.random.split(key, 7)
    H, S = len(cfg.held_experts), cfg.n_shared_experts

    def ffn(ks, n):
        return {"gate": linear_init(ks[0], D, F, dt, (L, n)),
                "up": linear_init(ks[1], D, F, dt, (L, n)),
                "down": linear_init(ks[2], F, D, dt, (L, n))}

    out = {"router": linear_init(keys[0], D, cfg.n_routed_experts, dt, (L,)),
           "experts": ffn(keys[1:4], H)}
    if S:
        out["shared"] = ffn(keys[4:7], S)
    return out


@jax.named_scope("moe_router")
def route(cfg: ModelConfig, router: jnp.ndarray, x: jnp.ndarray
          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (N, D) -> (ids (N, k) of the chosen experts, weights (N, k)
    float32, renormalised over the chosen). Product, sigmoid and top-k in
    float32: a bfloat16 score ties where a float32 one does not."""
    logits = jnp.einsum("nd,de->ne", x.astype(jnp.float32),
                        router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    top, ids = jax.lax.top_k(jax.nn.sigmoid(logits), cfg.n_experts_per_tok)
    return ids, top / jnp.sum(top, axis=-1, keepdims=True)


def _expert(x: jnp.ndarray, gate, up, down) -> jnp.ndarray:
    return (silu(x @ gate) * (x @ up)) @ down


@jax.named_scope("moe_shared")
def _shared(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    """The mean of the shared experts' outputs: one gated product as wide
    as all of them, its output divided by their number."""
    h = silu(jnp.einsum("nd,sdf->nsf", x, p["gate"])) \
        * jnp.einsum("nd,sdf->nsf", x, p["up"])
    out = jnp.einsum("nsf,sfd->nd", h, p["down"],
                     preferred_element_type=jnp.float32)
    return out / p["gate"].shape[0]


@jax.named_scope("moe_experts")
def _routed(cfg: ModelConfig, p: Params, x: jnp.ndarray, ids: jnp.ndarray,
            weights: jnp.ndarray, live: Optional[jnp.ndarray]
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """-> (the held experts' part of the routed sum (N, D) float32, rows
    each held expert computed (H,) int32)."""
    N, D = x.shape
    at = () if "layer" not in p else (p["layer"],)
    n_blocks = -(-N // ROW_BLOCK)
    total = jnp.zeros((N, D), jnp.float32)
    counts = []
    for i, e in enumerate(cfg.held_experts):
        chose = ids == e                                        # (N, k)
        w = jnp.sum(jnp.where(chose, weights, 0.0), axis=-1)    # (N,)
        picked = jnp.any(chose, axis=-1)
        if live is not None:
            picked = picked & live
            w = jnp.where(live, w, 0.0)
        n = jnp.sum(picked, dtype=jnp.int32)
        counts.append(n)
        # the expert's three matrices are sliced out INSIDE the branch: a
        # slice made outside is an operand of the conditional, which the
        # compiler then writes out in full every call, chosen or not
        run = lambda rows, at=at + (i,): _expert(
            rows, *(p[name][at] for name in ("gate", "up", "down")))
        skip = lambda rows: jnp.zeros(rows.shape, x.dtype)
        if n_blocks == 1:
            out = jax.lax.cond(n > 0, run, skip, x)
        else:
            # the rows that chose this expert first, in their own order
            order = jnp.argsort(~picked, stable=True)
            pad = n_blocks * ROW_BLOCK - N
            rows = x[jnp.pad(order, (0, pad), mode="edge")]
            out = jnp.concatenate([
                jax.lax.cond(n > b * ROW_BLOCK, run, skip,
                             rows[b * ROW_BLOCK:(b + 1) * ROW_BLOCK])
                for b in range(n_blocks)])
            out = out[jnp.argsort(order)]           # back to the rows' order
        # a row that did not choose this expert has weight 0: whatever a
        # part-filled block computed for it is dropped here
        total = total + jnp.where(picked[:, None],
                                  out.astype(jnp.float32) * w[:, None], 0.0)
    return total, jnp.stack(counts)


def moe_ffn(cfg: ModelConfig, p: Params, x: jnp.ndarray,
            live: Optional[jnp.ndarray] = None
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (B, T, D) -> (F (B, T, D) in x's type, rows each held expert
    computed (H,) int32). ``live`` (B, T) bool: rows that are real (a free
    slot's row, a chunk's padding are not): the others are routed nowhere,
    so they read no expert and count for none; their output is the shared
    experts' alone, which nothing reads."""
    B, T, D = x.shape
    rows = x.reshape(B * T, D)
    ids, weights = route(cfg, p["router"], rows)
    out, counts = _routed(cfg, p["experts"], rows, ids, weights,
                          None if live is None else live.reshape(B * T))
    if "shared" in p:
        out = out + _shared(p["shared"], rows)
    return out.astype(x.dtype).reshape(B, T, D), counts
