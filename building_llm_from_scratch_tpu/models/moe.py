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

Dispatch: no capacity factor and no dropped row, in one of two forms that
``expert_dispatch_path`` chooses at trace time by what the call is. A call of
more than ``ROW_BLOCK`` rows on a TPU (a prefill chunk, a prompt) is
``grouped``: ONE ordering of its (row, held expert) assignments by expert,
one gather of the rows into that order, one kernel over the groups
(ops/grouped_experts.py: an expert's weights read once for each row tile its
rows reach, an expert no row chose not at all) and one weighted sum back.
Everything else is ``per_expert``: a Python loop over the held experts, each
behind a ``lax.cond``, so an expert no row chose costs no weight read and no
product; a batch of at most ``ROW_BLOCK`` rows (a decode tick) is its own one
block, with nothing to sort, and a larger one sorts the rows that chose the
expert to the front and runs them in blocks of ``ROW_BLOCK``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

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


def expert_dispatch_path(cfg: ModelConfig, n_rows: int, dtype,
                         platform: Optional[str] = None) -> str:
    """THE rule for how a call of ``n_rows`` rows reaches the held experts,
    made once, at trace time, on what the code can observe (the sibling of
    ``kv_append_path`` and made the same way): ``"grouped"`` (one ordering,
    one gather, ops/grouped_experts.grouped_gated_product, one weighted sum)
    on a TPU for more rows than one ``ROW_BLOCK`` and the shapes
    ``supports_grouped_experts`` admits, ``"per_expert"`` (a conditional a
    held expert and row block) for everything else: a decode tick, any other
    backend, integer weights, widths that are not whole lane tiles. The
    engine reports the names (``stats()["expert_dispatch"]``). ``platform``
    is for tests, which have no TPU to ask about."""
    from building_llm_from_scratch_tpu.ops.grouped_experts import (
        supports_grouped_experts,
    )

    pairs = n_rows * min(cfg.n_experts_per_tok, len(cfg.held_experts))
    if ((platform or jax.default_backend()) == "tpu" and n_rows > ROW_BLOCK
            and supports_grouped_experts(pairs, cfg.emb_dim, cfg.hidden_dim,
                                         dtype)):
        return "grouped"
    return "per_expert"


def _per_expert(cfg: ModelConfig, p: Params, x: jnp.ndarray, ids: jnp.ndarray,
                weights: jnp.ndarray, live: Optional[jnp.ndarray]
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``_routed`` as a loop over the held experts."""
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


def _assignments(cfg: ModelConfig, ids: jnp.ndarray,
                 live: Optional[jnp.ndarray]
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """-> (for each of the N x k assignments, row-major, the index among the
    held experts of the expert it names, H where that expert is not held or
    the row is not live; rows each held expert got (H,) int32)."""
    held = cfg.held_experts
    index = np.full((cfg.n_routed_experts,), len(held), np.int32)
    index[list(held)] = np.arange(len(held))
    group = jnp.asarray(index)[ids]
    if live is not None:
        group = jnp.where(live[:, None], group, len(held))
    group = group.reshape(-1)
    counts = jnp.sum(group[:, None] == jnp.arange(len(held)), axis=0,
                     dtype=jnp.int32)
    return group, counts


def _grouped_sum(cfg: ModelConfig, experts: Params, layer: jnp.ndarray,
                 x: jnp.ndarray, ids: jnp.ndarray, weights: jnp.ndarray,
                 live: Optional[jnp.ndarray]
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    from building_llm_from_scratch_tpu.ops.grouped_experts import (
        buffer_rows,
        grouped_gated_product,
    )

    N, k = ids.shape
    H = len(cfg.held_experts)
    group, counts = _assignments(cfg, ids, live)
    # ONE ordering: the live, held assignments first, by expert, a group's
    # in the rows' order; at most N x min(k, H) of them, the buffer's length
    order = jnp.argsort(group, stable=True)
    M = buffer_rows(N * min(k, H))
    source = jnp.pad(order, (0, max(0, M - N * k)))[:M] // k
    ys = grouped_gated_product(
        x[source], experts["gate"], experts["up"], experts["down"], layer,
        counts, interpret=jax.default_backend() != "tpu")
    # back: an assignment's place in the buffer is its place in the order;
    # a row's total is its own assignments' outputs, each times its weight
    held = (group < H).reshape(N, k)
    place = jnp.where(held, jnp.argsort(order).reshape(N, k), 0)
    total = jnp.sum(jnp.where(
        held[..., None],
        ys[place].astype(jnp.float32) * weights[..., None], 0.0), axis=1)
    return total, counts


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _grouped(cfg, experts, layer, x, ids, weights, live):
    """``_routed`` in the grouped form. The kernel has no backward pass of
    its own: a gradient recomputes the per-expert form, which gives the same
    sum, and differentiates that."""
    return _grouped_sum(cfg, experts, layer, x, ids, weights, live)


def _grouped_fwd(cfg, experts, layer, x, ids, weights, live):
    return (_grouped_sum(cfg, experts, layer, x, ids, weights, live),
            (experts, layer, x, ids, weights, live))


def _grouped_bwd(cfg, saved, cts):
    experts, layer, x, ids, weights, live = saved
    _, vjp = jax.vjp(
        lambda experts, x, weights: _per_expert(
            cfg, dict(experts, layer=layer), x, ids, weights, live)[0],
        experts, x, weights)
    d_experts, d_x, d_weights = vjp(cts[0])
    return d_experts, None, d_x, None, d_weights, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


@jax.named_scope("moe_experts")
def _routed(cfg: ModelConfig, p: Params, x: jnp.ndarray, ids: jnp.ndarray,
            weights: jnp.ndarray, live: Optional[jnp.ndarray]
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """-> (the held experts' part of the routed sum (N, D) float32, rows
    each held expert computed (H,) int32)."""
    if expert_dispatch_path(cfg, x.shape[0], p["gate"].dtype) != "grouped":
        return _per_expert(cfg, p, x, ids, weights, live)
    # the stacked leaves and the layer's index, or one layer's leaves as a
    # stack of one (a reshape, not a copy)
    experts = {name: p[name] if "layer" in p else p[name][None]
               for name in ("gate", "up", "down")}
    layer = jnp.asarray(p.get("layer", 0), jnp.int32)
    return _grouped(cfg, experts, layer, x, ids, weights, live)


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
