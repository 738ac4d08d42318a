"""Plain GPT-2 in float32: forward, loss, gradients and AdamW steps.

Follows the published description (Radford et al. 2019; the reference repo's
``Models/GPT2/GPT2.py``): learned positions, pre-norm blocks of causal
multi-head attention and a GELU (erf) MLP, LayerNorm eps 1e-5, an output head.
No kernels, no cache, no batching tricks; it imports nothing of the program
and is handed the weights the benchmark drew from the seed, in float32.
Departures, each as the configuration file states it: no q/k/v bias, untied
head. Dropout, where the configuration has it, uses this file's own masks at
the published sites (embedding, attention weights, both residual branches):
the program's masks come from the chip's generator inside its kernels and
cannot be redrawn here, so a run with dropout agrees only in distribution.

On a TPU a float32 product runs in bfloat16 passes unless told otherwise, so
every entry point sets ``jax.default_matmul_precision("highest")``.

``precision`` chooses the arithmetic of the linear layers and the head:
``"float32"`` is the reference; ``"fp8_e4m3"`` is the control, the nearest
precision below bfloat16, as fp8 training and serving recipes have it: both
operands of every such product are rounded to float8 e4m3 (one scale per
tensor), and in the backward pass the incoming gradient is rounded to float8
e5m2 before its two products with those rounded operands.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0          # largest finite float8_e4m3fn


F8_GRAD_MAX = 57344.0   # largest finite float8_e5m2


def _fp8_round(x, dtype=jnp.float8_e4m3fn, top=F8_MAX):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8_linear(x, w):
    return _fp8_round(x) @ _fp8_round(w)


def _fp8_linear_fwd(x, w):
    xq, wq = _fp8_round(x), _fp8_round(w)
    return xq @ wq, (xq, wq)


def _fp8_linear_bwd(saved, dy):
    xq, wq = saved
    dyq = _fp8_round(dy, jnp.float8_e5m2, F8_GRAD_MAX)
    dw = jnp.einsum("...i,...o->io", xq, dyq)
    return dyq @ wq.T, dw


_fp8_linear.defvjp(_fp8_linear_fwd, _fp8_linear_bwd)


def _linear(x, w, precision: str):
    if precision == "fp8_e4m3":
        return _fp8_linear(x, w)
    if precision != "float32":
        raise ValueError(f"unknown reference precision {precision}")
    return x @ w


def param_shapes(m: Dict[str, Any]) -> Dict[str, Any]:
    """The parameter tree this reference reads (and the program holds), as
    shapes: per-layer leaves stacked on a leading axis."""
    L, D, V, T = m["n_layers"], m["emb_dim"], m["vocab_size"], m["context_length"]
    hd, F = D // m["n_heads"], m["hidden_dim"]
    Hq, Hkv = m["n_heads"], m["n_kv_groups"]
    if (m["norm"], m["positional"], m["activation"]) != (
            "layernorm", "learned", "gelu"):
        raise SystemExit("benchmark/reference/gpt2.py is the GPT-2 family's "
                         "reference; another family brings its own file")
    attn = {"wq": (L, D, Hq * hd), "wk": (L, D, Hkv * hd),
            "wv": (L, D, Hkv * hd), "wo": (L, Hq * hd, D)}
    if m["qkv_bias"]:
        attn.update(bq=(L, Hq * hd), bk=(L, Hkv * hd), bv=(L, Hkv * hd))
    if m["attn_out_bias"]:
        attn["bo"] = (L, D)
    mlp = {"up": (L, D, F), "down": (L, F, D)}
    if m["mlp_bias"]:
        mlp.update(b_up=(L, F), b_down=(L, D))
    norm = lambda *lead: ({"scale": lead + (D,), "bias": lead + (D,)}
                          if m["norm_bias"] else {"scale": lead + (D,)})
    return {"tok_emb": {"weight": (V, D)}, "pos_emb": {"weight": (T, D)},
            "blocks": {"norm1": norm(L), "attn": attn, "norm2": norm(L),
                       "mlp": mlp},
            "final_norm": norm(), "head": {"weight": (D, V)}}


def _layernorm(x, p, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    y = (x - mu) / jnp.sqrt(var + eps) * p["scale"]
    return y + p["bias"] if "bias" in p else y


def _dropout(x, rate: float, key):
    if rate <= 0.0 or key is None:
        return x
    keep = jax.random.bernoulli(key, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0)


def _block(model, precision, x, layer, key):
    B, T, D = x.shape
    H = model["n_heads"]
    hd = D // H
    rate = model["drop_rate"]
    k_att, k_r1, k_r2 = (jax.random.split(key, 3) if key is not None
                         else (None, None, None))
    a, m = layer["attn"], layer["mlp"]
    h = _layernorm(x, layer["norm1"], model["layernorm_eps"])
    q, k, v = (_linear(h, a[n], precision) + (a[b] if b in a else 0.0)
               for n, b in (("wq", "bq"), ("wk", "bk"), ("wv", "bv")))
    split = lambda t: t.reshape(B, T, H, hd).transpose(0, 2, 1, 3)
    scores = split(q) @ split(k).transpose(0, 1, 3, 2) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    probs = _dropout(probs, rate, k_att)
    ctx = (probs @ split(v)).transpose(0, 2, 1, 3).reshape(B, T, D)
    h = _linear(ctx, a["wo"], precision) + (a["bo"] if "bo" in a else 0.0)
    x = x + _dropout(h, rate, k_r1)
    h = _layernorm(x, layer["norm2"], model["layernorm_eps"])
    h = _linear(h, m["up"], precision) + (m["b_up"] if "b_up" in m else 0.0)
    h = jax.nn.gelu(h, approximate=False)
    h = _linear(h, m["down"], precision) + (
        m["b_down"] if "b_down" in m else 0.0)
    return x + _dropout(h, rate, k_r2)


def logits_fn(params, model: Dict[str, Any], tokens, *,
              precision: str = "float32", dropout_key=None):
    """(B, T) int tokens -> (B, T, V) float32 logits."""
    T = tokens.shape[1]
    x = params["tok_emb"]["weight"][tokens] + params["pos_emb"]["weight"][:T]
    keys = None
    if dropout_key is not None and model["drop_rate"] > 0.0:
        k_emb, k_layers = jax.random.split(dropout_key)
        x = _dropout(x, model["drop_rate"], k_emb)
        keys = jax.random.split(k_layers, model["n_layers"])

    # one layer at a time, recomputed in the backward pass, so that float32
    # activations of a whole model never sit in memory at once
    @jax.checkpoint
    def body(x, xs):
        layer, key = xs
        return _block(model, precision, x, layer, key), None

    if keys is None:
        x, _ = jax.lax.scan(lambda c, l: body(c, (l, None)), x,
                            params["blocks"])
    else:
        x, _ = jax.lax.scan(body, x, (params["blocks"], keys))
    x = _layernorm(x, params["final_norm"], model["layernorm_eps"])
    return _linear(x, params["head"]["weight"], precision)


def _mean_nll(params, model, inputs, targets, precision, dropout_key):
    logits = logits_fn(params, model, inputs, precision=precision,
                       dropout_key=dropout_key)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))


@functools.lru_cache(maxsize=None)
def _grad_fn(model_items: Tuple, precision: str):
    model = dict(model_items)
    return jax.jit(jax.value_and_grad(
        lambda p, i, t, k: _mean_nll(p, model, i, t, precision, k)))


def loss_and_grads(params, model, inputs, targets, *, precision="float32",
                   dropout_key=None, rows_per_block: int = 2):
    """Mean next-token loss over the batch and its gradient, taken in
    blocks of rows (equal blocks, so the mean of block means is the mean)."""
    fn = _grad_fn(tuple(sorted(model.items())), precision)
    n = inputs.shape[0]
    if n % rows_per_block:
        rows_per_block = 1
    blocks = range(0, n, rows_per_block)
    loss, grads = 0.0, None
    for j, r in enumerate(blocks):
        key = (jax.random.fold_in(dropout_key, j)
               if dropout_key is not None else None)
        l, g = fn(params, inputs[r:r + rows_per_block],
                  targets[r:r + rows_per_block], key)
        loss = loss + l / len(blocks)
        grads = g if grads is None else jax.tree_util.tree_map(
            jnp.add, grads, g)
    return loss, jax.tree_util.tree_map(lambda g: g / len(blocks), grads)


def lr_at(step: int, hp: Dict[str, float], total_steps: int) -> float:
    """The reference repo's linear warm-up then cosine decay; ``step`` counts
    from 1 at the first update."""
    warm = max(1, int(hp["warmup_steps"]))
    if step < warm:
        return hp["initial_lr"] + step * (hp["peak_lr"] - hp["initial_lr"]) / warm
    progress = (step - warm) / max(1, total_steps - warm)
    return hp["min_lr"] + (hp["peak_lr"] - hp["min_lr"]) * 0.5 * (
        1.0 + np.cos(np.pi * progress))


def _leaf_norms(tree) -> Dict[str, float]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): float(jnp.sqrt(jnp.sum(
        jnp.square(x.astype(jnp.float32))))) for p, x in flat}


def train_steps(params, model, batches: Sequence[Tuple[Any, Any]],
                hp: Dict[str, float], total_steps: int, *,
                precision: str = "float32", dropout_seed: int = 0
                ) -> Dict[str, Any]:
    """AdamW (clip by global norm, Adam b1 0.9 b2 0.999 eps 1e-8, decoupled
    weight decay, scheduled rate) over ``batches``. Returns each step's loss,
    the per-leaf norms of the first clipped gradient, and the per-leaf norms
    of the parameters' change after the last step."""
    with jax.default_matmul_precision("highest"):
        b1, b2, eps = 0.9, 0.999, 1e-8
        start = params
        mu = jax.tree_util.tree_map(jnp.zeros_like, params)
        nu = jax.tree_util.tree_map(jnp.zeros_like, params)
        key = (jax.random.PRNGKey(dropout_seed)
               if model["drop_rate"] > 0.0 else None)
        losses: List[float] = []
        first_grad = None

        @jax.jit
        def update(params, mu, nu, grads, t, lr):
            gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in
                                 jax.tree_util.tree_leaves(grads)))
            clip = jnp.minimum(1.0, hp["grad_clip_norm"] / (gnorm + 1e-30))
            grads = jax.tree_util.tree_map(lambda g: g * clip, grads)
            mu = jax.tree_util.tree_map(
                lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
            nu = jax.tree_util.tree_map(
                lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
            def step(p, m, v):
                mhat, vhat = m / (1 - b1 ** t), v / (1 - b2 ** t)
                return p - lr * (mhat / (jnp.sqrt(vhat) + eps)
                                 + hp["weight_decay"] * p)
            return (jax.tree_util.tree_map(step, params, mu, nu), mu, nu,
                    grads)

        for t, (inputs, targets) in enumerate(batches, start=1):
            k = jax.random.fold_in(key, t) if key is not None else None
            loss, grads = loss_and_grads(params, model, jnp.asarray(inputs),
                                         jnp.asarray(targets),
                                         precision=precision, dropout_key=k)
            params, mu, nu, clipped = update(
                params, mu, nu, grads, jnp.float32(t),
                jnp.float32(lr_at(t, hp, total_steps)))
            losses.append(float(loss))
            if first_grad is None:
                first_grad = _leaf_norms(clipped)
        change = _leaf_norms(jax.tree_util.tree_map(
            lambda a, b: a - b, params, start))
        return {"losses": losses, "first_grad_norms": first_grad,
                "change_norms": change}


def served_token_gaps(params, model, sequences: Sequence[Tuple[Any, Any]],
                      *, pad_to: int, control: str = "") -> Dict[str, Any]:
    """For each (prompt, served tokens): one causal pass over prompt + served
    tokens, and at each served position the gap by which the served token's
    logit lies below the reference's best. With ``control`` set, also the gap
    of the token that this lower precision puts first at those positions."""
    model = dict(model, drop_rate=0.0)

    @functools.partial(jax.jit, static_argnames="low")
    def gaps(params, tokens, low):
        # at every position, fixed shapes: one program whatever the lengths
        rows = logits_fn(params, model, tokens)[0]
        best = jnp.max(rows, axis=-1)
        nxt = jnp.roll(tokens[0], -1)
        served = best - jnp.take_along_axis(rows, nxt[:, None], -1)[:, 0]
        if not low:
            return served, served
        pick = jnp.argmax(logits_fn(params, model, tokens, precision=low)[0],
                          axis=-1)
        return served, best - jnp.take_along_axis(rows, pick[:, None], -1)[:, 0]

    worst, worst_control, n_tokens = 0.0, 0.0, 0
    with jax.default_matmul_precision("highest"):
        for prompt, served in sequences:
            seq = np.zeros((1, pad_to), np.int32)
            n_p, n_s = len(prompt), len(served)
            seq[0, :n_p + n_s] = np.concatenate([prompt, served])
            at = slice(n_p - 1, n_p - 1 + n_s)
            g, g_low = jax.device_get(gaps(params, jnp.asarray(seq), control))
            worst = max(worst, float(g[at].max()))
            worst_control = max(worst_control, float(g_low[at].max()))
            n_tokens += n_s
    return {"widest_gap": worst,
            "control_widest_gap": worst_control if control else None,
            "tokens": n_tokens}
