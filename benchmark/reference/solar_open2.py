"""Plain Solar-Open2 (``model_type: solar_open2``) in float32: the forward
pass, as one chip of an expert-parallel deployment holds it.

Written from the published configuration's keys and its description
(``benchmark/configs/solar-open2-250b.json``, PERF.md section 4). Every layer
is serial, ``x += Mix(RMSNorm1(x)); x += MoE(RMSNorm2(x))``, with no positions
anywhere; the mixer is one of two kinds by the period ``layer_kinds``. For
rows ``n = RMSNorm1(x)`` of width D:

``full``: gated NoPE grouped-query attention.

    q, k, v = n Wq, n Wk, n Wv          (Hq heads, Hkv key-value heads, hd)
    a   = causal softmax(q k^T / sqrt(hd)) v          no rotation, no bias
    out = (a * sigmoid(n Wg)) Wo              the gate as wide as the query

``linear``: the gated delta rule with a per-channel decay, token by token.

    q~, k~, v~ = n Wq, n Wk, n Wv                     (H heads of hd, each)
    c_t = silu(sum_{i<K} conv[i] * x_(t-K+1+i))   depthwise, causal, K taps,
                                                  zeros before the sequence
    q = c_q / |c_q|_2 * hd^-1/2,  k = c_k / |c_k|_2,  v = c_v       per head
    g_t = -exp(A_log[h]) * softplus((n W_fa) W_fb + dt_bias)     (H, hd) <= 0
    b_t = 2 sigmoid(n W_b)                                              (H,)
    S~  = Diag(exp(g_t)) S_(t-1);  S_t = S~ + b_t k_t (v_t - S~^T k_t)^T
    o_t = S_t^T q_t                              S (hd, hd) a head, S_0 = 0
    out = concat_h(RMSNorm(o_t; gamma) * sigmoid((n W_ga) W_gb)) Wo

The recurrence is a ``lax.scan`` over positions: the form above, literally.

Expert layer, on ``m = RMSNorm2(x + out)``:

    s = sigmoid(m Wr) over ALL routed experts; T = the k largest;
    w_e = s_e / sum_{e' in T} s_e'           (routed_scaling_factor 1)
    E(m) = (silu(m Wg) * (m Wu)) Wd          routed and shared alike
    F = sum_{e in T, e held here} w_e E_e(m) + mean_s S_s(m)    (one shared)
    logits = RMSNorm(x_L) Head                    embedding and head untied

``experts_held`` names the routed experts whose weights are here; what the
others would have added is left out, and that partial sum goes on to the next
layer, exactly as the program under test does it. No cache, no kernels, no
import of the program; handed the weights the benchmark drew from the seed, in
float32. On a TPU a float32 product runs in bfloat16 passes unless told
otherwise, so every entry point sets
``jax.default_matmul_precision("highest")``.

ASSUMED, where ``config.json`` and the description leave a choice (the same
list is in the configuration file): sigmoid scoring with no selection bias;
the gate of a full layer is elementwise, of the query's width, applied before
Wo; the decay and the output gate of a linear layer are factorised through
rank ``linear_gate_rank`` = the head size (``kda_use_full_proj: false``); no
bias on any projection or on the convolution; q and k L2-normalised (1e-6
under the root) and q scaled by hd^-1/2; an RMSNorm with a scale on each
head's output before its gate; the state float32; the shared expert as wide as
a routed one; text only. DEPARTURES from the published model: the layers, the
experts held and the vocabulary rows of ``reduced``; ``A_log`` and ``dt_bias``
are what ``benchmark/weights.py`` makes of 1-D leaves, zero (every channel
decays by about a half a token).

``precision``: ``"float32"`` is the reference; ``"fp8_e4m3"`` the control, the
nearest precision below bfloat16: both operands of every projection (the
mixers', the gates' factors, every expert's three products, the head) are
rounded to float8 e4m3, one scale a tensor. The router, the convolution and
the recurrence stay float32 there too. ``"bf16"`` (tests) rounds the same
operands to bfloat16.

NEAR-TIES are left out of the comparison by the one rule of
``near_tie.py``, which ``cohere2_moe.py`` imports too: an expert held here
that is chosen and lies within ``NEAR_TIE`` of the best one not chosen, or is
not chosen and lies that near the last one chosen, at any rank. The chip's
runs showed why (PERF.md section 6, PR 35): the served tokens that a rule on
the 8th and 9th logits alone left standing 0.5-0.75 under the reference's
best each had a held expert 7th within 0.02 of the 9th, or 10th that near the
8th, and the program had chosen the other way. ``cohere2_moe.py``'s second
rule, ``COHERENT_REPEATS``, is NOT here: it exists because that model's first
router reads the token's embedding alone, so a token that ties there ties at
every one of its positions. Here every router reads ``RMSNorm2(x + Mix)``,
the mixer's output included, so no tie is a token's own, and no chip run
showed a repeated one. Nor is a tie carried forward: an expert chosen the
other way moves its own position's logits by 0.15-0.25 rms and, through the
linear layers' convolution and state, the next positions' by 0.03.

At the cell's size (33,792 tokens beside 8.2 GB of float32 weights) the rows
go ``piece_rows`` at a time through the projections, the recurrence (the
state and the convolution's tail go from piece to piece) and the experts, one
expert at a time, and ``block_rows`` at a time, one key-value head at a time,
through attention's scores; one piece of one block is the unblocked pass, and
the tests hold the two equal.
"""

from __future__ import annotations

import functools
import json
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import near_tie
from benchmark.reference.near_tie import LEAST_COMPARED, NEAR_TIE, left_out

F8_MAX = 448.0          # largest finite float8_e4m3fn


def _fp8_round(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _linear(x, w, precision: str):
    if precision == "fp8_e4m3":
        return _fp8_round(x) @ _fp8_round(w)
    if precision == "bf16":       # tests: what a bfloat16 program computes
        return (x.astype(jnp.bfloat16) @ w.astype(jnp.bfloat16)
                ).astype(jnp.float32)
    if precision != "float32":
        raise ValueError(f"unknown reference precision {precision}")
    return x @ w


def _held(m: Dict[str, Any]) -> Tuple[int, ...]:
    return tuple(m.get("experts_held") or range(m["n_routed_experts"]))


def _layers_of(m: Dict[str, Any], linear: bool) -> Tuple[int, ...]:
    kinds = m["layer_kinds"]
    return tuple(l for l in range(m["n_layers"])
                 if (kinds[l % len(kinds)] == "linear") == linear)


def param_shapes(m: Dict[str, Any]) -> Dict[str, Any]:
    """The parameter tree this reference reads (and the program holds), as
    shapes: per-layer leaves stacked on a leading axis (the mixers over the
    layers of their own kind), an expert's on a second."""
    if not (m.get("n_routed_experts") and "linear" in m.get("layer_kinds", ())
            and m.get("attn_out_gate") and not m.get("parallel_block")
            and not m.get("tie_embeddings") and m["norm"] == "rmsnorm"
            and m["positional"] == "none"):
        raise SystemExit("benchmark/reference/solar_open2.py is the "
                         "Solar-Open2 family's reference; another family "
                         "brings its own file")
    L, D, V, F = m["n_layers"], m["emb_dim"], m["vocab_size"], m["hidden_dim"]
    hd, Hq, Hkv = m["attn_head_dim"], m["n_heads"], m["n_kv_groups"]
    La, Ll = len(_layers_of(m, False)), len(_layers_of(m, True))
    H, r, K = m["linear_heads"], m["linear_gate_rank"], m["linear_conv"]
    W = H * m["linear_head_dim"]
    ffn = lambda n: {"gate": (L, n, D, F), "up": (L, n, D, F),
                     "down": (L, n, F, D)}
    return {"tok_emb": {"weight": (V, D)},
            "blocks": {
                "norm1": {"scale": (L, D)},
                "norm2": {"scale": (L, D)},
                "attn": {"wq": (La, D, Hq * hd), "wk": (La, D, Hkv * hd),
                         "wv": (La, D, Hkv * hd), "wo": (La, Hq * hd, D),
                         "wg": (La, D, Hq * hd)},
                "linear": {"wq": (Ll, D, W), "wk": (Ll, D, W),
                           "wv": (Ll, D, W), "wo": (Ll, W, D),
                           "conv": (Ll, K, 3 * W),
                           "A_log": (Ll, H), "dt_bias": (Ll, W),
                           "w_fa": (Ll, D, r), "w_fb": (Ll, r, W),
                           "w_ga": (Ll, D, r), "w_gb": (Ll, r, W),
                           "w_b": (Ll, D, H),
                           "o_norm": {"scale": (Ll, m["linear_head_dim"])}},
                "moe": {"router": (L, D, m["n_routed_experts"]),
                        "experts": ffn(len(_held(m))),
                        "shared": ffn(m["n_shared_experts"])}},
            "final_norm": {"scale": (D,)},
            "head": {"weight": (D, V)}}


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _expert(x, gate, up, down, precision):
    return _linear(jax.nn.silu(_linear(x, gate, precision))
                   * _linear(x, up, precision), down, precision)


def _route(m, n, router):
    """-> (weight of each routed expert for each row (N, E), zero outside
    the k chosen; margin (N,): how near the edge of the chosen k the nearest
    expert held here lies, at any rank: ``near_tie.margin``)."""
    logits = n @ router
    k, held = m["n_experts_per_tok"], jnp.asarray(_held(m))
    top, ids = jax.lax.top_k(logits, min(k + 1, logits.shape[-1]))
    scores = jax.nn.sigmoid(top[:, :k])
    w = scores / jnp.sum(scores, -1, keepdims=True)
    dense = jnp.zeros_like(logits).at[
        jnp.arange(logits.shape[0])[:, None], ids[:, :k]].set(w)
    return dense, near_tie.margin(logits, top, k, held)


def _attend(q, q_pos, k, v, k_pos, block_rows):
    """Causal softmax(q k^T / sqrt(hd)) v, no positions. q (R, Hq, hd) at
    ``q_pos`` (R,); k, v (T, Hkv, hd) at ``k_pos`` (T,) -> (R, Hq * hd).
    ``block_rows`` query rows and one key-value head at a time (no weights
    in here: a loop that closes over a slice of the parameters makes the
    compiler keep a copy of it)."""
    R, Hq, hd = q.shape
    Hkv = k.shape[1]
    kT, vT = k.transpose(1, 0, 2), v.transpose(1, 0, 2)

    def block(xs):
        qb, pos = xs                                    # (B, Hq, hd), (B,)
        seen = pos[:, None] >= k_pos[None, :]

        def one_kv_head(ys):
            qg, kg, vg = ys                   # (B, G, hd), (T, hd), (T, hd)
            scores = jnp.einsum("rgd,td->grt", qg, kg) / np.sqrt(hd)
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
            return jnp.einsum("grt,td->rgd", probs, vg)

        ctx = jax.lax.map(one_kv_head, (
            qb.reshape(-1, Hkv, Hq // Hkv, hd).transpose(1, 0, 2, 3), kT, vT))
        return ctx.transpose(1, 0, 2, 3).reshape(-1, Hq * hd)

    split = lambda a: a.reshape((R // block_rows, block_rows) + a.shape[1:])
    return jax.lax.map(block, (split(q), split(q_pos))).reshape(R, Hq * hd)


def _in_pieces(a, piece_rows: int):
    return a.reshape((-1, piece_rows) + a.shape[1:])


def _full_mixer(m, a, n, precision, piece_rows, block_rows):
    """The gated NoPE attention layer: n (T, D) -> (T, D), the queries a
    piece of rows at a time."""
    T, D = n.shape
    hd, Hq, Hkv = m["attn_head_dim"], m["n_heads"], m["n_kv_groups"]
    positions = jnp.arange(T)
    k = _linear(n, a["wk"], precision).reshape(T, Hkv, hd)
    v = _linear(n, a["wv"], precision).reshape(T, Hkv, hd)

    def piece(xs):
        x, pos = xs
        q = _linear(x, a["wq"], precision).reshape(-1, Hq, hd)
        ctx = _attend(q, pos, k, v, positions, block_rows)
        gate = jax.nn.sigmoid(_linear(x, a["wg"], precision))
        return _linear(ctx * gate, a["wo"], precision)

    return jax.lax.map(piece, (_in_pieces(n, piece_rows),
                               _in_pieces(positions, piece_rows))
                       ).reshape(T, D)


def _delta_rule(q, k, v, g, beta, S):
    """The recurrence, token by token. q, k, g (R, H, hd), v (R, H, hd),
    beta (R, H), S (H, hd, hd) -> (o (R, H, hd), S after the last)."""
    def token(S, xs):
        q, k, v, g, b = xs
        S = jnp.exp(g)[:, :, None] * S                       # S~
        u = b[:, None] * (v - jnp.einsum("hkv,hk->hv", S, k))
        S = S + k[:, :, None] * u[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q)

    S, o = jax.lax.scan(token, S, (q, k, v, g, beta))
    return o, S


def _linear_mixer(m, p, n, precision, piece_rows):
    """The gated-delta-rule layer: n (T, D) -> (T, D), a piece of rows at a
    time; the convolution's tail and the state go from piece to piece."""
    T, D = n.shape
    H, hd, K = m["linear_heads"], m["linear_head_dim"], m["linear_conv"]
    W, R = H * hd, piece_rows
    proj = lambda x, *names: functools.reduce(
        lambda y, name: _linear(y, p[name], precision), names, x)
    decay_rate = jnp.exp(p["A_log"])[:, None]

    def piece(carry, x):
        tail, S = carry
        seen = jnp.concatenate([tail, jnp.concatenate(
            [proj(x, "wq"), proj(x, "wk"), proj(x, "wv")], -1)])
        conv = sum(p["conv"][i] * seen[i:i + R] for i in range(K))
        q, k, v = (c.reshape(R, H, hd)
                   for c in jnp.split(jax.nn.silu(conv), 3, axis=-1))
        q, k = _unit(q) * hd ** -0.5, _unit(k)
        g = -decay_rate * jax.nn.softplus(
            proj(x, "w_fa", "w_fb") + p["dt_bias"]).reshape(R, H, hd)
        beta = jax.nn.sigmoid(proj(x, "w_b"))
        if m["linear_neg_eigval"]:
            beta = 2.0 * beta
        o, S = _delta_rule(q, k, v, g, beta, S)
        y = (_rmsnorm(o, p["o_norm"]["scale"], m["rmsnorm_eps"])
             * jax.nn.sigmoid(proj(x, "w_ga", "w_gb")).reshape(R, H, hd))
        return (seen[-(K - 1):], S), proj(y.reshape(R, W), "wo")

    zeros = (jnp.zeros((K - 1, 3 * W), jnp.float32),   # zeros before t = 0
             jnp.zeros((H, hd, hd), jnp.float32))
    _, out = jax.lax.scan(piece, zeros, _in_pieces(n, piece_rows))
    return out.reshape(T, D)


def _layer(m, precision, blocks, l, x, block_rows, piece_rows):
    """Layer ``l`` of the stacked ``blocks``: x (T, D) -> (x' (T, D),
    its router's margin (T,): ``_route``)."""
    kinds = m["layer_kinds"]
    linear = kinds[l % len(kinds)] == "linear"
    at = _layers_of(m, linear).index(l)
    take = lambda tree, i: jax.tree_util.tree_map(lambda a: a[i], tree)
    moe = blocks["moe"]
    T, D = x.shape
    n = _rmsnorm(x, blocks["norm1"]["scale"][l], m["rmsnorm_eps"])
    if linear:
        x = x + _linear_mixer(m, take(blocks["linear"], at), n, precision,
                              piece_rows)
    else:
        x = x + _full_mixer(m, take(blocks["attn"], at), n, precision,
                            piece_rows, block_rows)
    n2 = _rmsnorm(x, blocks["norm2"]["scale"][l], m["rmsnorm_eps"])
    w, margin = _route(m, n2, moe["router"][l])
    rows = _in_pieces(n2, piece_rows)

    def add_expert(group, scale):
        """x += scale (T, 1) * expert ``i`` of ``group``: each matrix one
        slice of the stacked leaf, taken where it is used (inside the loop
        over the experts, at the loop's index: a copy of one expert, not of
        a layer's), the rows a piece at a time."""
        def body(i, x):
            gate, up, down = (jax.lax.dynamic_slice(
                moe[group][name], (l, i, 0, 0),
                (1, 1) + moe[group][name].shape[2:])[0, 0]
                for name in ("gate", "up", "down"))
            part = jax.lax.map(
                lambda xs: _expert(xs[0], gate, up, down, precision) * xs[1],
                (rows, _in_pieces(jax.lax.dynamic_slice_in_dim(
                    scale, i, 1, axis=1), piece_rows)))
            return x + part.reshape(T, D)
        return body

    held = jnp.asarray(_held(m))
    x = jax.lax.fori_loop(0, len(_held(m)), add_expert("experts", w[:, held]),
                          x)
    n_shared = m["n_shared_experts"]
    x = jax.lax.fori_loop(
        0, n_shared, add_expert("shared", jnp.full((T, n_shared),
                                                   1.0 / n_shared)), x)
    return x, margin


def hidden_fn(params, model: Dict[str, Any], tokens, *,
              precision: str = "float32", block_rows: Optional[int] = None,
              piece_rows: Optional[int] = None):
    """(T,) int tokens -> (final-normed hidden rows (T, D) float32, every
    layer's router margin (L, T): ``_route``, ``near_tie.left_out``). The
    rows go ``piece_rows`` at a time through the projections, the recurrence
    and the experts and ``block_rows`` at a time through attention's scores;
    the default for each is all of them."""
    m = model
    T = tokens.shape[0]
    block_rows, piece_rows = block_rows or T, piece_rows or T
    if T % piece_rows or piece_rows % block_rows:
        raise ValueError(f"{T} rows are not whole pieces of {piece_rows} "
                         f"rows of whole blocks of {block_rows}")
    x = params["tok_emb"]["weight"][tokens]
    margins = []
    for l in range(m["n_layers"]):
        x, margin = _layer(m, precision, params["blocks"], l, x, block_rows,
                           piece_rows)
        margins.append(margin)
    return (_rmsnorm(x, params["final_norm"]["scale"], m["rmsnorm_eps"]),
            jnp.stack(margins))


def logits_fn(params, model: Dict[str, Any], tokens, *,
              precision: str = "float32", **blocking):
    """(B, T) int tokens -> (B, T, V) float32 logits."""
    rows = lambda t: _linear(
        hidden_fn(params, model, t, precision=precision, **blocking)[0],
        params["head"]["weight"], precision)
    return jnp.stack([rows(t) for t in tokens])


#: at the cell's size: one key-value head's scores for 128 rows of 8 query
#: heads against 33,792 keys are 138 MB of float32; the convolution's input
#: for 3072 rows is 302 MB; 33,792 positions are 11 pieces
BLOCK_ROWS, PIECE_ROWS = 128, 3072


def served_token_gaps(params, model, sequences: Sequence[Tuple[Any, Any]],
                      *, pad_to: int, control: str = "") -> Dict[str, Any]:
    """For each (prompt, served tokens): one causal pass over prompt + served
    tokens, and at each served position the gap by which the served token's
    logit lies below the reference's best. With ``control`` set, also the gap
    of the token that this lower precision puts first at those positions.

    The positions ``left_out`` names (a router near-tie at a held expert)
    are left out of both, and the share
    of served positions compared is printed and returned: under
    ``LEAST_COMPARED`` fails the run (the widest gap reads infinite)."""
    R = min(BLOCK_ROWS, pad_to)
    blocking = {"block_rows": R, "piece_rows": min(PIECE_ROWS, pad_to)}
    if pad_to % blocking["piece_rows"] or blocking["piece_rows"] % R:
        raise SystemExit(f"pad_to {pad_to} is not whole pieces of "
                         f"{PIECE_ROWS} rows of whole blocks of {R}")

    # one program a pass, at every position, fixed shapes: whatever the
    # lengths, and the control's pass after the reference's, not beside it
    @functools.partial(jax.jit, static_argnames="precision")
    def hidden(params, tokens, precision):
        return hidden_fn(params, model, tokens, precision=precision,
                         **blocking)

    @functools.partial(jax.jit, static_argnames="low")
    def head(w, tokens, h, h_low, low):
        nxt = jnp.roll(tokens, -1)

        def rows_block(xs):
            h, h_low, nxt = xs
            rows = h @ w
            best = jnp.max(rows, axis=-1)
            served = best - jnp.take_along_axis(rows, nxt[:, None], -1)[:, 0]
            if not low:
                return served, served
            pick = jnp.argmax(_linear(h_low, w, low), axis=-1)
            return served, best - jnp.take_along_axis(
                rows, pick[:, None], -1)[:, 0]

        split = lambda a: a.reshape((-1, R) + a.shape[1:])
        g, g_low = jax.lax.map(rows_block,
                               (split(h), split(h_low), split(nxt)))
        return g.reshape(-1), g_low.reshape(-1)

    worst, worst_control, n_tokens, n_compared = 0.0, 0.0, 0, 0
    longest_prompt = longest = 0
    read = []
    with jax.default_matmul_precision("highest"):
        for prompt, served in sequences:
            seq = np.zeros((pad_to,), np.int32)
            n_p, n_s = len(prompt), len(served)
            seq[:n_p + n_s] = np.concatenate([prompt, served])
            at = slice(n_p - 1, n_p - 1 + n_s)
            tokens = jnp.asarray(seq)
            h, margins = hidden(params, tokens, "float32")
            h_low = hidden(params, tokens, control)[0] if control else h
            g, g_low, margins = jax.device_get(
                head(params["head"]["weight"], tokens, h, h_low, control)
                + (margins,))
            keep = ~left_out(margins)[at]
            read.append((n_p - 1, g[at], margins[:, at], keep))
            if keep.any():
                worst = max(worst, float(g[at][keep].max()))
                worst_control = max(worst_control,
                                    float(g_low[at][keep].max()))
            n_tokens += n_s
            n_compared += int(keep.sum())
            longest_prompt = max(longest_prompt, n_p)
            longest = max(longest, n_p + n_s)
    share = n_compared / max(1, n_tokens)
    # an earlier line of the output, like the harness's own
    print(json.dumps({"reference_compared": {
        "positions": n_compared, "of_served": n_tokens, "share": share,
        "left_out": "an expert held here within near_tie of the edge of "
                    "the chosen, in any layer",
        "near_tie": NEAR_TIE,
        "longest_prompt": longest_prompt,
        "longest_sequence": longest,
        "widest_gap_at": near_tie.widest(read)}}), flush=True)
    if share < LEAST_COMPARED:
        worst = float("inf")
    return {"widest_gap": worst,
            "control_widest_gap": worst_control if control else None,
            "tokens": n_tokens, "compared_share": share}
