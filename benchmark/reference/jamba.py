"""Plain Jamba (``model_type: jamba``, the dense members: ``num_experts`` 1)
in float32: the forward pass, whole, as one chip holds it.

Written from the published configuration's keys and the family's published
modelling code (``benchmark/configs/ai21-jamba2-3b.json``, PERF.md section
4). Every layer is serial, ``x += Mix(RMSNorm1(x)); x += MLP(RMSNorm2(x))``,
with no positions anywhere; the mixer is one of two kinds by the period
``layer_kinds``. For rows ``n = RMSNorm1(x)`` of width D:

``full``: NoPE multi-query attention, no bias.

    q, k, v = n Wq, n Wk, n Wv          (Hq heads, Hkv key-value heads, hd)
    out = (causal softmax(q k^T / sqrt(hd)) v) Wo         no rotation

``ssm``: Mamba-1 with a norm on each of dt, B and C, token by token.

    [u~, z] = n W_in                                    (I channels each)
    u_t = silu(sum_{i<K} conv[i] * u~_(t-K+1+i) + conv_b)   depthwise,
                                    causal, zeros before the sequence
    [dt, B, C] = u W_x                               (R, N and N columns)
    dt, B, C = RMSNorm(dt; g_dt), RMSNorm(B; g_b), RMSNorm(C; g_c)
    delta_t = softplus(dt W_dt + dt_bias)                        (I,) > 0
    s_t = exp(delta_t (x) A) * s_(t-1) + B_t (x) (delta_t * u_t)
                              A = -exp(A_log), s (N, I) a layer, s_0 = 0
    y_t = C_t . s_t + D * u_t
    out = (y * silu(z)) W_out

The recurrence is a ``lax.scan`` over positions: the form above, literally.

    MLP(m) = (silu(m Wg) * (m Wu)) Wd                    every layer, dense
    logits = RMSNorm(x_L) E^T                  the head tied to the embedding

No cache, no kernels, no import of the program; handed the weights the
benchmark drew from the seed, in float32. On a TPU a float32 product runs in
bfloat16 passes unless told otherwise, so every entry point sets
``jax.default_matmul_precision("highest")``.

ASSUMED, where ``config.json`` leaves a choice (the same list is in the
configuration file): the attention layer is the one at ``attn_layer_offset``
7 of each period of 14 (the catalog gives the order of the layer types as not
given); no rotation and no bias in attention; ``mamba_conv_bias`` true and
``mamba_proj_bias`` false as the keys say; the three inner norms carry a
scale each and share ``rms_norm_eps``; ``num_experts`` 1 makes every
feed-forward the dense SwiGLU of ``intermediate_size``
(``expert_layer_period`` is inert); the state float32. DEPARTURES from the
published model: the context of ``reduced``; ``conv_b``, ``dt_bias`` and ``D``
are what ``benchmark/weights.py`` makes of 1-D leaves, zero, and ``A_log`` its
normal(0, 0.02): every channel's ``A`` is about -1 and ``delta`` about 0.69,
so a state halves a token (PERF.md section 7).

``precision``: ``"float32"`` is the reference; ``"fp8_e4m3"`` the control, the
nearest precision below bfloat16: both operands of every projection (the
mixers', the feed-forward's three, the head) are rounded to float8 e4m3, one
scale a tensor. The convolution, the norms and the recurrence stay float32
there too. ``"bf16"`` (tests) rounds the same operands to bfloat16.

At the cell's size (3,072 positions beside 12.12 GB of float32 weights) the
rows go ``piece_rows`` at a time through the projections and the recurrence
(the state and the convolution's tail go from piece to piece) and
``block_rows`` at a time through attention's scores and the head; runs of
'ssm' layers are one ``fori_loop`` each, so a layer's weights are sliced out
of the stacked leaves where they are used and the program is six bodies, not
twenty-eight. One piece of one block is the unblocked pass, and the tests
hold the two equal.
"""

from __future__ import annotations

import functools
import json
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

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


def _kind(m: Dict[str, Any], l: int) -> str:
    return m["layer_kinds"][l % len(m["layer_kinds"])]


def _layers_of(m: Dict[str, Any], kind: str) -> Tuple[int, ...]:
    return tuple(l for l in range(m["n_layers"]) if _kind(m, l) == kind)


def param_shapes(m: Dict[str, Any]) -> Dict[str, Any]:
    """The parameter tree this reference reads (and the program holds), as
    shapes: per-layer leaves stacked on a leading axis, the mixers over the
    layers of their own kind."""
    if not (set(m.get("layer_kinds", ())) == {"ssm", "full"}
            and not m.get("n_routed_experts") and m.get("tie_embeddings")
            and not m.get("parallel_block") and not m.get("attn_out_gate")
            and m["norm"] == "rmsnorm" and m["positional"] == "none"
            and m["activation"] == "swiglu"):
        raise SystemExit("benchmark/reference/jamba.py is the dense Jamba "
                         "family's reference; another family brings its "
                         "own file")
    L, D, V, F = m["n_layers"], m["emb_dim"], m["vocab_size"], m["hidden_dim"]
    hd, Hq, Hkv = m["attn_head_dim"], m["n_heads"], m["n_kv_groups"]
    La, Ls = len(_layers_of(m, "full")), len(_layers_of(m, "ssm"))
    I, N, R, K = (m["ssm_inner"], m["ssm_state"], m["ssm_dt_rank"],
                  m["ssm_conv"])
    return {"tok_emb": {"weight": (V, D)},
            "blocks": {
                "norm1": {"scale": (L, D)},
                "norm2": {"scale": (L, D)},
                "attn": {"wq": (La, D, Hq * hd), "wk": (La, D, Hkv * hd),
                         "wv": (La, D, Hkv * hd), "wo": (La, Hq * hd, D)},
                "ssm": {"w_in": (Ls, D, 2 * I), "w_out": (Ls, I, D),
                        "conv": (Ls, K, I), "conv_b": (Ls, I),
                        "w_x": (Ls, I, R + 2 * N), "w_dt": (Ls, R, I),
                        "dt_bias": (Ls, I), "A_log": (Ls, N, I),
                        "D": (Ls, I),
                        "dt_norm": {"scale": (Ls, R)},
                        "b_norm": {"scale": (Ls, N)},
                        "c_norm": {"scale": (Ls, N)}},
                "mlp": {"gate": (L, D, F), "up": (L, D, F),
                        "down": (L, F, D)}},
            "final_norm": {"scale": (D,)}}


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _in_pieces(a, piece_rows: int):
    return a.reshape((-1, piece_rows) + a.shape[1:])


def _attend(q, q_pos, k, v, k_pos, block_rows):
    """Causal softmax(q k^T / sqrt(hd)) v, no positions. q (R, Hq, hd) at
    ``q_pos`` (R,); k, v (T, Hkv, hd) at ``k_pos`` (T,) -> (R, Hq * hd),
    ``block_rows`` query rows and one key-value head at a time."""
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


def _full_mixer(m, a, n, precision, piece_rows, block_rows):
    """The NoPE multi-query attention layer: n (T, D) -> (T, D), the queries
    a piece of rows at a time."""
    T, D = n.shape
    hd, Hq, Hkv = m["attn_head_dim"], m["n_heads"], m["n_kv_groups"]
    positions = jnp.arange(T)
    k = _linear(n, a["wk"], precision).reshape(T, Hkv, hd)
    v = _linear(n, a["wv"], precision).reshape(T, Hkv, hd)

    def piece(xs):
        x, pos = xs
        q = _linear(x, a["wq"], precision).reshape(-1, Hq, hd)
        return _linear(_attend(q, pos, k, v, positions, block_rows),
                       a["wo"], precision)

    return jax.lax.map(piece, (_in_pieces(n, piece_rows),
                               _in_pieces(positions, piece_rows))
                       ).reshape(T, D)


def _selective_scan(u, delta, A, Bm, Cm, s):
    """The recurrence, token by token. u, delta (R, I), Bm, Cm (R, N), A,
    s (N, I) -> (C_t . s_t (R, I), s after the last)."""
    def token(s, xs):
        u_t, d_t, b_t, c_t = xs
        s = jnp.exp(d_t[None, :] * A) * s + b_t[:, None] * (d_t * u_t)[None, :]
        return s, jnp.sum(c_t[:, None] * s, axis=0)

    s, y = jax.lax.scan(token, s, (u, delta, Bm, Cm))
    return y, s


def _ssm_mixer(m, p, n, precision, piece_rows):
    """The Mamba layer: n (T, D) -> (T, D), a piece of rows at a time; the
    convolution's tail and the state go from piece to piece."""
    T, D = n.shape
    I, N, R, K = (m["ssm_inner"], m["ssm_state"], m["ssm_dt_rank"],
                  m["ssm_conv"])
    eps, rows = m["rmsnorm_eps"], piece_rows
    A = -jnp.exp(p["A_log"])

    def piece(carry, x):
        tail, s = carry
        uz = _linear(x, p["w_in"], precision)
        seen = jnp.concatenate([tail, uz[:, :I]])
        u = jax.nn.silu(sum(p["conv"][i] * seen[i:i + rows]
                            for i in range(K)) + p["conv_b"])
        dbc = _linear(u, p["w_x"], precision)
        dt = _rmsnorm(dbc[:, :R], p["dt_norm"]["scale"], eps)
        Bm = _rmsnorm(dbc[:, R:R + N], p["b_norm"]["scale"], eps)
        Cm = _rmsnorm(dbc[:, R + N:], p["c_norm"]["scale"], eps)
        delta = jax.nn.softplus(_linear(dt, p["w_dt"], precision)
                                + p["dt_bias"])
        y, s = _selective_scan(u, delta, A, Bm, Cm, s)
        y = (y + p["D"] * u) * jax.nn.silu(uz[:, I:])
        return (seen[-(K - 1):], s), _linear(y, p["w_out"], precision)

    zeros = (jnp.zeros((K - 1, I), jnp.float32),       # zeros before t = 0
             jnp.zeros((N, I), jnp.float32))
    _, out = jax.lax.scan(piece, zeros, _in_pieces(n, piece_rows))
    return out.reshape(T, D)


def _mlp(p, x, precision, piece_rows):
    def piece(x):
        return _linear(jax.nn.silu(_linear(x, p["gate"], precision))
                       * _linear(x, p["up"], precision), p["down"], precision)

    return jax.lax.map(piece, _in_pieces(x, piece_rows)).reshape(x.shape)


def _take(tree, i):
    """Layer ``i`` (a traced index) of stacked leaves, sliced out where it
    is used."""
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), tree)


def _runs(m: Dict[str, Any]) -> List[Tuple[str, int, int]]:
    """The layers as runs of one kind: [(kind, first layer, one past the
    last)]."""
    runs: List[Tuple[str, int, int]] = []
    for l in range(m["n_layers"]):
        if runs and runs[-1][0] == _kind(m, l):
            runs[-1] = (runs[-1][0], runs[-1][1], l + 1)
        else:
            runs.append((_kind(m, l), l, l + 1))
    return runs


def hidden_fn(params, model: Dict[str, Any], tokens, *,
              precision: str = "float32", block_rows: Optional[int] = None,
              piece_rows: Optional[int] = None):
    """(T,) int tokens -> final-normed hidden rows (T, D) float32. The rows
    go ``piece_rows`` at a time through the projections and the recurrence
    and ``block_rows`` at a time through attention's scores; the default for
    each is all of them."""
    m, blocks = model, params["blocks"]
    T = tokens.shape[0]
    block_rows, piece_rows = block_rows or T, piece_rows or T
    if T % piece_rows or piece_rows % block_rows:
        raise ValueError(f"{T} rows are not whole pieces of {piece_rows} "
                         f"rows of whole blocks of {block_rows}")
    eps = m["rmsnorm_eps"]
    own = {"ssm": "ssm", "full": "attn"}

    def run_of(kind, lo):
        """The body of the run of layers of ``kind`` that starts at layer
        ``lo``: layer ``l``, its mixer the (l - lo)-th after the run's first
        among its kind."""
        first = _layers_of(m, kind).index(lo)

        def body(l, x):
            n = _rmsnorm(x, blocks["norm1"]["scale"][l], eps)
            mixer = _take(blocks[own[kind]], first + l - lo)
            if kind == "ssm":
                x = x + _ssm_mixer(m, mixer, n, precision, piece_rows)
            else:
                x = x + _full_mixer(m, mixer, n, precision, piece_rows,
                                    block_rows)
            n2 = _rmsnorm(x, blocks["norm2"]["scale"][l], eps)
            return x + _mlp(_take(blocks["mlp"], l), n2, precision,
                            piece_rows)
        return body

    x = params["tok_emb"]["weight"][tokens]
    for kind, lo, hi in _runs(m):
        x = jax.lax.fori_loop(lo, hi, run_of(kind, lo), x)
    return _rmsnorm(x, params["final_norm"]["scale"], eps)


def logits_fn(params, model: Dict[str, Any], tokens, *,
              precision: str = "float32", **blocking):
    """(B, T) int tokens -> (B, T, V) float32 logits."""
    rows = lambda t: _linear(
        hidden_fn(params, model, t, precision=precision, **blocking),
        params["tok_emb"]["weight"].T, precision)
    return jnp.stack([rows(t) for t in tokens])


#: at the cell's size: one key-value head's scores for 128 rows of 20 query
#: heads against 3,072 keys are 31 MB of float32, a block's logits 34 MB; a
#: piece of 1,024 rows is 42 MB of the mixer's two streams
BLOCK_ROWS, PIECE_ROWS = 128, 1024


def served_token_gaps(params, model, sequences: Sequence[Tuple[Any, Any]],
                      *, pad_to: int, control: str = "") -> Dict[str, Any]:
    """For each (prompt, served tokens): one causal pass over prompt + served
    tokens, and at each served position the gap by which the served token's
    logit lies below the reference's best. With ``control`` set, also the gap
    of the token that this lower precision puts first at those positions.
    There is no routing, so every served position is compared."""
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
    def head(emb, tokens, h, h_low, low):
        nxt = jnp.roll(tokens, -1)

        def rows_block(xs):
            h, h_low, nxt = xs
            rows = h @ emb.T
            best = jnp.max(rows, axis=-1)
            served = best - jnp.take_along_axis(rows, nxt[:, None], -1)[:, 0]
            if not low:
                return served, served
            pick = jnp.argmax(_linear(h_low, emb.T, low), axis=-1)
            return served, best - jnp.take_along_axis(
                rows, pick[:, None], -1)[:, 0]

        split = lambda a: a.reshape((-1, R) + a.shape[1:])
        g, g_low = jax.lax.map(rows_block,
                               (split(h), split(h_low), split(nxt)))
        return g.reshape(-1), g_low.reshape(-1)

    worst, worst_control, n_tokens, longest = 0.0, 0.0, 0, 0
    with jax.default_matmul_precision("highest"):
        for prompt, served in sequences:
            seq = np.zeros((pad_to,), np.int32)
            n_p, n_s = len(prompt), len(served)
            seq[:n_p + n_s] = np.concatenate([prompt, served])
            at = slice(n_p - 1, n_p - 1 + n_s)
            tokens = jnp.asarray(seq)
            h = hidden(params, tokens, "float32")
            h_low = hidden(params, tokens, control) if control else h
            g, g_low = jax.device_get(head(
                params["tok_emb"]["weight"], tokens, h, h_low, control))
            worst = max(worst, float(g[at].max()))
            worst_control = max(worst_control, float(g_low[at].max()))
            n_tokens += n_s
            longest = max(longest, n_p + n_s)
    # an earlier line of the output, like the harness's own
    print(json.dumps({"reference_compared": {
        "positions": n_tokens, "of_served": n_tokens, "share": 1.0,
        "longest_sequence": longest}}), flush=True)
    return {"widest_gap": worst,
            "control_widest_gap": worst_control if control else None,
            "tokens": n_tokens}
