"""Plain Cohere2-MoE (command-a-plus) in float32: the forward pass, as one
chip of an expert-parallel deployment holds it.

Follows the published configuration (``model_type: cohere2_moe``), written
down as equations in ``benchmark/configs/command-a-plus-05-2026.json`` and
PERF.md section 4. For layer ``l``, kind ``sliding`` or ``full`` by the
period ``layer_kinds``, and rows ``x`` of width D:

    n  = (x - mean x) / sqrt(var x + eps) * g                 (no bias)
    q, k, v = n Wq, n Wk, n Wv     (Hq heads, Hkv key-value heads, no bias)
    sliding: q, k rotated (RoPE over all of a head, pairs (2i, 2i+1));
             position i attends to j with i - window < j <= i
    full:    no rotation; causal over everything
    A  = concat_heads(softmax(q k^T / sqrt(hd)) v) Wo
    s  = sigmoid(n Wr) over ALL routed experts; T = the k largest;
         w_e = s_e / sum_{e' in T} s_e'
    E(n) = (silu(n Wg) * (n Wu)) Wd             routed and shared alike
    F  = sum_{e in T, e held here} w_e E_e(n) + mean_s S_s(n)
    x' = x + A + F
    logits = LayerNorm(x_L) Emb^T                (tied, logit scale 1)

``experts_held`` names the routed experts whose weights are here; what the
others would have added is left out, and that partial ``x'`` goes on to the
next layer, exactly as the program under test does it. No cache, no kernels,
no import of the program; handed the weights the benchmark drew from the
seed, in float32. On a TPU a float32 product runs in bfloat16 passes unless
told otherwise, so every entry point sets
``jax.default_matmul_precision("highest")``.

``precision``: ``"float32"`` is the reference; ``"fp8_e4m3"`` the control,
the nearest precision below bfloat16: both operands of every projection, of
every expert's three products and of the head are rounded to float8 e4m3
(one scale a tensor). The router stays float32 there too, as fp8 recipes
keep it.

At the cell's size (17k tokens beside 12.5 GB of float32 weights) the rows
go a piece at a time through the projections and the experts, one expert at
a time, and a block at a time, one key-value head at a time, through
attention's scores; one piece of one block is the unblocked pass, and the
tests hold the two equal.
"""

from __future__ import annotations

import collections
import functools
import itertools
import json
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import near_tie
from benchmark.reference.near_tie import LEAST_COMPARED, NEAR_TIE, left_out

F8_MAX = 448.0          # largest finite float8_e4m3fn

#: WHAT IS LEFT OUT of the comparison is ``near_tie.py``'s to say, for this
#: file and ``solar_open2.py`` alike: the positions where in any layer an
#: expert held here lies within ``NEAR_TIE`` of the edge of the chosen k, at
#: any rank (``FIRST_TIE``, below, in the first layer). Until PR 42 this file
#: asked only whether the k-th or the (k+1)-th logit was a held expert's;
#: what that missed is PERF.md section 6.
#:
#: THE FIRST LAYER'S NEAR-TIES ARE THE TOKEN'S. Its router reads the
#: normalised embedding and nothing else, so a token that ties there ties at
#: every one of its positions, and the program resolves them all the same
#: way. A greedy sequence of seeded weights ends in runs of one token; when
#: that token is such a tie and the program's rounding took the other side,
#: hundreds of positions carry another expert's output into the keys and
#: values of the layers above, and positions with no tie of their own then
#: differ by more than the fp8 control does (PR 28, seed 16200000029: token
#: 2157, 264 times in one sequence, margin 0.0011 of the rms logit, gaps to
#: 0.38 at positions with no tie at all). One flipped position among
#: thousands is diluted by attention; a repeated one is not. So for the tie
#: tokens of a sequence that stand ``COHERENT_REPEATS`` times or more in it
#: (the ``MAX_COHERENT`` most frequent), the sequence is read under each
#: resolution (the held expert nearest the edge on this side of it or on the
#: other, ``_route``'s ``swap``, at all of the token's positions at once) and
#: judged by the one that fits the served tokens best: a tie within rounding
#: may fall either way, the reference still takes nothing from the program,
#: and a program that fits neither fails. Whatever the held expert's rank:
#: until PR 42 only the tie between ranks k and k+1 was read both ways, and
#: four tie tokens in ten tie elsewhere.
COHERENT_REPEATS, MAX_COHERENT = 8, 3

#: which first-layer margins are ties. The first router's input is exact in
#: program and reference, so its logits move only by the rounding to
#: bfloat16 of the embedding, the normalised row and the router: over a
#: whole vocabulary 0.002 of the rms logit at the median and 0.016 at most,
#: and the held chosen set changed at margins up to 0.0105 (three seeds'
#: draws on the CPU, PR 42, which also flip tokens 2157 and 21800 as the
#: chip's runs did). ``NEAR_TIE`` is for layers whose input has been through
#: bfloat16 layers. Here it would name a seventh of the vocabulary: a served
#: run of one such token is left out whole (216 of 216 positions at a margin
#: of 0.074; a run's share compared fell to 0.34: PR 42's chip runs), and
#: each repeated one costs a second pass over its sequence for nothing. So
#: the first layer leaves out, and reads both ways, within ``FIRST_TIE``.
FIRST_TIE = 0.03


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


def param_shapes(m: Dict[str, Any]) -> Dict[str, Any]:
    """The parameter tree this reference reads (and the program holds), as
    shapes: per-layer leaves stacked on a leading axis, an expert's on a
    second."""
    if not (m.get("n_routed_experts") and m.get("parallel_block")
            and m.get("tie_embeddings") and m["norm"] == "layernorm"
            and not m["norm_bias"]):
        raise SystemExit("benchmark/reference/cohere2_moe.py is the Cohere2 "
                         "MoE family's reference; another family brings its "
                         "own file")
    L, D, V, F = m["n_layers"], m["emb_dim"], m["vocab_size"], m["hidden_dim"]
    hd, Hq, Hkv = m["attn_head_dim"], m["n_heads"], m["n_kv_groups"]
    ffn = lambda n: {"gate": (L, n, D, F), "up": (L, n, D, F),
                     "down": (L, n, F, D)}
    return {"tok_emb": {"weight": (V, D)},
            "blocks": {
                "norm1": {"scale": (L, D)},
                "attn": {"wq": (L, D, Hq * hd), "wk": (L, D, Hkv * hd),
                         "wv": (L, D, Hkv * hd), "wo": (L, Hq * hd, D)},
                "moe": {"router": (L, D, m["n_routed_experts"]),
                        "experts": ffn(len(_held(m))),
                        "shared": ffn(m["n_shared_experts"])}},
            "final_norm": {"scale": (D,)}}


def _layernorm(x, scale, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale


def _rope(x, positions, theta: float):
    """x (T, H, hd) rotated at ``positions`` (T,): the pair (2i, 2i+1) by the
    angle position / theta^(2i / hd)."""
    hd = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angle = positions.astype(jnp.float32)[:, None, None] * inv_freq
    even, odd = x[..., 0::2], x[..., 1::2]
    c, s = jnp.cos(angle), jnp.sin(angle)
    return jnp.stack([even * c - odd * s, odd * c + even * s],
                     axis=-1).reshape(x.shape)


def _expert(x, gate, up, down, precision):
    return _linear(jax.nn.silu(_linear(x, gate, precision))
                   * _linear(x, up, precision), down, precision)


def _route(m, n, router, swap=None):
    """-> (weight of each routed expert for each row (N, E), zero outside
    the k chosen; margin (N,): how near the edge of the chosen k the nearest
    expert held here lies, at any rank: ``near_tie.margin``). Rows where
    ``swap`` (N,) bool is set take the other resolution of their tie: that
    expert on the other side of the edge (chosen, it leaves and the (k+1)-th
    enters; not chosen, it enters and the k-th leaves)."""
    logits = n @ router
    k, held = m["n_experts_per_tok"], jnp.asarray(_held(m))
    top, ids = jax.lax.top_k(logits, min(k + 1, logits.shape[-1]))
    chosen, chosen_ids = top[:, :k], ids[:, :k]
    if swap is not None and top.shape[-1] > k:
        at, inside = near_tie.nearest(logits, top, k, held)
        column = jax.nn.one_hot(held[at], logits.shape[-1], dtype=bool)
        forced = jnp.where(
            column, jnp.where(inside, -jnp.inf, jnp.inf)[:, None], logits)
        other_ids = jax.lax.top_k(forced, k)[1]
        chosen_ids = jnp.where(swap[:, None], other_ids, chosen_ids)
        chosen = jnp.take_along_axis(logits, chosen_ids, axis=-1)
    scores = jax.nn.sigmoid(chosen)
    w = scores / jnp.sum(scores, -1, keepdims=True)
    dense = jnp.zeros_like(logits).at[
        jnp.arange(logits.shape[0])[:, None], chosen_ids].set(w)
    return dense, near_tie.margin(logits, top, k, held)


def _attend(m, kind, q, q_pos, k, v, k_pos, block_rows):
    """softmax(q k^T / sqrt(hd)) v under the kind's mask. q (R, Hq, hd) at
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
        if kind == "sliding":
            seen &= pos[:, None] - k_pos[None, :] < m["sliding_window"]

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


def _layer(m, kind, precision, blocks, l, x, block_rows, piece_rows,
           unknown_zero=0, swap=None):
    """Layer ``l`` of the stacked ``blocks``: x (T, D) -> (x' (T, D),
    its router's margin (T,)). ``swap``: ``_route``'s."""
    moe = blocks["moe"]
    p = jax.tree_util.tree_map(
        lambda a: a[l], {"norm1": blocks["norm1"], "attn": blocks["attn"],
                         "router": moe["router"]})
    a = p["attn"]
    T = x.shape[0]
    hd, Hq, Hkv = m["attn_head_dim"], m["n_heads"], m["n_kv_groups"]
    positions = jnp.arange(T)
    n = _layernorm(x, p["norm1"]["scale"], m["layernorm_eps"])
    k = _linear(n, a["wk"], precision).reshape(T, Hkv, hd)
    v = _linear(n, a["wv"], precision).reshape(T, Hkv, hd)
    if kind == "sliding":
        k = _rope(k, positions, m["rope_base"])
    def mats(group, i):
        # each matrix one slice of the stacked leaf, taken where it is used,
        # at an index the compiler cannot fold (``unknown_zero``): with
        # constant indices it merges the slices of a layer's experts into
        # one copy of them all (1.6 GB a leaf in float32), and a 20k-token
        # pass beside the weights then needs 22 GB
        for name in ("gate", "up", "down"):
            leaf = moe[group][name]
            yield jax.lax.dynamic_slice(
                leaf, (l + unknown_zero, i + unknown_zero, 0, 0),
                (1, 1) + leaf.shape[2:])[0, 0]

    # pieces inside, experts outside: a rounded copy of an expert's weights
    # (the control) then lives for that expert's pieces only
    pieces = [slice(r, r + piece_rows) for r in range(0, T, piece_rows)]
    outs = []
    for rows in pieces:
        q = _linear(n[rows], a["wq"], precision).reshape(-1, Hq, hd)
        if kind == "sliding":
            q = _rope(q, positions[rows], m["rope_base"])
        outs.append(x[rows] + _linear(
            _attend(m, kind, q, positions[rows], k, v, positions, block_rows),
            a["wo"], precision))
    w, margin = _route(m, n, p["router"], swap)
    S = m["n_shared_experts"]
    for group, i, scale in (
            [("experts", i, w[:, e:e + 1]) for i, e in enumerate(_held(m))]
            + [("shared", s, 1.0 / S) for s in range(S)]):
        gate, up, down = mats(group, i)
        for j, rows in enumerate(pieces):
            part = _expert(n[rows], gate, up, down, precision)
            outs[j] += part * (scale if group == "shared" else scale[rows])
    return jnp.concatenate(outs), margin


def first_layer_margins(params, model: Dict[str, Any], tokens):
    """(T,) int tokens -> (T,): the first router's margin, which is the
    token's (what ``hidden_fn`` computes there, without the rest of the
    pass)."""
    first = jax.tree_util.tree_map(
        lambda a: a[0], {"norm1": params["blocks"]["norm1"],
                         "router": params["blocks"]["moe"]["router"]})
    n = _layernorm(params["tok_emb"]["weight"][tokens],
                   first["norm1"]["scale"], model["layernorm_eps"])
    return _route(model, n, first["router"])[1]


def hidden_fn(params, model: Dict[str, Any], tokens, *,
              precision: str = "float32", block_rows: Optional[int] = None,
              piece_rows: Optional[int] = None, swap_first=None):
    """(T,) int tokens -> (final-normed hidden rows (T, D) float32, every
    layer's router margin (L, T): ``_route``, ``near_tie.left_out``).
    ``swap_first`` (T,) bool: positions whose first-layer tie takes its other
    resolution (``_route``'s ``swap``).
    The rows go ``piece_rows`` at a time through the projections and the
    experts and ``block_rows`` at a time through attention's scores; the
    default for each is all of them."""
    m = model
    T = tokens.shape[0]
    block_rows, piece_rows = block_rows or T, piece_rows or T
    if T % piece_rows or piece_rows % block_rows:
        raise ValueError(f"{T} rows are not whole pieces of {piece_rows} "
                         f"rows of whole blocks of {block_rows}")
    x = params["tok_emb"]["weight"][tokens]
    margins = []
    kinds = m["layer_kinds"]
    for l in range(m["n_layers"]):
        x, margin = _layer(m, kinds[l % len(kinds)], precision,
                           params["blocks"], l, x, block_rows, piece_rows,
                           # token ids are never negative: 0, unprovably
                           unknown_zero=jnp.minimum(tokens[0], 0),
                           swap=swap_first if l == 0 else None)
        margins.append(margin)
    return (_layernorm(x, params["final_norm"]["scale"],
                       m["layernorm_eps"]), jnp.stack(margins))


def logits_fn(params, model: Dict[str, Any], tokens, *,
              precision: str = "float32", **blocking):
    """(B, T) int tokens -> (B, T, V) float32 logits."""
    rows = lambda t: _linear(
        hidden_fn(params, model, t, precision=precision, **blocking)[0],
        params["tok_emb"]["weight"].T, precision)
    return jnp.stack([rows(t) for t in tokens])


#: at the cell's size: one key-value head's scores for 256 rows against
#: 20480 keys are 335 MB of float32; the queries of 4096 rows are 268 MB
BLOCK_ROWS, PIECE_ROWS = 128, 2048


def served_token_gaps(params, model, sequences: Sequence[Tuple[Any, Any]],
                      *, pad_to: int, control: str = "") -> Dict[str, Any]:
    """For each (prompt, served tokens): one causal pass over prompt + served
    tokens, and at each served position the gap by which the served token's
    logit lies below the reference's best. With ``control`` set, also the gap
    of the token that this lower precision puts first at those positions.

    The positions ``near_tie.left_out`` names (in some layer an expert held
    here within ``NEAR_TIE`` of the edge of the chosen, at any rank; in the
    first layer within ``FIRST_TIE``) are left out of both, and the share of
    served positions compared is printed and returned: under
    ``LEAST_COMPARED`` fails the run (the widest gap reads infinite). A
    sequence in which a first-layer tie token is repeated is read under each
    resolution of that tie and judged by the best fit (``COHERENT_REPEATS``,
    above); the line printed names the tokens, how often each stands and the
    resolution taken, and where the widest compared gap stands."""
    R = min(BLOCK_ROWS, pad_to)
    blocking = {"block_rows": R, "piece_rows": min(PIECE_ROWS, pad_to)}
    if pad_to % blocking["piece_rows"] or blocking["piece_rows"] % R:
        raise SystemExit(f"pad_to {pad_to} is not whole pieces of "
                         f"{PIECE_ROWS} rows of whole blocks of {R}")

    # one program a pass, at every position, fixed shapes: whatever the
    # lengths, and the control's pass after the reference's, not beside it
    @functools.partial(jax.jit, static_argnames="precision")
    def hidden(params, tokens, swap_first, precision):
        return hidden_fn(params, model, tokens, precision=precision,
                         swap_first=swap_first, **blocking)

    first_margins = jax.jit(lambda params, tokens: first_layer_margins(
        params, model, tokens))

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

    def gaps(params, tokens, swap_first, low):
        h, margins = hidden(params, tokens, swap_first, "float32")
        h_low = hidden(params, tokens, swap_first, low)[0] if low else h
        return head(params["tok_emb"]["weight"], tokens, h, h_low, low) \
            + (margins,)

    worst, worst_control, n_tokens, n_compared = 0.0, 0.0, 0, 0
    coherent_ties, read = [], []
    # how far the check reached: a window layer forgets from position
    # ``sliding_window`` on, and the program's ring (that many positions
    # plus one prefill chunk) has wrapped once a sequence is longer
    longest_prompt = longest = past_window = 0
    with jax.default_matmul_precision("highest"):
        for prompt, served in sequences:
            seq = np.zeros((pad_to,), np.int32)
            n_p, n_s = len(prompt), len(served)
            seq[:n_p + n_s] = np.concatenate([prompt, served])
            at = slice(n_p - 1, n_p - 1 + n_s)
            tokens = jnp.asarray(seq)
            live = np.arange(pad_to) < n_p + n_s
            first = np.asarray(first_margins(params, tokens))
            tied = live & (first < FIRST_TIE)
            repeated = [(int(t), c) for t, c in collections.Counter(
                seq[tied].tolist()).most_common(MAX_COHERENT)
                if c >= COHERENT_REPEATS]
            # every resolution of the repeated first-layer ties: one pass
            # where there is none, which is nearly always
            readings = []
            for taken in itertools.product((False, True),
                                           repeat=len(repeated)):
                swap = np.zeros((pad_to,), bool)
                for (t, _), on in zip(repeated, taken):
                    swap |= on & live & (seq == t)
                g, g_low, margins = jax.device_get(
                    gaps(params, tokens, jnp.asarray(swap), control))
                keep = ~left_out(margins, first=FIRST_TIE)[at]
                readings.append((
                    float(g[at][keep].max()) if keep.any() else 0.0,
                    float(g_low[at][keep].max()) if keep.any() else 0.0,
                    keep, taken, g[at], margins[:, at]))
            # the layers above tie elsewhere under another resolution, so
            # each reading has its own positions; one that kept next to
            # nothing would fit anything and is not taken
            most = max(int(r[2].sum()) for r in readings)
            fit = min((r for r in readings if 2 * int(r[2].sum()) >= most),
                      key=lambda r: r[0])
            keep = fit[2]
            read.append((n_p - 1, fit[4], fit[5], keep))
            worst = max(worst, fit[0])
            worst_control = max(worst_control, min(r[1] for r in readings))
            if repeated:
                coherent_ties.append({
                    "tokens": repeated, "swapped": list(fit[3]),
                    "margins": [float(first[seq == t][0])
                                for t, _ in repeated],
                    "widest_gap_by_resolution": [r[0] for r in readings]})
            n_tokens += n_s
            n_compared += int(keep.sum())
            longest_prompt = max(longest_prompt, n_p)
            longest = max(longest, n_p + n_s)
            past_window += int(keep[max(
                0, model["sliding_window"] - (n_p - 1)):].sum())
    share = n_compared / max(1, n_tokens)
    # an earlier line of the output, like the harness's own
    print(json.dumps({"reference_compared": {
        "positions": n_compared, "of_served": n_tokens, "share": share,
        "left_out": "an expert held here within near_tie of the edge of "
                    "the chosen, in any layer (first_tie in the first)",
        "near_tie": NEAR_TIE, "first_tie": FIRST_TIE,
        "longest_prompt": longest_prompt,
        "longest_sequence": longest,
        "compared_past_window": past_window,
        "repeated_first_layer_ties": coherent_ties,
        "widest_gap_at": near_tie.widest(read)}}), flush=True)
    if share < LEAST_COMPARED:
        worst = float("inf")
    return {"widest_gap": worst,
            "control_widest_gap": worst_control if control else None,
            "tokens": n_tokens, "compared_share": share}
