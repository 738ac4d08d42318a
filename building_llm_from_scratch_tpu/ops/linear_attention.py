"""The gated delta rule with a per-channel decay: the recurrence of a
'linear' layer (``cfg.layer_kinds``), in two forms, and the short causal
convolution in front of it.

One head keeps a state ``S`` (dk, dv), float32, zero before a sequence. For
the token at ``t``, with its key and query L2-normalised (the query also
scaled by dk ** -0.5), a log-decay ``g_t <= 0`` a key channel and a step
size ``beta_t``:

    S~  = Diag(exp(g_t)) S_(t-1)
    S_t = S~ + beta_t k_t (v_t - S~^T k_t)^T
    o_t = S_t^T q_t

``recurrent_step`` is that, literally, for one token a row: what a decode
tick runs. ``chunked_delta_rule`` runs T tokens from an incoming state to T
outputs and an outgoing state in sub-chunks of ``SUB_CHUNK``: inside a
sub-chunk the T corrections ``u_t = beta_t (v_t - S~_t^T k_t)`` solve one
unit lower-triangular system (the WY form), solved for every sub-chunk at
once before the state is known (``u = w_v - w_k S``), so the walk over the
sub-chunks is three products a step. ``recurrent_scan`` is the first form
over a sequence (the tests hold the chunked form to it).

DECAYS ARE NEVER INVERTED. A product ``a_t . (exp(G_t - G_s) * b_s)``, G the
running sum of g, is not split into ``exp(G_t)`` and ``exp(-G_s)``: a
channel that decays by e^-11 a token (A = 16) overflows float32 in seven
tokens. ``_decayed_products`` splits at a token r between s and t instead
(``exp(G_t - G_r)`` and ``exp(G_r - G_s)``, both at most 1), halving the
sub-chunk down to blocks of ``BASE`` tokens whose pairs are written out.

Everything here is float32 with products at ``Precision.HIGHEST``: the
recurrence is some 5 GFLOP a 512-token chunk of 64 heads against the 141 of
that layer's projections, and its state lives for the whole sequence.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

SUB_CHUNK = 64      # tokens whose corrections one triangular system holds
BASE = 16           # tokens whose pairwise decays are written out
_HI = jax.lax.Precision.HIGHEST


def linear_attention_path(Tq: int) -> str:
    """THE rule for the form a program's 'linear' layers run, made once, at
    trace time, on what the code can observe: ``"step"`` for one token a row
    (a decode tick), ``"chunked"`` for a span (a prefill chunk, a prompt, a
    training sequence). The engine reports the names
    (``stats()["linear_attention"]``)."""
    return "step" if Tq == 1 else "chunked"


def l2norm(x: jnp.ndarray) -> jnp.ndarray:
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


@jax.named_scope("linear_conv")
def causal_conv(pre: jnp.ndarray, tail: jnp.ndarray, w: jnp.ndarray,
                n_valid=None, bias: Optional[jnp.ndarray] = None
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """A depthwise causal convolution of K taps over time (plus ``bias``
    (Ch,), where given), then SiLU.
    ``pre`` (B, T, Ch) are the tokens' channels, ``tail`` (B, K-1, Ch) the
    K-1 tokens before them (zeros before a sequence), ``w`` (K, Ch), its last
    tap on the current token. -> (float32 (B, T, Ch); the next tail: the
    K-1 tokens that end at the ``n_valid``-th of these, all T where None)."""
    K, T = w.shape[0], pre.shape[1]
    x = jnp.concatenate([tail.astype(pre.dtype), pre], axis=1)
    w32 = w.astype(jnp.float32)
    out = sum(x[:, j:j + T].astype(jnp.float32) * w32[j] for j in range(K))
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    start = T if n_valid is None else n_valid
    new_tail = jax.lax.dynamic_slice_in_dim(x, start, K - 1, axis=1)
    return jax.nn.silu(out), new_tail.astype(tail.dtype)


def recurrent_step(q, k, v, g, beta, state):
    """One token a row. q, k, g (..., H, dk), v (..., H, dv), beta (..., H),
    state (..., H, dk, dv), all float32 -> (o (..., H, dv), the new state).
    The state is read twice and written once: ``o`` comes from the decayed
    state and the correction, not from a third pass over the new one."""
    decayed = state * jnp.exp(g)[..., None]
    k_s = jnp.sum(decayed * k[..., None], axis=-2)
    q_s = jnp.sum(decayed * q[..., None], axis=-2)
    u = beta[..., None] * (v - k_s)
    o = q_s + u * jnp.sum(q * k, axis=-1, keepdims=True)
    return o, decayed + k[..., None] * u[..., None, :]


@jax.jit     # a program's layers of one shape lower ONE body
def recurrent_step_rows(q, k, v, g, beta, state, table):
    """``recurrent_step`` for the rows ``table`` names alone ((S + 1,) int32:
    the rows that decode in front, their count last; ``live_rows_table``),
    in place: one trip a named row slices that row's state out, steps it and
    writes it back where it lay. q, k, g (S, H, dk), v (S, H, dv), beta (S,
    H), state (S, H, dk, dv) -> (o (S, H, dv), the state). A row that is not
    named is neither read nor written: it keeps its state bit for bit and
    its ``o`` reads 0. A trip moves one state in and out (some 8 MB at 64
    heads of 128 x 128), so a few long trips: the form for few, large
    states."""
    S = state.shape[0]
    row = lambda a, r: jax.lax.dynamic_slice_in_dim(a, r, 1, axis=0)
    put = lambda a, b, r: jax.lax.dynamic_update_slice_in_dim(a, b, r, axis=0)

    def one(j, carry):
        o, state = carry
        r = table[j]
        o_r, s_r = recurrent_step(*(row(a, r) for a in (q, k, v, g, beta)),
                                  row(state, r))
        return put(o, o_r, r), put(state, s_r, r)

    return jax.lax.fori_loop(0, table[S], one,
                             (jnp.zeros(v.shape, jnp.float32), state))


def recurrent_scan(q, k, v, g, beta, state):
    """``recurrent_step`` over T tokens: q, k, g (B, T, H, dk), v (B, T, H,
    dv), beta (B, T, H) -> (o (B, T, H, dv), the state after the last)."""
    def one(state, xs):
        o, state = recurrent_step(*xs, state)
        return state, o

    time_first = lambda a: jnp.moveaxis(a, 1, 0)
    state, o = jax.lax.scan(one, state,
                            tuple(map(time_first, (q, k, v, g, beta))))
    return jnp.moveaxis(o, 0, 1), state


def _decayed_products(a, b, G):
    """P[t, s] = sum_d a[t, d] b[s, d] exp(G[t, d] - G[s, d]) for s <= t and
    0 above the diagonal. a, b, G (..., c, dk) (broadcast against each
    other in front), G non-increasing along c -> (..., c, c)."""
    c = G.shape[-2]
    if c <= BASE:
        lower = jnp.tril(jnp.ones((c, c), bool))
        # masked BEFORE the exponential: above the diagonal the difference
        # is positive and may be large
        decay = jnp.exp(jnp.where(
            lower[:, :, None], G[..., :, None, :] - G[..., None, :, :],
            -jnp.inf))
        return jnp.sum(a[..., :, None, :] * b[..., None, :, :] * decay, -1)
    h = c // 2
    lo, hi = (lambda x: x[..., :h, :]), (lambda x: x[..., h:, :])
    G_r = G[..., h - 1:h, :]             # the last token of the first half
    off = jnp.einsum("...td,...sd->...ts", hi(a) * jnp.exp(hi(G) - G_r),
                     lo(b) * jnp.exp(G_r - lo(G)), precision=_HI)
    first = _decayed_products(lo(a), lo(b), lo(G))
    second = _decayed_products(hi(a), hi(b), hi(G))
    return jnp.concatenate([
        jnp.concatenate([first, jnp.zeros_like(off)], -1),
        jnp.concatenate([off, second], -1)], -2)


def _sub_chunk(T: int) -> int:
    c = BASE
    while c < min(T, SUB_CHUNK):
        c *= 2
    return c


@jax.named_scope("linear_attention")
def chunked_delta_rule(q, k, v, g, beta, state,
                       sub_chunk: Optional[int] = None):
    """T tokens from ``state`` to their outputs and the state after them.
    q, k, g (B, T, H, dk), v (B, T, H, dv), beta (B, T, H), state (B, H, dk,
    dv), float32 -> (o (B, T, H, dv), state). A token with ``g = 0`` and
    ``beta = 0`` leaves the state as it was (padding)."""
    B, T, H, dk = q.shape
    c = sub_chunk or _sub_chunk(T)
    n = -(-T // c)
    pad = n * c - T

    def blocks(a):          # (B, T, H, ...) -> (n, B, H, c, ...)
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        a = a.reshape((B, n, c) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 1, 0), 2, 3)

    q, k, v, g = (blocks(a.astype(jnp.float32)) for a in (q, k, v, g))
    beta = blocks(beta.astype(jnp.float32)[..., None])        # (n,B,H,c,1)
    G = jnp.cumsum(g, axis=-2)                     # to each token, itself in
    # k_t . decayed k_s (below the diagonal) and q_t . decayed k_s (on it too)
    kk, qk = _decayed_products(jnp.stack([k, q]), k[None], G[None])
    system = jnp.eye(c, dtype=jnp.float32) + beta * jnp.tril(kk, -1)
    from_start = jnp.exp(G)                  # decay from the incoming state
    # u = w_v - w_k S for whatever state S comes in: both solved here
    w = jax.scipy.linalg.solve_triangular(
        system, jnp.concatenate([beta * v, beta * k * from_start], -1),
        lower=True, unit_diagonal=True)
    w_v, w_k = w[..., :v.shape[-1]], w[..., v.shape[-1]:]
    G_end = G[..., -1:, :]
    to_end = k * jnp.exp(G_end - G)          # decay to the sub-chunk's end

    def one(S, xs):
        w_v, w_k, q_in, qk, to_end, end = xs
        u = w_v - jnp.einsum("bhck,bhkv->bhcv", w_k, S, precision=_HI)
        o = (jnp.einsum("bhck,bhkv->bhcv", q_in, S, precision=_HI)
             + jnp.einsum("bhts,bhsv->bhtv", qk, u, precision=_HI))
        S = S * end[..., None] + jnp.einsum("bhck,bhcv->bhkv", to_end, u,
                                            precision=_HI)
        return S, o

    state, o = jax.lax.scan(
        one, state.astype(jnp.float32),
        (w_v, w_k, q * from_start, qk, to_end, jnp.exp(G_end[..., 0, :])))
    o = jnp.moveaxis(jnp.moveaxis(o, 2, 3), 0, 1)            # (B, n, c, H, dv)
    return o.reshape(B, n * c, H, -1)[:, :T], state
