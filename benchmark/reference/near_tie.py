"""The one near-tie rule of the sparse models' references.

A router picks the k largest of its logits. The program under test computes
the layers before in bfloat16, which moves a logit by up to about a hundredth
of the row's root-mean-square logit, so where an expert lies that near the
edge of the chosen k the program and a float32 reference may pick different
sets. When the expert is one whose weights this chip holds, that position's
logits then differ by a whole expert's output, which says nothing of either's
arithmetic: the references leave such positions out of the comparison and say
how many they compared.

THE EDGE IS NOT A PAIR OF RANKS. An expert held here that is chosen leaves
the chosen when the best one not chosen (the (k+1)-th logit) passes it; one
that is not chosen enters when it passes the last one chosen (the k-th).
Whatever its own rank: the 7th of 8 leaves when the 8th and the 9th both pass
it, the 10th enters when it passes the 9th and the 8th. A rule that asks only
whether the k-th or the (k+1)-th logit belongs to a held expert never sees
those (PERF.md section 6: PR 35 on the longdoc cell, PR 42 on the rag cell).

``cohere2_moe.py`` and ``solar_open2.py`` both import this and nothing else
decides what is left out; what is a model's own (the first router of
Cohere2 reads the token alone) stays in its file.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

#: an expert held here whose logit lies nearer the edge of the chosen k than
#: this share of the row's root-mean-square logit is a NEAR-TIE. How the
#: number was found (PERF.md section 6): 0.04 over the rag cell's first 25
#: sound runs, until a position whose margin was 0.056 read 0.161 (PR 28); on
#: the longdoc cell's served tokens the reference with bfloat16 operands
#: chose another way at margins up to 0.034, and 0.03 left a 0.198 standing
#: where 0.04 and over left 0.086 (PR 35).
NEAR_TIE = 0.08

#: the least share of the served positions a run has to compare; under it
#: the widest gap reads infinite. At ``NEAR_TIE`` each layer leaves out about
#: a sixth of the positions (eight or twenty held experts, each with about
#: 0.10 sigma of edge on either side of it to fall into), four layers about
#: a half: the longdoc cell's runs compared 0.46-0.51 of their positions
#: (PR 35), the rag cell's float32 pass over random tokens 0.49 and its 28
#: sound runs 0.478-0.719 with the first layer's own threshold (PR 42): a
#: greedy sequence's runs of one token move a run's share by a tenth either
#: way.
LEAST_COMPARED = 0.3


def _to_edge(logits, top, k: int, held):
    """For each expert held here how far its logit lies from changing sides
    (N, len(held)), and whether it is among the chosen (N, len(held))."""
    last_in, best_out = top[:, k - 1:k], top[:, k:k + 1]
    ours = logits[:, held]
    chosen = ours >= last_in
    return jnp.where(chosen, ours - best_out, last_in - ours), chosen


def margin(logits, top, k: int, held):
    """How near the edge of the chosen k the nearest expert HELD HERE lies,
    as a share of the row's rms logit: a chosen one over the best not chosen,
    one not chosen under the last chosen. logits (N, E); ``top`` (N, k + 1),
    the row's largest logits in falling order as the caller's ``top_k`` gave
    them (where E <= k every expert is chosen and nothing can tie: inf);
    ``held`` the ids of the experts held here -> (N,)."""
    if top.shape[-1] <= k:
        return jnp.full(logits.shape[:1], jnp.inf)
    to_edge, _ = _to_edge(logits, top, k, held)
    rms = jnp.sqrt(jnp.mean(logits ** 2, axis=-1))
    return jnp.min(to_edge, axis=-1) / rms


def nearest(logits, top, k: int, held):
    """The held expert that ``margin`` measured: -> (its index in ``held``
    (N,), whether it is among the chosen (N,) bool). A tie's other
    resolution is that expert on the other side of the edge."""
    to_edge, chosen = _to_edge(logits, top, k, held)
    at = jnp.argmin(to_edge, axis=-1)
    return at, jnp.take_along_axis(chosen, at[:, None], axis=-1)[:, 0]


def left_out(margins, first: float = NEAR_TIE) -> np.ndarray:
    """margins (L, T), one row a layer (``margin``) -> (T,) bool: the
    positions the comparison leaves out, those where in any layer an expert
    held here lies within ``NEAR_TIE`` of the edge of the chosen. ``first``:
    the first layer's own threshold, for a model whose first router reads
    the token's embedding alone, which no bfloat16 layer has moved
    (``cohere2_moe.FIRST_TIE``)."""
    margins = np.asarray(margins)
    return (margins[0] < first) | (margins[1:] < NEAR_TIE).any(axis=0)


def widest(sequences) -> dict:
    """Where the widest compared gap stands, for the line a reference
    prints, so that a false ``correct`` says whether a tie stood beside it.
    ``sequences``: for each checked sequence the position that chose its
    first served token and, over its served positions, (gaps (n,), margins
    (L, n), compared (n,) bool) -> the sequence, the position in it, the
    index among its served tokens, the gap, and the smallest margin of any
    layer there with its layer (a compared position's is ``NEAR_TIE`` or
    more)."""
    best = {}
    for i, (first, gaps, margins, compared) in enumerate(sequences):
        gaps, margins = np.asarray(gaps), np.asarray(margins)
        if not np.any(compared):
            continue
        at = int(np.argmax(np.where(compared, gaps, -np.inf)))
        if not best or gaps[at] > best["gap"]:
            layer = int(np.argmin(margins[:, at]))
            best = {"sequence": i, "position": first + at, "served_index": at,
                    "gap": float(gaps[at]), "layer": layer,
                    "smallest_margin": float(margins[layer, at])}
    return best
