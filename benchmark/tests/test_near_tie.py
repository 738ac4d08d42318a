"""The one near-tie rule (``benchmark/reference/near_tie.py``) through both
sparse references at their tiny sizes (``tiny_cohere2.py``,
``tiny_solar_open2.py``: 8 routed experts, the best 2 chosen, experts 1, 5
and 6 held): router logits built so that an expert lies near the edge of the
chosen at each rank, held here and held elsewhere; and the rag cell's finding
of PR 42 replayed at the tiny size: a program that chose the other way where
a held expert FIRST of two lay within rounding of the third reads over the
limit under the rule as it was and under it with the rule as it is; and with
that token repeated, the sequence is read under both resolutions of its tie.
"""

import importlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import spec, weights
from benchmark.reference import near_tie

CELLS = {"cohere2_moe": ("serve_command_a_plus_rag_mixed", "tiny_cohere2"),
         "solar_open2": ("serve_solar_open2_longdoc_mixed",
                         "tiny_solar_open2")}
HELD, AWAY = 5, 3         # an expert held here, one held on another chip

# rows of eight logits by rank (``row`` puts the expert under test at one of
# them). With k = 2, ranks 1 and 2 are chosen; "7th within the margin of the 9th" of the cell's k = 8 is rank 1
# near rank 3 here, "10th within the margin of the 8th" is rank 4 near rank 2
RANKED = {
    "chosen_not_last_near_best_out": [1.000, 0.998, 0.996, 0.5, 0.4, 0.3,
                                      0.2, 0.1],          # ranks 1, 2, 3 tie
    "not_chosen_past_best_out_near_last_in": [1.5, 1.000, 0.998, 0.996, 0.4,
                                              0.3, 0.2, 0.1],  # 2, 3, 4 tie
    "last_in_and_best_out": [1.5, 1.000, 0.998, 0.5, 0.4, 0.3, 0.2, 0.1],
    "no_tie": [1.5, 1.0, 0.5, 0.4, 0.3, 0.2, 0.1, 0.05],
}
CASES = [
    # (logits by rank, rank of the expert under test, left out if held here)
    ("chosen_not_last_near_best_out", 1, True),
    ("not_chosen_past_best_out_near_last_in", 4, True),
    ("last_in_and_best_out", 2, True),
    ("last_in_and_best_out", 3, True),
    ("no_tie", 1, False),
    ("no_tie", 2, False),
    ("no_tie", 3, False),
]


def tiny_model(reference: str):
    cell, tiny = CELLS[reference]
    return importlib.import_module(f"benchmark.tests.{tiny}").tiny_cell(
        cell).config["model"]


def row(by_rank, rank: int, expert: int, held):
    """(8,) logits: ``expert`` at ``rank`` (from 1), no OTHER held expert
    within the tie (they take the lowest ranks left)."""
    others = [e for e in range(8) if e != expert and e not in held] + [
        e for e in held if e != expert]
    ids = others[:rank - 1] + [expert] + others[rank - 1:]
    logits = np.zeros(8, np.float32)
    logits[ids] = by_rank
    return logits


@pytest.mark.parametrize("held_here", [True, False], ids=["held", "away"])
@pytest.mark.parametrize("name, rank, tie", CASES,
                         ids=[f"{n}-rank{r}" for n, r, _ in CASES])
@pytest.mark.parametrize("reference", sorted(CELLS))
def test_a_held_expert_near_the_edge_at_any_rank_is_left_out(
        reference, name, rank, tie, held_here):
    """Through each reference's own ``_route``: the position is left out
    when the expert near the edge is held here, whatever its rank, and stays
    in when that expert is held elsewhere (its output is not in this chip's
    sum either way) or nothing ties."""
    ref = spec.load_module("reference", reference)
    m = tiny_model(reference)
    held = list(m["experts_held"])
    assert m["n_experts_per_tok"] == 2 and HELD in held and AWAY not in held
    logits = row(RANKED[name], rank, HELD if held_here else AWAY, held)
    w, margin = ref._route(m, jnp.asarray(logits[None]),
                           jnp.eye(8, dtype=jnp.float32))
    assert sorted(np.flatnonzero(np.asarray(w[0])).tolist()) == sorted(
        np.argsort(logits)[-2:].tolist())
    margins = np.full((m["n_layers"], 3), np.inf)
    margins[1, 1] = float(margin[0])
    assert ref.left_out is near_tie.left_out
    assert near_tie.left_out(margins).tolist() == [
        False, tie and held_here, False]
    if tie and held_here:
        rms = float(np.sqrt(np.mean(np.square(logits))))
        near = {1: 0.004, 4: 0.004, 2: 0.002, 3: 0.002}[rank]
        np.testing.assert_allclose(float(margin[0]), near / rms, rtol=2e-3)


@pytest.mark.parametrize("layer", [0, 2])
def test_the_first_layer_takes_its_own_threshold(layer):
    """``left_out(margins, first=)``: a margin between ``FIRST_TIE`` and
    ``NEAR_TIE`` leaves a position out in any layer but the first of a model
    whose first router reads the token alone; under ``FIRST_TIE`` it is left
    out there too; without ``first`` every layer takes ``NEAR_TIE``."""
    first_tie = spec.load_module("reference", "cohere2_moe").FIRST_TIE
    assert first_tie < 0.05 < near_tie.NEAR_TIE
    margins = np.full((4, 3), np.inf)
    margins[layer, 1], margins[layer, 2] = 0.05, first_tie / 2
    assert near_tie.left_out(margins).tolist() == [False, True, True]
    assert near_tie.left_out(margins, first=first_tie).tolist() == [
        False, layer != 0, True]


def pair_margin(logits, top, k, held):
    """The rule as ``cohere2_moe.py`` had it until PR 42: a tie only between
    the k-th and the (k+1)-th logit, and only if one of the two is held."""
    ids = jax.lax.top_k(logits, k + 1)[1]
    edge_held = jnp.any(ids[:, k - 1:k + 1, None] == held, axis=(1, 2))
    rms = jnp.sqrt(jnp.mean(logits ** 2, axis=-1))
    return jnp.where(edge_held, (top[:, k - 1] - top[:, k]) / rms, jnp.inf)


def tie_at_another_rank(ref, m, token, loud=30.0):
    """Parameters whose FIRST router gives ``token`` the rag cell's finding of
    PR 42 in small (k = 2): the held expert first, two experts held elsewhere
    second and third, all within a thousandth of the row's rms logit, every
    other expert far below; and the "program": the same with the held
    expert's logit a five-hundredth of the rms lower, which is what rounding
    to bfloat16 does, so that it chooses the other way."""
    params = weights.make_params({"reference": "cohere2_moe", "model": m},
                                 42, np.float32)
    moe = dict(params["blocks"]["moe"])
    held = list(m["experts_held"])
    # routers that spread their scores (at the init's 0.02 every margin is a
    # near-tie) and first-layer experts loud enough that which one ran shows
    moe["router"] = 40.0 * moe["router"]
    moe["experts"] = dict(moe["experts"],
                          down=moe["experts"]["down"].at[0].multiply(loud))
    n0 = ref._layernorm(params["tok_emb"]["weight"][token],
                        params["blocks"]["norm1"]["scale"][0],
                        m["layernorm_eps"])
    logits = n0 @ moe["router"][0]
    away = [e for e in range(8) if e not in held]
    scale = float(jnp.max(jnp.abs(logits)))
    base = np.asarray([scale if e in (HELD, away[0], away[1])
                       else -scale * (1.0 + 0.1 * e) for e in range(8)],
                      np.float32)
    rms = float(np.sqrt(np.mean(base ** 2)))
    base[away[0]] -= 0.0005 * rms
    base[away[1]] -= 0.001 * rms
    moe["router"] = moe["router"].at[0].add(
        jnp.outer(n0, jnp.asarray(base) - logits) / jnp.dot(n0, n0))
    params = dict(params, blocks=dict(params["blocks"], moe=moe))
    nudged = dict(moe, router=moe["router"].at[0, :, HELD].add(
        -0.002 * rms * n0 / jnp.dot(n0, n0)))
    return params, dict(params, blocks=dict(params["blocks"], moe=nudged))


@pytest.mark.parametrize("repeats, N_P, N_S, draw, loud", [
    (1, 24, 12, 3, 30.0), (14, 36, 20, 2, 100.0)], ids=["once", "repeated"])
def test_the_rag_cells_finding_at_the_tiny_size(repeats, N_P, N_S, draw, loud,
                                                monkeypatch, capsys):
    """Seed 608847492's position (PERF.md section 6, PR 42) in small
    (``tie_at_another_rank``); the program serves its own greedy tokens.

    ONCE, at the position that chooses the first served token: read by the
    rule of the k-th and (k+1)-th logits the position is compared and the
    served token lies an expert's output under the reference's best; read by
    the rule at any rank it is left out, and the line printed says where the
    widest gap then stands. REPEATED, fourteen times in the prompt: each of its
    positions is left out, but what they carried into the keys and values
    moves positions with no tie of their own; read under both resolutions of
    the token's tie (the held expert on either side of the edge) the other
    one fits and is the one named."""
    ref = spec.load_module("reference", "cohere2_moe")
    # one expert of eight held (the cell holds a sixteenth): with the tiny
    # cell's three, over half of so few positions tie in some layer
    m = dict(tiny_model("cohere2_moe"), experts_held=[HELD])
    limit = spec.load_cell(CELLS["cohere2_moe"][0]).config["limits"]["serve"][
        "served_logit_widest_gap"]
    A, PAD = 7, 64
    params, program = tie_at_another_rank(ref, m, A, loud)
    rng = np.random.default_rng(draw)
    prompt = rng.integers(8, m["vocab_size"], N_P).astype(np.int32)
    prompt[-1] = A                  # the position that chooses served[0]
    prompt[rng.choice(N_P - 1, repeats - 1, replace=False)] = A
    step = jax.jit(lambda t: ref.hidden_fn(program, m, t)[0])
    seq = list(prompt)
    for _ in range(N_S):
        padded = np.zeros((PAD,), np.int32)
        padded[:len(seq)] = seq
        h = step(jnp.asarray(padded))
        nxt = h[len(seq) - 1] @ params["tok_emb"]["weight"].T
        seq.append(int(jnp.argmax(nxt.at[A].set(-jnp.inf))))  # A: prompt only
    served = np.asarray(seq[N_P:], np.int32)

    def read():
        capsys.readouterr()
        out = ref.served_token_gaps(params, m, [(prompt, served)], pad_to=PAD)
        line = next(l for l in capsys.readouterr().out.splitlines()
                    if l.startswith('{"reference_compared"'))
        return out, json.loads(line)["reference_compared"]

    with monkeypatch.context() as old:
        old.setattr(near_tie, "margin", pair_margin)
        out, line = read()
        assert out["widest_gap"] > limit
        assert line["widest_gap_at"]["served_index"] == 0
        assert line["widest_gap_at"]["position"] == N_P - 1
        # the old rule saw no tie there, nor a token to read both ways
        assert line["widest_gap_at"]["smallest_margin"] >= near_tie.NEAR_TIE
        assert line["repeated_first_layer_ties"] == []
    out, line = read()
    assert out["widest_gap"] < limit and out["compared_share"] >= 0.5
    assert line["widest_gap_at"]["served_index"] > 0
    assert line["widest_gap_at"]["smallest_margin"] >= near_tie.NEAR_TIE
    ties = line["repeated_first_layer_ties"]
    if repeats == 1:
        assert ties == []
        return
    assert ties[0]["tokens"] == [[A, repeats]] and ties[0]["swapped"] == [True]
    np.testing.assert_allclose(ties[0]["margins"], [0.001], rtol=0.05)
    own, other = ties[0]["widest_gap_by_resolution"]
    assert other == out["widest_gap"] < 1e-4 and own > 100 * max(other, 1e-6)
