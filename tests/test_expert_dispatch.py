"""The grouped dispatch of a call's rows to the held experts
(``models/moe.py`` ``_grouped`` over ``ops/grouped_experts.py``, its kernel in
interpret mode on the CPU) against the per-expert loop of conditionals as its
oracle, the rule that chooses between them, and what the engine reports.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from building_llm_from_scratch_tpu.configs import get_config
from building_llm_from_scratch_tpu.models import moe
from building_llm_from_scratch_tpu.models import transformer as tf
from building_llm_from_scratch_tpu.obs.metrics import get_metrics
from building_llm_from_scratch_tpu.obs.schema import validate_event
from building_llm_from_scratch_tpu.ops import grouped_experts as ge
from building_llm_from_scratch_tpu.serving import (
    DecodeEngine,
    KVCachePolicy,
    SamplingParams,
)
from building_llm_from_scratch_tpu.serving import engine as engine_mod

D = 128
#: the two cells' expert layers at a tiny model width: (routed experts, held
#: here, width): command-a-plus 128 / 8 / 4096, solar-open2 320 / 20 / 1280
SHAPES = {"F4096_H8": (128, 8, 4096), "F1280_H20": (320, 20, 1280)}
ROUTERS = ("uniform", "held_only", "none_held", "one_expert", "part_filled")


def layer_cfg(shape, **kw):
    E, H, F = SHAPES[shape]
    # held experts that are neither the first ids nor in order: the counts
    # and the weights follow ``held_experts``' order, not the ids'
    held = tuple(range(E - 1, E - 1 - 3 * H, -3))
    return get_config("command_a_plus", "218B", debug=True,
                      dtype="fp32").replace(
        emb_dim=D, hidden_dim=F, n_routed_experts=E, n_experts_per_tok=8,
        experts_held=held, **kw)


def experts_of(cfg, key, layers=2, dtype=jnp.float32):
    H, F = len(cfg.held_experts), cfg.hidden_dim
    kg, ku, kd = jax.random.split(key, 3)
    draw = lambda k, shape: (jax.random.normal(k, (layers, H) + shape)
                             / np.sqrt(shape[0])).astype(dtype)
    return {"gate": draw(kg, (D, F)), "up": draw(ku, (D, F)),
            "down": draw(kd, (F, D)), "layer": layers - 1}


def routing(cfg, router, N, key):
    """-> (ids (N, k), weights (N, k), live (N,) or None) of a router that
    is uniform over all experts, or made to send every row to held experts
    only (the buffer's static worst case), to none, or to ONE held expert
    and otherwise away; ``part_filled``: uniform, a chunk's tail dead."""
    held = jnp.asarray(cfg.held_experts)
    logits = jax.random.normal(key, (N, cfg.n_routed_experts))
    if router == "held_only":
        logits = logits.at[:, held].add(100.0)
    elif router == "none_held":
        logits = logits.at[:, held].add(-100.0)
    elif router == "one_expert":
        logits = logits.at[:, held].add(-100.0).at[:, held[3]].add(200.0)
    top, ids = jax.lax.top_k(jax.nn.sigmoid(logits), cfg.n_experts_per_tok)
    live = jnp.arange(N) < N - 45 if router == "part_filled" else None
    return ids, top / jnp.sum(top, axis=-1, keepdims=True), live


@pytest.fixture
def on_tpu(monkeypatch):
    """The rule told it is on a TPU (the kernel interprets on the CPU it
    really is on), and a weight-tile budget that walks an expert's width in
    tiles of 256 at this model width, as the real one does at the real."""
    rule = functools.partial(moe.expert_dispatch_path, platform="tpu")
    monkeypatch.setattr(moe, "expert_dispatch_path", rule)
    monkeypatch.setattr(engine_mod, "expert_dispatch_path", rule)
    monkeypatch.setattr(ge, "_WEIGHT_TILES_BYTES", 6 * D * 256 * 4)
    return rule


@pytest.mark.parametrize("router", ROUTERS)
@pytest.mark.parametrize("shape", SHAPES)
def test_grouped_form_equals_the_per_expert_form(on_tpu, shape, router):
    """Sum and counts of the grouped form against the oracle's, 300 rows
    top-8: no row dropped when every row chooses held experts only (N x
    min(k, H) assignments, the whole buffer, ten row tiles the last
    part-filled), zeros and counts of 0 when none does, one expert taking
    every row (two row tiles of one group), dead rows reading no expert and
    counting for none.
    The experts that got no row are NaN where the router allows it: nothing
    of them reaches the sum."""
    cfg = layer_cfg(shape)
    H, N = len(cfg.held_experts), 300
    assert ge._width_tile(D, cfg.hidden_dim, 4) == 256
    p = experts_of(cfg, jax.random.PRNGKey(1))
    x = jax.random.normal(jax.random.PRNGKey(2), (N, D))
    ids, weights, live = routing(cfg, router, N, jax.random.PRNGKey(3))
    assert on_tpu(cfg, N, jnp.float32) == "grouped"
    want, want_counts = jax.jit(
        lambda p: moe._per_expert(cfg, p, x, ids, weights, live))(p)
    if router in ("none_held", "one_expert"):
        idle = jnp.arange(H) != (3 if router == "one_expert" else -1)
        p = {k: v if k == "layer" else jnp.where(
            idle[None, :, None, None], jnp.nan, v) for k, v in p.items()}
    got, counts = jax.jit(
        lambda p: moe._routed(cfg, p, x, ids, weights, live))(p)
    assert counts.dtype == want_counts.dtype == jnp.int32
    assert counts.tolist() == want_counts.tolist()
    n_live = N if live is None else int(live.sum())
    assert int(counts.sum()) == {
        "held_only": N * min(8, H), "none_held": 0, "one_expert": N,
    }.get(router, int(counts.sum()))
    assert 0 <= int(counts.max()) <= n_live
    assert bool(jnp.isfinite(got).all())
    if live is not None:
        assert float(jnp.abs(got[n_live:]).max()) == 0.0
    assert float(jnp.abs(got - want).max()) < 2e-5 * max(
        1.0, float(jnp.abs(want).max()))


def test_grouped_form_in_bf16_and_one_layer_without_a_stack(on_tpu):
    """bfloat16 operands (the cells' precision: the kernel's SiLU and gate
    in float32 before its one cast, the oracle's in bfloat16) and a layer's
    leaves handed over alone, as the training forward's scan does."""
    cfg = layer_cfg("F1280_H20")
    p = experts_of(cfg, jax.random.PRNGKey(1), dtype=jnp.bfloat16)
    one = {k: v[1] for k, v in p.items() if k != "layer"}
    x = jax.random.normal(jax.random.PRNGKey(2), (256, D), jnp.bfloat16)
    ids, weights, _ = routing(cfg, "uniform", 256, jax.random.PRNGKey(3))
    want, want_counts = moe._per_expert(cfg, p, x, ids, weights, None)
    for leaves in (p, one):
        got, counts = jax.jit(
            lambda p: moe._routed(cfg, p, x, ids, weights, None))(leaves)
        assert counts.tolist() == want_counts.tolist()
        assert float(jnp.abs(got - want).max()) < 2e-2 * float(
            jnp.abs(want).max())


def test_gradient_of_the_grouped_form_is_the_per_expert_forms(on_tpu):
    cfg = layer_cfg("F1280_H20")
    p = experts_of(cfg, jax.random.PRNGKey(1))
    leaves = {k: v for k, v in p.items() if k != "layer"}
    x = jax.random.normal(jax.random.PRNGKey(2), (160, D))
    ids, weights, _ = routing(cfg, "uniform", 160, jax.random.PRNGKey(3))
    loss = lambda form: lambda leaves, x, w: jnp.sum(form(
        cfg, dict(leaves, layer=1), x, ids, w, None)[0] ** 2)
    got = jax.jit(jax.grad(loss(moe._routed), (0, 1, 2)))(leaves, x, weights)
    want = jax.grad(loss(moe._per_expert), (0, 1, 2))(leaves, x, weights)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert float(jnp.abs(a - b).max()) < 1e-4 * max(
            1.0, float(jnp.abs(b).max()))


@pytest.mark.parametrize("sizes", [
    [0, 0, 0], [5, 0, 7], [256, 256, 0], [255, 2, 255], [0, 600, 1],
    [1, 1, 1], [768, 0, 0]])
def test_visits_walk_every_tile_a_group_reaches_and_no_other(sizes):
    """The grid's walk over a buffer of three row tiles: a group visits the
    tiles that hold its rows, in order, a tile is revisited only by
    consecutive visits, an empty group visits none, and the walk ends at
    the last live row."""
    T = ge.ROW_TILE
    offsets, group, tile, n = ge._visits(jnp.asarray(sizes, jnp.int32), 3 * T)
    n = int(n)
    assert offsets.tolist() == [0] + np.cumsum(sizes).tolist()
    assert group.shape == tile.shape == (3 + 3 - 1,)
    want = [(g, t) for g in range(3) if sizes[g]
            for t in range(int(offsets[g]) // T,
                           (int(offsets[g + 1]) - 1) // T + 1)]
    assert list(zip(group[:n].tolist(), tile[:n].tolist())) == want
    assert tile[:n].tolist() == sorted(tile[:n].tolist())


@pytest.mark.parametrize("rows,platform,dtype,want", [
    (512, "tpu", jnp.bfloat16, "grouped"),
    (512, "tpu", jnp.float32, "grouped"),
    (129, "tpu", jnp.bfloat16, "grouped"),
    (48, "tpu", jnp.bfloat16, "per_expert"),        # a decode tick
    (128, "tpu", jnp.bfloat16, "per_expert"),       # one row block
    (512, "cpu", jnp.bfloat16, "per_expert"),
    (512, None, jnp.bfloat16, "per_expert"),        # asks: this is a CPU
    (512, "tpu", jnp.int8, "per_expert"),
    (8192, "tpu", jnp.bfloat16, "per_expert"),      # past the buffer's cap
])
def test_rule_answers_by_rows_platform_and_dtype(rows, platform, dtype, want):
    for model, size, held in (("command_a_plus", "218B", 8),
                              ("solar_open2", "250B", 20)):
        cfg = get_config(model, size, dtype="bf16",
                         target_context_length=None).replace(
            experts_held=tuple(range(held)))
        assert moe.expert_dispatch_path(cfg, rows, dtype, platform) == want


def test_rule_refuses_widths_that_are_not_whole_lane_tiles():
    cfg = get_config("command_a_plus", "218B", debug=True, dtype="bf16")
    assert cfg.emb_dim % 128
    assert moe.expert_dispatch_path(cfg, 512, jnp.bfloat16,
                                    "tpu") == "per_expert"
    wide = cfg.replace(emb_dim=128, hidden_dim=320)
    assert moe.expert_dispatch_path(wide, 512, jnp.bfloat16,
                                    "tpu") == "per_expert"
    assert moe.expert_dispatch_path(wide.replace(hidden_dim=384), 512,
                                    jnp.bfloat16, "tpu") == "grouped"


def test_engine_names_its_dispatch_and_counts_the_same_rows(monkeypatch):
    """An engine whose chunk program takes the grouped form (chunks of 16
    over row blocks of 8) serves the tokens and books the ``expert_rows``
    and ``experts_touched`` of one that takes the per-expert form, tick for
    tick; both name their forms in ``stats()``, ``/healthz`` and the
    warm-up event; a dense model's engine says None."""
    monkeypatch.setattr(moe, "ROW_BLOCK", 8)
    cfg = get_config("command_a_plus", "218B", debug=True,
                     dtype="fp32").replace(
        emb_dim=128, hidden_dim=256, sliding_window=16)
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    params["blocks"]["moe"]["router"] = 40.0 * params["blocks"]["moe"][
        "router"]
    prompts = [np.asarray(jax.random.randint(
        jax.random.PRNGKey(s), (n,), 0, cfg.vocab_size))
        for s, n in ((1, 37), (2, 9), (3, 21))]

    def serve():
        eng = DecodeEngine(cfg, params, None, n_slots=3,
                           kv_policy=KVCachePolicy(prefill_chunk=16))
        eng.warmup()
        before = len(get_metrics().recent("tick"))
        reqs = [eng.submit(p, SamplingParams(
            max_new_tokens=5, temperature=0.0, ignore_eos=True))
            for p in prompts]
        eng.run_until_idle()
        ticks = [(t.get("chunks", 0), t["expert_rows"], t["experts_touched"])
                 for t in get_metrics().recent("tick")[before:]
                 if "expert_rows" in t]
        assert eng.stats()["expert_dispatch"] == eng.expert_dispatch
        assert eng.healthz_payload()["expert_dispatch"] == eng.expert_dispatch
        return eng.expert_dispatch, [r.output_ids for r in reqs], ticks

    want = serve()
    rule = functools.partial(moe.expert_dispatch_path, platform="tpu")
    monkeypatch.setattr(moe, "expert_dispatch_path", rule)
    monkeypatch.setattr(engine_mod, "expert_dispatch_path", rule)
    got = serve()
    assert want[0] == {"tick": "per_expert", "chunk": "per_expert"}
    assert got[0] == {"tick": "per_expert", "chunk": "grouped"}
    assert validate_event("serve_warmup", {"expert_dispatch": got[0]}) == []
    assert got[1] == want[1]
    assert got[2] == want[2] and any(chunks for chunks, _, _ in got[2])
    dense = get_config("GPT2", "124M", debug=True)
    plain = DecodeEngine(dense, tf.init_params(dense, jax.random.PRNGKey(0)),
                         None, n_slots=2)
    assert plain.expert_dispatch is None
    assert plain.stats()["expert_dispatch"] is None
