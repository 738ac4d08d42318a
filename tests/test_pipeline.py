"""Pipeline parallelism parity on the 8-device CPU mesh: GPipe scheduling
is placement, not semantics — loss and gradients must match single-device."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from building_llm_from_scratch_tpu.configs import get_config
from building_llm_from_scratch_tpu.models import init_params
from building_llm_from_scratch_tpu.parallel.pipeline import (
    make_pp_loss_fn,
    make_pp_mesh,
    make_pp_train_step,
    stage_shardings,
)
from building_llm_from_scratch_tpu.training import (
    build_optimizer,
    init_train_state,
    make_train_step,
)
from building_llm_from_scratch_tpu.training.train_step import (
    cross_entropy_loss,
)



def _cfg(n_layers=4):
    return get_config("llama3_2", "1B", debug=True).replace(
        emb_dim=64, hidden_dim=128, vocab_size=512, context_length=64,
        n_layers=n_layers, drop_rate=0.0, dtype="fp32")


def _batch(cfg, bs=8, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.integers(0, cfg.vocab_size, (bs, cfg.context_length)).astype(
        np.int32)
    return {"inputs": x, "targets": np.roll(x, -1, 1).astype(np.int32),
            "weights": np.ones_like(x, np.float32)}


def _ref_loss(params, cfg, batch):
    from building_llm_from_scratch_tpu.models import forward

    logits = forward(params, cfg, jnp.asarray(batch["inputs"]))
    return cross_entropy_loss(logits, jnp.asarray(batch["targets"]),
                              jnp.asarray(batch["weights"]))


@pytest.mark.parametrize("stages,n_micro", [(2, 2), (4, 4), (8, 8)])
def test_pp_loss_matches_single_device(stages, n_micro):
    # stages < 8 leave devices for the data axis: (data=4,stage=2) etc.
    cfg = _cfg(n_layers=8)
    mesh = make_pp_mesh(stages)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    want = float(_ref_loss(params, cfg, batch))
    loss_fn = make_pp_loss_fn(cfg, mesh, n_micro)
    got = float(jax.jit(loss_fn)(params, batch))
    assert abs(got - want) < 1e-5, (got, want)


def test_pp_gradients_match_single_device():
    cfg = _cfg(n_layers=4)
    mesh = make_pp_mesh(4)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    gw = jax.grad(lambda p: _ref_loss(p, cfg, batch))(params)
    loss_fn = make_pp_loss_fn(cfg, mesh, n_micro=4)
    gp = jax.jit(jax.grad(loss_fn))(params, batch)
    flat_w = jax.tree_util.tree_leaves_with_path(gw)
    flat_p = jax.tree_util.tree_leaves(gp)
    for (path, a), b in zip(flat_w, flat_p):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), atol=1e-5, rtol=1e-4,
            err_msg=str(path))


def test_pp_training_matches_single_device():
    """3 pipelined train steps == 3 single-device steps."""
    cfg = _cfg(n_layers=8)
    mesh = make_pp_mesh(4)
    opt = build_optimizer(peak_lr=1e-3, warmup_steps=2, total_steps=10)
    batches = [_batch(cfg, seed=s) for s in range(3)]

    ref_state = init_train_state(init_params(cfg, jax.random.PRNGKey(0)),
                                 opt, jax.random.PRNGKey(0))
    ref_step = make_train_step(cfg, opt)
    ref_losses = []
    for b in batches:
        ref_state, m = ref_step(ref_state, b)
        ref_losses.append(float(m["loss"]))

    state = init_train_state(init_params(cfg, jax.random.PRNGKey(0)),
                             opt, jax.random.PRNGKey(0))
    state = jax.device_put(state, stage_shardings(state, mesh))
    step = make_pp_train_step(cfg, opt, mesh, n_micro=4)
    losses = []
    for b in batches:
        state, m = step(state, b)
        losses.append(float(m["loss"]))

    np.testing.assert_allclose(losses, ref_losses, rtol=2e-4, atol=2e-5)
    ref_w = np.asarray(ref_state["trainable"]["blocks"]["attn"]["wq"])
    got_w = np.asarray(jax.device_get(
        state["trainable"]["blocks"]["attn"]["wq"]))
    np.testing.assert_allclose(got_w, ref_w, rtol=2e-3, atol=2e-5)


def test_pp_tp_loss_and_gradients_match_single_device():
    """pp x tp (round-5 VERDICT #6): (data=2, stage=2, model=2) mesh —
    loss AND every RAW gradient leaf match single-device. No manual
    gradient corrections exist or are needed: jax's shard_map transpose
    differentiates through the Megatron psums exactly (the torch-world
    f/g conjugate pair is an autograd workaround jax does not require —
    an earlier draft that added it produced garbage gradients)."""
    from building_llm_from_scratch_tpu.parallel.pipeline import MODEL_AXIS

    cfg = _cfg(n_layers=4)
    mesh = make_pp_mesh(2, tp=2)
    assert mesh.shape == {"data": 2, "stage": 2, MODEL_AXIS: 2}
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)

    want = float(_ref_loss(params, cfg, batch))
    loss_fn = make_pp_loss_fn(cfg, mesh, n_micro=2)
    got = float(jax.jit(loss_fn)(params, batch))
    assert abs(got - want) < 1e-5, (got, want)

    # RAW gradient parity — adam-step parity alone would be blind to
    # per-leaf scale errors (m/sqrt(v) cancels constant factors)
    gw = jax.grad(lambda p: _ref_loss(p, cfg, batch))(params)
    gp = jax.jit(jax.grad(loss_fn))(params, batch)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(gw),
                            jax.tree_util.tree_leaves(gp)):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), atol=1e-5, rtol=1e-4,
            err_msg=str(path))

    # gradient parity through the full train step (which applies the
    # replicated-grad 1/tp correction)
    opt = build_optimizer(peak_lr=1e-3, warmup_steps=2, total_steps=10)
    ref_state = init_train_state(init_params(cfg, jax.random.PRNGKey(0)),
                                 opt, jax.random.PRNGKey(0))
    ref_step = make_train_step(cfg, opt)
    state = init_train_state(init_params(cfg, jax.random.PRNGKey(0)),
                             opt, jax.random.PRNGKey(0))
    state = jax.device_put(state, stage_shardings(state, mesh))
    step = make_pp_train_step(cfg, opt, mesh, n_micro=2)
    for seed in range(2):
        b = _batch(cfg, seed=seed)
        ref_state, mr = ref_step(ref_state, b)
        state, mp = step(state, b)
        np.testing.assert_allclose(float(mp["loss"]), float(mr["loss"]),
                                   rtol=2e-4, atol=2e-5)
    for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(ref_state["trainable"]),
            jax.tree_util.tree_leaves(state["trainable"])):
        np.testing.assert_allclose(np.asarray(jax.device_get(b)),
                                   np.asarray(a), rtol=2e-3, atol=2e-5,
                                   err_msg=str(path))


def test_pp_tp_state_shardings_split_model_axis():
    from jax.sharding import PartitionSpec as P

    cfg = _cfg(n_layers=4)
    mesh = make_pp_mesh(2, tp=2)
    params = init_params(cfg, jax.random.PRNGKey(0))
    sh = stage_shardings(params, mesh)
    assert sh["blocks"]["attn"]["wq"].spec == P("stage", None, "model")
    assert sh["blocks"]["attn"]["wo"].spec == P("stage", "model")
    assert sh["blocks"]["mlp"]["up"].spec == P("stage", None, "model")
    assert sh["blocks"]["mlp"]["down"].spec == P("stage", "model")
    assert sh["blocks"]["norm1"]["scale"].spec == P("stage")
    assert sh["tok_emb"]["weight"].spec == P()


def test_pp_tp_dropout_trains_gpt2():
    """GPT-2 (dropout 0.1, qkv biases) under pp x tp: runs and the loss is
    finite — attention masks fold the model-shard index, residual masks
    stay shard-identical (transformer._block)."""
    cfg = get_config("GPT2", "124M", debug=True).replace(
        emb_dim=64, hidden_dim=128, vocab_size=512, context_length=64,
        n_layers=4, dtype="fp32")
    mesh = make_pp_mesh(2, tp=2)
    opt = build_optimizer(total_steps=10)
    state = init_train_state(init_params(cfg, jax.random.PRNGKey(0)),
                             opt, jax.random.PRNGKey(0))
    state = jax.device_put(state, stage_shardings(state, mesh))
    step = make_pp_train_step(cfg, opt, mesh, n_micro=2)
    losses = []
    for seed in range(3):
        state, m = step(state, _batch(cfg, seed=seed))
        losses.append(float(m["loss"]))
    assert np.isfinite(losses).all(), losses


def test_pp_lora_matches_single_device():
    """pp + LoRA: adapters merge before the stage split; losses match the
    plain LoRA step and ONLY the adapters update."""
    from building_llm_from_scratch_tpu.models.lora import init_lora_params

    cfg = _cfg(n_layers=4)
    mesh = make_pp_mesh(4)
    opt = build_optimizer(peak_lr=1e-2, total_steps=10)
    params = init_params(cfg, jax.random.PRNGKey(0))
    # host snapshot: both states get their OWN device copies (the donated
    # steps delete their input buffers — the aliasing footgun of VERDICT r2)
    base_np = jax.tree_util.tree_map(np.asarray, params)
    fresh_base = lambda: jax.tree_util.tree_map(jnp.asarray, base_np)
    batches = [_batch(cfg, seed=s) for s in range(3)]

    lora = init_lora_params(cfg, params, jax.random.PRNGKey(1), rank=4)
    ref_state = init_train_state(lora, opt, jax.random.PRNGKey(0),
                                 frozen=fresh_base())
    ref_step = make_train_step(cfg, opt, lora_alpha=8, lora_rank=4)
    ref_losses = []
    for b in batches:
        ref_state, m = ref_step(ref_state, b)
        ref_losses.append(float(m["loss"]))

    lora2 = init_lora_params(cfg, params, jax.random.PRNGKey(1), rank=4)
    state = init_train_state(lora2, opt, jax.random.PRNGKey(0),
                             frozen=fresh_base())
    state = jax.device_put(state, stage_shardings(state, mesh))
    step = make_pp_train_step(cfg, opt, mesh, n_micro=4, lora_alpha=8,
                              lora_rank=4)
    losses = []
    for b in batches:
        state, m = step(state, b)
        losses.append(float(m["loss"]))

    np.testing.assert_allclose(losses, ref_losses, rtol=2e-4, atol=2e-5)
    # base stays frozen; adapters moved
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), b),
        state["frozen"], base_np)
    assert float(jnp.abs(
        state["trainable"]["blocks"]["attn"]["wq"]["B"]).max()) > 0


def test_pp_param_spec_for_weight_loading():
    """The weight-conversion path places each tensor via plan.param_spec:
    block leaves stage-shard their layer axis, non-divisible or non-block
    leaves replicate."""
    from jax.sharding import PartitionSpec as P

    from building_llm_from_scratch_tpu.parallel.pipeline import PipelinePlan

    plan = PipelinePlan(make_pp_mesh(2), n_micro=2)
    assert plan.param_spec(("blocks", "attn", "wq"), (4, 64, 64)) \
        == P("stage")
    assert plan.param_spec(("blocks", "norm1", "scale"), (3, 64)) == P()
    assert plan.param_spec(("tok_emb", "weight"), (512, 64)) == P()

    # end-to-end: a converted leaf placed with this spec spans the mesh
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    leaf = jnp.zeros((4, 8, 8))
    placed = jax.device_put(leaf, NamedSharding(
        plan.mesh, plan.param_spec(("blocks", "attn", "wq"), leaf.shape)))
    assert len(placed.sharding.device_set) == 8      # (data=4, stage=2)


def test_pp_rejects_bad_shapes():
    cfg = _cfg(n_layers=6)
    mesh = make_pp_mesh(4)
    with pytest.raises(ValueError, match="not divisible"):
        make_pp_loss_fn(cfg, mesh, n_micro=2)


# ---------------------------------------------------------------------------
# round-4 (pipeline v2): remat opt-in, dropout, drain-tick gating
# ---------------------------------------------------------------------------

def test_pp_gradients_match_with_and_without_remat():
    """--use_actv_ckpt only changes memory/recompute, never values: pp
    grads with remat on == off (and == single-device)."""
    cfg = _cfg(n_layers=4)
    mesh = make_pp_mesh(2)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, bs=16)   # (data=4, stage=2): Bm must divide 4

    def grads_for(c):
        loss_fn = make_pp_loss_fn(c, mesh, n_micro=4)
        return jax.jit(jax.grad(lambda p: loss_fn(p, batch)))(params)

    g_plain = grads_for(cfg)
    g_remat = grads_for(cfg.replace(use_actv_ckpt=True))
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6),
        g_plain, g_remat)


def test_pp_dropout_trains_gpt2():
    """GPT-2 (dropout 0.1) pipelines since v2: per-(micro,data,stage,layer)
    folded masks; losses finite and decreasing on a repeated batch."""
    cfg = get_config("GPT2", "124M", debug=True).replace(
        emb_dim=64, hidden_dim=128, vocab_size=256, context_length=64,
        n_heads=4, n_layers=4, dtype="fp32")
    assert cfg.drop_rate > 0.0
    mesh = make_pp_mesh(2)
    opt = build_optimizer(total_steps=12)
    state = init_train_state(init_params(cfg, jax.random.PRNGKey(0)), opt,
                             jax.random.PRNGKey(1))
    step = make_pp_train_step(cfg, opt, mesh, n_micro=4)
    batch = _batch(cfg, bs=16)
    losses = []
    for _ in range(10):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_pp_dropout_deterministic_per_step_rng():
    """Same state (rng, step) -> identical pp loss; different step ->
    different masks."""
    cfg = _cfg(n_layers=4).replace(drop_rate=0.3)
    mesh = make_pp_mesh(2)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, bs=16)
    loss_fn = jax.jit(make_pp_loss_fn(cfg, mesh, n_micro=4))
    rng = jax.random.PRNGKey(5)
    a = float(loss_fn(params, batch, rng))
    b = float(loss_fn(params, batch, rng))
    assert a == b
    c = float(loss_fn(params, batch, jax.random.PRNGKey(6)))
    assert a != c
    # rng=None -> deterministic path, matches the no-dropout reference
    want = float(_ref_loss(params, cfg.replace(drop_rate=0.0), batch))
    got = float(loss_fn(params, batch))
    assert abs(got - want) < 1e-5
