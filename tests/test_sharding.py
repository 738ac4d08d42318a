"""Sharding-tier tests on the virtual 8-device CPU mesh (conftest.py).

The load-bearing test: dp / fsdp / zero1 / tp all produce the SAME losses
as single-device training — the strategies are placement, not semantics.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from building_llm_from_scratch_tpu.configs import get_config
from building_llm_from_scratch_tpu.models import forward, init_params
from building_llm_from_scratch_tpu.parallel import (
    MeshPlan,
    build_mesh_plan,
    gather_full,
    make_mesh,
)
from building_llm_from_scratch_tpu.training import (
    build_optimizer,
    init_train_state,
    make_train_step,
)


def tiny_cfg():
    # emb 64 / hidden 128 so every big tensor divides by 8
    return get_config("GPT2", "124M", debug=True).replace(
        emb_dim=64, hidden_dim=128, vocab_size=50264, drop_rate=0.0)


def make_batch(cfg, bs=8, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.integers(0, cfg.vocab_size, (bs, cfg.context_length)).astype(np.int32)
    return {"inputs": x, "targets": np.roll(x, -1, 1).astype(np.int32),
            "weights": np.ones_like(x, np.float32)}


def test_make_mesh_shapes():
    mesh = make_mesh()
    assert mesh.shape == {"data": 8, "seq": 1, "model": 1}
    mesh2 = make_mesh(data=-1, model=2)
    assert mesh2.shape == {"data": 4, "seq": 1, "model": 2}
    with pytest.raises(ValueError):
        make_mesh(data=3, model=3)


def test_fsdp_specs_shard_large_params_only():
    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    plan = build_mesh_plan("fsdp")
    shardings = plan.params_shardings(params)
    # big stacked weights shard a non-layer axis
    wq = shardings["blocks"]["attn"]["wq"]
    assert wq.spec != P() and wq.spec[0] is None
    # embeddings shard
    assert shardings["tok_emb"]["weight"].spec != P()
    # tiny norm scales replicate
    assert shardings["blocks"]["norm1"]["scale"].spec == P()


def test_dp_specs_replicate_params():
    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    plan = build_mesh_plan("dp")
    shardings = plan.params_shardings(params)
    assert all(s.spec == P() for s in jax.tree_util.tree_leaves(
        shardings, is_leaf=lambda x: hasattr(x, "spec")))


def test_zero1_shards_opt_state_not_params():
    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = build_optimizer(total_steps=10)
    state = init_train_state(params, opt, jax.random.PRNGKey(0))
    plan = build_mesh_plan("zero1")
    shardings = plan.state_shardings(state)
    # params replicated
    assert shardings["trainable"]["blocks"]["attn"]["wq"].spec == P()
    # adam moments sharded
    flat = jax.tree_util.tree_flatten_with_path(shardings["opt_state"])[0]
    mu_specs = [s.spec for p, s in flat
                if any(getattr(e, "name", "") == "mu" for e in p)
                and hasattr(s, "spec")]
    assert any(spec != P() for spec in mu_specs)


def test_fsdp_actually_reduces_per_device_bytes():
    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    plan = build_mesh_plan("fsdp")
    sharded = plan.shard_params(params)
    w = sharded["blocks"]["attn"]["wq"]
    shard_elems = w.addressable_shards[0].data.size
    assert shard_elems == w.size // 8


def test_shard_batch_partitions_data_axis():
    cfg = tiny_cfg()
    plan = build_mesh_plan("fsdp")
    batch = plan.shard_batch(make_batch(cfg))
    x = batch["inputs"]
    assert x.sharding.spec[0] == "data"
    assert x.addressable_shards[0].data.shape[0] == 1  # 8 rows / 8 devices


@pytest.mark.parametrize("mode,tp", [("dp", 1), ("fsdp", 1), ("zero1", 1),
                                     ("tp", 2), ("tp_fsdp", 2)])
def test_sharded_training_matches_single_device(mode, tp):
    """3 steps under every strategy == 3 single-device steps."""
    cfg = tiny_cfg()
    opt = build_optimizer(peak_lr=1e-3, warmup_steps=2, total_steps=10)
    batches = [make_batch(cfg, seed=s) for s in range(3)]

    # single-device baseline (fresh params; the step donates its state)
    ref_state = init_train_state(init_params(cfg, jax.random.PRNGKey(0)),
                                 opt, jax.random.PRNGKey(0))
    step = make_train_step(cfg, opt)
    ref_losses = []
    for b in batches:
        ref_state, m = step(ref_state, b)
        ref_losses.append(float(m["loss"]))

    plan = build_mesh_plan(mode, tp=tp)
    state = init_train_state(init_params(cfg, jax.random.PRNGKey(0)),
                             opt, jax.random.PRNGKey(0))
    state = plan.shard_state(state)
    sharded_step = make_train_step(cfg, opt)
    losses = []
    for b in batches:
        state, m = sharded_step(state, plan.shard_batch(b))
        losses.append(float(m["loss"]))

    np.testing.assert_allclose(losses, ref_losses, rtol=2e-4, atol=2e-5)
    # final params agree too
    ref_w = np.asarray(ref_state["trainable"]["blocks"]["attn"]["wq"])
    got_w = gather_full(state)["trainable"]["blocks"]["attn"]["wq"]
    np.testing.assert_allclose(got_w, ref_w, rtol=2e-3, atol=2e-5)


def test_tp_forward_parity():
    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.asarray(make_batch(cfg, bs=4)["inputs"])
    ref = forward(params, cfg, tokens)
    plan = build_mesh_plan("tp", tp=2)
    sharded = plan.shard_params(params)
    got = jax.jit(lambda p, t: forward(p, cfg, t))(sharded, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_tp_spec_placements():
    """TP rules land on the documented axes: column-parallel QKV/up,
    row-parallel wo/down, vocab-parallel embedding and head."""
    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    plan = build_mesh_plan("tp", tp=2)
    s = plan.params_shardings(params)
    assert s["blocks"]["attn"]["wq"].spec == P(None, None, "model")
    assert s["blocks"]["attn"]["wo"].spec == P(None, "model", None)
    assert s["blocks"]["mlp"]["up"].spec == P(None, None, "model")
    assert s["blocks"]["mlp"]["down"].spec == P(None, "model", None)
    assert s["tok_emb"]["weight"].spec == P("model", None)   # vocab-parallel
    assert s["head"]["weight"].spec == P(None, "model")      # vocab-parallel


def test_invalid_shard_mode_rejected():
    with pytest.raises(ValueError):
        MeshPlan(mesh=make_mesh(), shard_mode="ddp")


def test_shard_state_is_donation_safe():
    """Round-2 VERDICT weak #1: shard_state must return fresh buffers even
    when device_put would alias — donating its result must not delete arrays
    the caller still holds."""
    cfg = tiny_cfg()
    opt = build_optimizer(total_steps=10)
    params = init_params(cfg, jax.random.PRNGKey(0))
    plan = build_mesh_plan("dp")
    s1 = init_train_state(params, opt, jax.random.PRNGKey(0))
    s2 = plan.shard_state(init_train_state(params, opt, jax.random.PRNGKey(0)))
    step = make_train_step(cfg, opt)           # donates its state argument
    s1, _ = step(s1, make_batch(cfg))          # deletes s1's input buffers
    # s2 shares `params` with the donated s1; it must still be fully alive
    for leaf in jax.tree_util.tree_leaves(s2):
        assert not (hasattr(leaf, "is_deleted") and leaf.is_deleted())
    assert np.isfinite(float(s2["trainable"]["tok_emb"]["weight"].sum()))


def test_zero1_trainer_keeps_opt_state_sharded():
    """Round-2 ADVICE medium #1: zero1 + bf16_hybrid must NOT route through
    the replicated-spec shard_map step; the GSPMD step honors opt_spec, so
    adam moments stay sharded after a real step."""
    from building_llm_from_scratch_tpu.training import get_policy
    from building_llm_from_scratch_tpu.training.trainer import Trainer
    from building_llm_from_scratch_tpu.data import ByteTokenizer, PretrainLoader

    cfg = tiny_cfg().replace(vocab_size=300)
    plan = build_mesh_plan("zero1")
    params = init_params(cfg, jax.random.PRNGKey(0))
    tok = ByteTokenizer()
    loader = PretrainLoader(tok, batch_size=8, max_length=cfg.context_length)
    tr = Trainer(cfg, params, tok, loader, policy=get_policy("bf16_hybrid"),
                 plan=plan, eval_freq=10_000, print_sample_iter=10_000,
                 save_ckpt_freq=10_000)
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        path = f"{d}/c.txt"
        open(path, "w").write("sphinx of black quartz judge my vow. " * 100)
        tr.train_model([path], n_epochs=1)
    assert tr.global_step > 0
    flat = jax.tree_util.tree_flatten_with_path(tr.state["opt_state"])[0]
    mu = [(p, leaf) for p, leaf in flat
          if any(getattr(e, "name", "") == "mu" for e in p)
          and hasattr(leaf, "sharding") and np.ndim(leaf) >= 2]
    assert mu, "no adam mu leaves found"
    # at least the big mu leaves remain sharded over the data axis
    assert any(leaf.sharding.spec != P() for _, leaf in mu), (
        "zero1 optimizer state was silently replicated")


def test_mesh_kernel_matches_unsharded_forward_and_grads():
    """``mesh_kernel`` (what every pallas kernel calls itself through) is
    placement, not semantics: under a (data, seq, model) mesh — with
    activations replicated over model and heads sharded over it — a
    stand-in kernel gives the values AND gradients of the bare call. The
    replicated axis is the sharp edge: its cotangents must not be counted
    once per model shard."""
    from building_llm_from_scratch_tpu.parallel.collectives import (
        mesh_kernel,
        trace_under_mesh,
    )

    mesh = make_mesh(data=2, seq=2, model=2)
    act = ("data", "seq", None)
    heads = ("data", None, "model", None)
    shards = []

    def op(x, h, w):
        def residual(shard, x, h):
            shards.append(shard)
            return x + jnp.tanh(h) * 2.0

        y = mesh_kernel(residual, (x, h), (act, act), act)
        q = (y @ w).reshape(*y.shape[:2], 4, 8)
        z = mesh_kernel(lambda _, q: jnp.sin(q), (q,), (heads,), heads)
        return (z ** 2).sum()

    rng = np.random.default_rng(0)
    x, h = (jnp.asarray(rng.normal(size=(4, 8, 16)), jnp.float32)
            for _ in range(2))
    w = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)
    want = jax.value_and_grad(op, (0, 1, 2))(x, h, w)     # no mesh: bare
    assert int(shards[0]) == 0
    rows = NamedSharding(mesh, P("data", "seq"))
    got = jax.jit(trace_under_mesh(jax.value_and_grad(op, (0, 1, 2)), mesh))(
        jax.device_put(x, rows), jax.device_put(h, rows),
        jax.device_put(w, NamedSharding(mesh, P(None, "model"))))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-4)
