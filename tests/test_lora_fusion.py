"""Fused multi-LoRA training (training/lora_fusion.py): parity with the
solo trainer, zero-recompile job churn, co-residency fault isolation, and
the per-job export → hot-deploy hop."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from building_llm_from_scratch_tpu.configs import get_config
from building_llm_from_scratch_tpu.models import init_params
from building_llm_from_scratch_tpu.models.lora import (
    init_lora_params,
    load_adapter,
)
from building_llm_from_scratch_tpu.models.transformer import forward
from building_llm_from_scratch_tpu.obs.metrics import configure_metrics
from building_llm_from_scratch_tpu.training import (
    build_optimizer,
    init_train_state,
    make_train_step,
    warmup_cosine_schedule,
)
from building_llm_from_scratch_tpu.training.lora_fusion import (
    FinetuneJob,
    FusedLoRATrainer,
    fleet_lr_schedule,
    init_fleet_state,
    make_fused_train_step,
    stack_fleet_batch,
)

RANK, ALPHA = 4, 8.0


def _copy(tree):
    return jax.tree_util.tree_map(lambda x: x.copy(), tree)


@pytest.fixture(scope="module")
def cfg():
    # drop_rate=0: the parity claims below are about the math, not about
    # reproducing dropout masks across different batch shapes
    return get_config("GPT2", "124M", dtype="fp32",
                      debug=True).replace(drop_rate=0.0)


@pytest.fixture(scope="module")
def base_params(cfg):
    return init_params(cfg, jax.random.PRNGKey(0))


def _job_arrays(cfg, rows, seed, mask_frac=3):
    rng = np.random.default_rng(seed)
    T = cfg.context_length
    w = np.ones((rows, T), np.float32)
    w[:, : T // mask_frac] = 0.0
    return {
        "inputs": rng.integers(0, cfg.vocab_size,
                               (rows, T)).astype(np.int32),
        "targets": rng.integers(0, cfg.vocab_size,
                                (rows, T)).astype(np.int32),
        "weights": w,
    }


def _fused_batch(jobs, rows, k, horizon):
    return stack_fleet_batch(
        [{kk: jb[kk] for kk in ("inputs", "targets", "weights")}
         for jb in jobs],
        capacity=k, scaling=ALPHA / RANK, horizon=horizon)


def _set_row(pool, j, tree):
    return jax.tree_util.tree_map(lambda p, l: p.at[j].set(l), pool, tree)


def _row(tree, j):
    return jax.tree_util.tree_map(lambda a: np.asarray(a[j]), tree)


# ---------------------------------------------------------------------------
# Parity
# ---------------------------------------------------------------------------

def test_k1_fused_matches_unmerged_reference(cfg, base_params):
    """One job through the fused step IS the unmerged single-adapter
    forward with a gather: the per-job loss is bit-identical to the
    reference, and the gradients agree to float32 epsilon (the reference
    contracts dA over B·T in one matmul; the gather's transpose
    scatter-adds per-row — a different reduction tree, last-ulp only)."""
    lora = init_lora_params(cfg, base_params, jax.random.PRNGKey(1),
                            rank=RANK)
    lora = jax.tree_util.tree_map(lambda a: a + 0.01, lora)  # B nonzero
    rows = 3
    jb = _job_arrays(cfg, rows, seed=0, mask_frac=2)

    def ref_loss(l):
        logits = forward(base_params, cfg, jb["inputs"], lora=l,
                         lora_scaling=ALPHA / RANK)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        ll = jnp.take_along_axis(
            logp, jnp.asarray(jb["targets"])[..., None], axis=-1)[..., 0]
        w = jnp.asarray(jb["weights"])
        return (-jnp.sum(jnp.where(w > 0, ll * w, 0.0))
                / jnp.maximum(w.sum(), 1.0))

    ref_l, ref_g = jax.value_and_grad(ref_loss)(lora)

    state = init_fleet_state(cfg, base_params, capacity=1, rank=RANK,
                             rng=jax.random.PRNGKey(123))
    state["trainable"] = _set_row(state["trainable"], 0, lora)
    step = make_fused_train_step(cfg, capacity=1, jit=False)
    batch = _fused_batch([jb], rows, 1, horizon=10)

    def fused_loss(pool):
        adapter = {"pool": pool,
                   "scaling": jnp.asarray(batch["scaling"]),
                   "ids": jnp.asarray(batch["job_ids"])}
        logits = forward(base_params, cfg, jb["inputs"], adapter=adapter)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        ll = jnp.take_along_axis(
            logp, jnp.asarray(jb["targets"])[..., None], axis=-1)[..., 0]
        w = jnp.asarray(jb["weights"])
        return (-jnp.sum(jnp.where(w > 0, ll * w, 0.0))
                / jnp.maximum(w.sum(), 1.0))

    f_l, f_g = jax.value_and_grad(fused_loss)(state["trainable"])
    # loss: BIT-for-bit
    assert float(f_l) == float(ref_l)
    # the step's own per-job loss metric reports the same value
    _, metrics = step(state, batch)
    assert float(metrics["loss"][0]) == float(ref_l)
    # grads: same math, epsilon-level reduction-order drift only (pinned)
    ref_leaves = jax.tree_util.tree_leaves(jax.device_get(ref_g))
    fused_leaves = [np.asarray(l[0]) for l in
                    jax.tree_util.tree_leaves(jax.device_get(f_g))]
    for a, b in zip(ref_leaves, fused_leaves):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=3e-7, rtol=0)


def test_k3_fused_tracks_each_solo_run(cfg, base_params):
    """Three jobs co-trained fused land within float-epsilon of their own
    solo ``--use_lora`` runs (the merged-weights optax trainer): per-job
    losses equal at 1e-5 rtol and adapter params within 5e-6 after 6
    steps — fusion changes the schedule of the computation, not the
    training each tenant gets."""
    k, rows, n, horizon = 3, 2, 6, 8
    jobs = []
    for j in range(k):
        jb = _job_arrays(cfg, rows, seed=j)
        jb["lora"] = init_lora_params(cfg, base_params,
                                      jax.random.PRNGKey(10 + j),
                                      rank=RANK)
        jobs.append(jb)

    solo_final = []
    for j in range(k):
        sched = warmup_cosine_schedule(5e-4, 1e-5, 1e-6, 2, horizon)
        opt = build_optimizer(total_steps=horizon, warmup_steps=2,
                              schedule=sched)
        state = init_train_state(_copy(jobs[j]["lora"]), opt,
                                 jax.random.PRNGKey(123),
                                 frozen=_copy(base_params))
        step = make_train_step(cfg, opt, lora_rank=RANK, lora_alpha=ALPHA,
                               lr_schedule=sched)
        for _ in range(n):
            state, m = step(state, {kk: jobs[j][kk] for kk in
                                    ("inputs", "targets", "weights")})
        solo_final.append((float(jax.device_get(m["loss"])),
                           jax.device_get(state["trainable"])))

    fstate = init_fleet_state(cfg, base_params, capacity=k, rank=RANK,
                              rng=jax.random.PRNGKey(123))
    for j in range(k):
        fstate["trainable"] = _set_row(fstate["trainable"], j,
                                       _copy(jobs[j]["lora"]))
    fstep = make_fused_train_step(cfg, capacity=k, warmup_steps=2)
    batch = _fused_batch(jobs, rows, k, horizon)
    for _ in range(n):
        fstate, fm = fstep(fstate, batch)
    floss = jax.device_get(fm["loss"])
    ftrain = jax.device_get(fstate["trainable"])
    for j in range(k):
        solo_loss, solo_params = solo_final[j]
        assert floss[j] == pytest.approx(solo_loss, rel=1e-5)
        for a, b in zip(jax.tree_util.tree_leaves(solo_params),
                        jax.tree_util.tree_leaves(_row(ftrain, j))):
            np.testing.assert_allclose(np.asarray(a), b, atol=5e-6,
                                       rtol=0)


def test_per_job_lr_schedule_matches_solo_schedule(cfg):
    """The traced-horizon vectorized schedule reproduces
    ``warmup_cosine_schedule`` elementwise — two jobs with different
    horizons each decay over their OWN length inside one program."""
    horizons = np.asarray([7, 23], np.int32)
    for count in range(10):
        got = fleet_lr_schedule(
            jnp.full((2,), count, jnp.int32), jnp.asarray(horizons),
            peak_lr=5e-4, initial_lr=1e-5, min_lr=1e-6, warmup_steps=3)
        for i, horizon in enumerate(horizons):
            ref = warmup_cosine_schedule(5e-4, 1e-5, 1e-6, 3,
                                         int(horizon))(count)
            assert float(got[i]) == pytest.approx(float(ref), rel=1e-6)


# ---------------------------------------------------------------------------
# Engine: churn, isolation, export
# ---------------------------------------------------------------------------

def _make_job(cfg, name, *, rows=2, steps_per_epoch=2, n_epochs=1,
              seed=0, export_path=None, init=None):
    batches = [_job_arrays(cfg, rows, seed=seed + i)
               for i in range(steps_per_epoch)]

    def make_batches(epoch):
        for b in batches:
            yield b["inputs"], b["targets"], b["weights"]

    return FinetuneJob(name=name, make_batches=make_batches,
                       steps_per_epoch=steps_per_epoch, n_epochs=n_epochs,
                       export_path=export_path, init=init)


def test_join_finish_zero_recompile_and_deploy(cfg, base_params, tmp_path):
    """Job churn is data: a short job finishing, a queued job hot-joining
    its freed slot, and per-job exports all happen under the frozen
    CompileWatcher with ZERO recompiles; each artifact loads into a live
    AdapterRegistry (the train→deploy hop)."""
    from building_llm_from_scratch_tpu.serving.adapters import (
        AdapterRegistry,
    )

    registry = AdapterRegistry(cfg, base_params, capacity=4,
                               max_rank=RANK)
    fleet = FusedLoRATrainer(cfg, base_params, capacity=2, rank=RANK,
                             alpha=ALPHA, rows_per_job=2, log_every=1,
                             export_dir=str(tmp_path), deploy=registry)
    # capacity 2, three jobs of different lengths: "late" must hot-join
    # the slot "fast" frees, mid-run
    fleet.add_job(_make_job(cfg, "fast", steps_per_epoch=2, seed=0))
    fleet.add_job(_make_job(cfg, "slow", steps_per_epoch=3, n_epochs=2,
                            seed=10))
    fleet.add_job(_make_job(cfg, "late", steps_per_epoch=2, seed=20))
    fleet.run()
    assert [j.status for j in fleet.jobs] == ["done"] * 3
    assert fleet.n_recompiles == 0
    for job in fleet.jobs:
        assert os.path.isfile(job.artifact)
        lora, meta = load_adapter(job.artifact)
        assert meta["rank"] == RANK
        # deployed: the registry serves the tenant by name
        assert registry.lookup(job.name) is not None
    assert registry.n_loaded == 3


@pytest.mark.slow
def test_nonfinite_job_retires_alone_coresidents_bit_identical(
        cfg, base_params, tmp_path):
    """Poisoning job B's adapter row mid-run retires B (no artifact, a
    ``finetune_job_failed`` event) while job A's exported adapter is
    BIT-identical to a run where B stayed healthy — co-residency costs a
    tenant nothing, even under a neighbor's divergence (the serving
    fault-isolation contract, training-side)."""
    init_a = init_lora_params(cfg, base_params, jax.random.PRNGKey(50),
                              rank=RANK)
    init_b = init_lora_params(cfg, base_params, jax.random.PRNGKey(51),
                              rank=RANK)

    def run(poison: bool, out_dir):
        mj = os.path.join(str(out_dir), "m.jsonl")
        configure_metrics(mj)
        try:
            fleet = FusedLoRATrainer(cfg, base_params, capacity=2,
                                     rank=RANK, alpha=ALPHA,
                                     rows_per_job=2, log_every=2,
                                     export_dir=str(out_dir))
            fleet.add_job(_make_job(cfg, "a", steps_per_epoch=6, seed=0,
                                    init=_copy(init_a)))
            fleet.add_job(_make_job(cfg, "b", steps_per_epoch=6, seed=9,
                                    init=_copy(init_b)))

            def hook(engine):
                if poison and engine.global_step == 3:
                    bad = engine._slots[1]
                    assert bad is not None and bad.name == "b"
                    engine.state["trainable"] = jax.tree_util.tree_map(
                        lambda p: p.at[1].set(jnp.nan),
                        engine.state["trainable"])

            fleet.on_step = hook
            fleet.run()
        finally:
            configure_metrics(None)
        rows = [json.loads(line) for line in open(mj)]
        return fleet, rows

    clean, _ = run(False, tmp_path / "clean")
    poisoned, rows = run(True, tmp_path / "poisoned")

    a_clean = next(j for j in clean.jobs if j.name == "a")
    a_pois = next(j for j in poisoned.jobs if j.name == "a")
    b_pois = next(j for j in poisoned.jobs if j.name == "b")
    assert a_pois.status == "done" and a_clean.status == "done"
    assert b_pois.status == "failed" and b_pois.artifact is None
    assert "non-finite" in b_pois.error
    failed = [r for r in rows if r.get("event") == "finetune_job_failed"]
    assert len(failed) == 1 and failed[0]["job_id"] == "b"
    assert failed[0]["reason"] == "non_finite"
    # the poisoned run never recompiled (retire is data, not shape)
    assert poisoned.n_recompiles == 0
    # job A's artifact: bit-identical across the two runs
    lora_clean, _ = load_adapter(a_clean.artifact)
    lora_pois, _ = load_adapter(a_pois.artifact)
    for x, y in zip(jax.tree_util.tree_leaves(lora_clean),
                    jax.tree_util.tree_leaves(lora_pois)):
        assert np.array_equal(x, y)


def test_zero_supervision_job_retires_instead_of_exporting(
        cfg, base_params, tmp_path):
    """A job whose every row is fully loss-masked (the
    template-overflows-context hazard) never trained: it must retire as
    failed (``no_supervised_tokens``) instead of exporting and deploying
    a zero-delta adapter as 'done'."""
    masked = _job_arrays(cfg, 2, seed=0)
    masked["weights"][:] = 0.0

    def make_batches(epoch):
        yield masked["inputs"], masked["targets"], masked["weights"]

    mj = os.path.join(str(tmp_path), "m.jsonl")
    configure_metrics(mj)
    try:
        fleet = FusedLoRATrainer(cfg, base_params, capacity=2, rank=RANK,
                                 alpha=ALPHA, rows_per_job=2, log_every=1,
                                 export_dir=str(tmp_path))
        fleet.add_job(FinetuneJob(name="masked",
                                  make_batches=make_batches,
                                  steps_per_epoch=1, n_epochs=2))
        fleet.add_job(_make_job(cfg, "healthy", steps_per_epoch=2,
                                seed=1))
        fleet.run()
    finally:
        configure_metrics(None)
    bad = next(j for j in fleet.jobs if j.name == "masked")
    good = next(j for j in fleet.jobs if j.name == "healthy")
    assert bad.status == "failed" and bad.artifact is None
    assert "no_supervised_tokens" in bad.error
    assert good.status == "done" and os.path.isfile(good.artifact)
    rows = [json.loads(line) for line in open(mj)]
    failed = [r for r in rows if r.get("event") == "finetune_job_failed"]
    assert failed and failed[0]["reason"] == "no_supervised_tokens"


def test_fast_job_exports_before_slow_job_finishes(cfg, base_params,
                                                   tmp_path):
    """Per-JOB export discipline: the fast tenant's ``adapter_save``
    lands while the slow job is still training (event order pinned) —
    deployments never wait for the whole fleet."""
    mj = os.path.join(str(tmp_path), "m.jsonl")
    configure_metrics(mj)
    try:
        fleet = FusedLoRATrainer(cfg, base_params, capacity=2, rank=RANK,
                                 alpha=ALPHA, rows_per_job=2, log_every=1,
                                 export_dir=str(tmp_path))
        fleet.add_job(_make_job(cfg, "fast", steps_per_epoch=2, seed=0))
        fleet.add_job(_make_job(cfg, "slow", steps_per_epoch=4,
                                n_epochs=2, seed=10))
        fleet.run()
    finally:
        configure_metrics(None)
    rows = [json.loads(line) for line in open(mj)]
    kinds = [(r.get("event"), r.get("job_id")) for r in rows
             if r.get("type") == "event"]
    fast_save = kinds.index(("adapter_save", "fast"))
    slow_done = kinds.index(("finetune_job_done", "slow"))
    assert fast_save < slow_done
    # both artifacts exist and are distinct files
    paths = {j.artifact for j in fleet.jobs}
    assert len(paths) == 2 and all(os.path.isfile(p) for p in paths)


def test_forward_adapter_mixed_ids_matches_per_row_lora(cfg, base_params):
    """The jobs-axis threading unit: a mixed-ids batch through
    ``forward(adapter=)`` equals running each row with its own adapter
    through the existing ``forward(lora=)`` path (id −1 rows equal the
    bare base forward bit-for-bit)."""
    lora0 = init_lora_params(cfg, base_params, jax.random.PRNGKey(2),
                             rank=RANK)
    lora0 = jax.tree_util.tree_map(lambda a: a + 0.02, lora0)
    lora1 = init_lora_params(cfg, base_params, jax.random.PRNGKey(3),
                             rank=RANK)
    lora1 = jax.tree_util.tree_map(lambda a: a - 0.015, lora1)
    pool = jax.tree_util.tree_map(
        lambda a, b: jnp.stack([a, b]), lora0, lora1)
    rng = np.random.default_rng(1)
    tokens = rng.integers(0, cfg.vocab_size,
                          (3, cfg.context_length)).astype(np.int32)
    ids = np.asarray([1, -1, 0], np.int32)
    scaling = np.full((2,), ALPHA / RANK, np.float32)
    got = forward(base_params, cfg, tokens,
                  adapter={"pool": pool, "scaling": scaling, "ids": ids})
    # same batch shape as ``got``: a (1, T) forward is another XLA
    # program whose sums associate differently (equal only to an ulp)
    ref1 = forward(base_params, cfg, tokens)[1:2]
    ref0 = forward(base_params, cfg, tokens[2:3], lora=lora0,
                   lora_scaling=ALPHA / RANK)
    ref_1 = forward(base_params, cfg, tokens[0:1], lora=lora1,
                    lora_scaling=ALPHA / RANK)
    # the id -1 row is the bare base path EXACTLY (clamped gather x zero
    # scale = exact zero delta)
    assert np.array_equal(np.asarray(got[1]), np.asarray(ref1[0]))
    np.testing.assert_allclose(np.asarray(got[2]), np.asarray(ref0[0]),
                               atol=1e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref_1[0]),
                               atol=1e-5, rtol=0)


# ---------------------------------------------------------------------------
# CLI flag surface
# ---------------------------------------------------------------------------

def test_fleet_flag_validation(tmp_path):
    from building_llm_from_scratch_tpu.args import get_args

    records = [{"instruction": "a", "input": "", "output": "b"}] * 4
    jpath = os.path.join(str(tmp_path), "j.json")
    with open(jpath, "w") as f:
        json.dump(records, f)
    data = os.path.join(str(tmp_path), "data")
    os.makedirs(data)

    base = ["--debug", "--byte_tokenizer", "--output_dir",
            os.path.join(str(tmp_path), "out")]
    # happy path parses
    args = get_args(["--mode", "finetune_fleet",
                     "--fleet_jobs", f"a={jpath}"] + base)
    assert args.mode == "finetune_fleet"
    # fleet mode without jobs
    with pytest.raises(ValueError, match="fleet_jobs"):
        get_args(["--mode", "finetune_fleet"] + base)
    # missing records file
    with pytest.raises(FileNotFoundError):
        get_args(["--mode", "finetune_fleet",
                  "--fleet_jobs", "a=/nonexistent.json"] + base)
    # fleet flags stray outside the mode
    with pytest.raises(ValueError, match="finetune_fleet"):
        get_args(["--data_dir", data,
                  "--fleet_jobs", f"a={jpath}"] + base)
    # --use_lora / --finetune / --save_adapter are solo-run flags
    for extra in (["--use_lora"], ["--finetune"],
                  ["--save_adapter", "x.npz"]):
        with pytest.raises(ValueError):
            get_args(["--mode", "finetune_fleet",
                      "--fleet_jobs", f"a={jpath}"] + base + extra)


def test_job_from_records_plain_style(cfg):
    from building_llm_from_scratch_tpu.data.tokenizers import (
        build_tokenizer,
    )

    tok = build_tokenizer("GPT2", None, fallback_byte=True)
    records = [{"instruction": "ab", "input": "", "output": "cdef"}
               for _ in range(5)]
    job = FinetuneJob.from_records(
        "t", records, tok, max_length=cfg.context_length,
        rows_per_step=2, n_epochs=2, pad_token_id=cfg.eos_id, seed=1,
        style="plain")
    assert job.total_steps == 4          # 5 records // 2 rows, x2 epochs
    inp, tgt, w = job.next_rows()
    assert inp.shape == (2, cfg.context_length)
    # plain style leaves supervised positions inside the tiny context
    assert w.sum() > 0
    # too-few records refuse loudly
    with pytest.raises(ValueError, match="cannot fill"):
        FinetuneJob.from_records(
            "t2", records[:1], tok, max_length=cfg.context_length,
            rows_per_step=2, n_epochs=1, pad_token_id=cfg.eos_id)


# ---------------------------------------------------------------------------
# Slot-aligned adapter application (ROADMAP PR 12 follow-up)
# ---------------------------------------------------------------------------

def test_aligned_matches_gather_path_k3(cfg, base_params):
    """The slot-aligned ``(J, R*T)`` application (default) trains each
    job identically to the historical per-row gather: k=3 per-job
    losses within 1e-5 and adapter params within 5e-6 after 6 steps —
    the reshape removes the rows_per_job-fold A/B duplication, not any
    math. (The HLO difference is what the re-baselined
    ``micro_lora_fusion`` fingerprint pins.)"""
    k, rows, n, horizon = 3, 2, 6, 8
    jobs = []
    for j in range(k):
        jb = _job_arrays(cfg, rows, seed=j)
        jb["lora"] = init_lora_params(cfg, base_params,
                                      jax.random.PRNGKey(10 + j),
                                      rank=RANK)
        jobs.append(jb)
    batch = _fused_batch(jobs, rows, k, horizon)

    def run(aligned):
        state = init_fleet_state(cfg, base_params, capacity=k, rank=RANK,
                                 rng=jax.random.PRNGKey(123))
        for j in range(k):
            state["trainable"] = _set_row(state["trainable"], j,
                                          _copy(jobs[j]["lora"]))
        step = make_fused_train_step(cfg, capacity=k, warmup_steps=2,
                                     aligned=aligned)
        losses = []
        for _ in range(n):
            state, m = step(state, batch)
            losses.append(np.asarray(jax.device_get(m["loss"])))
        return np.stack(losses), jax.device_get(state["trainable"])

    l_aligned, p_aligned = run(True)
    l_gather, p_gather = run(False)
    np.testing.assert_allclose(l_aligned, l_gather, rtol=1e-5, atol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(p_aligned),
                    jax.tree_util.tree_leaves(p_gather)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-6, rtol=0)


def test_aligned_rejects_misaligned_batch(cfg, base_params):
    """The aligned path is only valid for the stack_fleet_batch layout:
    a row count not divisible by rows_per_job is a loud error, not a
    silently mis-bucketed delta."""
    from building_llm_from_scratch_tpu.models.transformer import (
        forward_hidden,
    )

    pool = jax.tree_util.tree_map(
        lambda a: jnp.zeros((2,) + a.shape, a.dtype),
        init_lora_params(cfg, base_params, jax.random.PRNGKey(0),
                         rank=RANK))
    tokens = np.zeros((3, cfg.context_length), np.int32)  # 3 % 2 != 0
    with pytest.raises(ValueError, match="rows_per_job"):
        forward_hidden(base_params, cfg, tokens,
                       adapter={"pool": pool,
                                "scaling": jnp.ones((2,), jnp.float32),
                                "rows_per_job": 2})


# ---------------------------------------------------------------------------
# Fleet checkpoint / resume (PR 1 machinery on the stacked pool state)
# ---------------------------------------------------------------------------

def _ckpt_jobs(cfg, tok, n_epochs=3):
    def records(vocab):
        return [{"instruction": vocab[i % 4] * 2, "input": "",
                 "output": vocab[(i + 1) % 4] * 3} for i in range(8)]

    return [FinetuneJob.from_records(
        name, records(vocab), tok, max_length=cfg.context_length,
        rows_per_step=2, n_epochs=n_epochs, pad_token_id=cfg.eos_id,
        style="plain") for name, vocab in (("ja", "abcd"), ("jb", "wxyz"))]


def _tracked_run(engine, record, stop_at=None, signal_at=None):
    """Run a fleet recording each flushed step's per-job losses; with
    ``signal_at``, deliver a REAL SIGTERM (to this process, through a
    GracefulStopper) once global_step reaches it."""
    import os
    import signal as _signal

    from building_llm_from_scratch_tpu.training.resilience import (
        GracefulStopper,
    )

    orig_flush = engine._flush

    def wrapped(*a, **kw):
        orig_flush(*a, **kw)
        if engine._last_fetched is not None:
            record[engine.global_step] = [
                round(float(x), 10) for x in engine._last_fetched["loss"]]

    engine._flush = wrapped
    if signal_at is not None:
        def on_step(eng):
            if eng.global_step == signal_at:
                os.kill(os.getpid(), _signal.SIGTERM)

        engine.on_step = on_step
        with GracefulStopper() as stopper:
            engine.run(stopper=stopper)
    else:
        engine.run()
    return engine


@pytest.mark.slow
def test_fleet_sigterm_resume_bit_for_bit(cfg, base_params, tmp_path):
    """SIGTERM mid-fleet -> step-boundary checkpoint -> `--resume auto`
    discovery -> per-job loss trajectories continue BIT-FOR-BIT: the
    stacked pool state round-trips through the PR 1 sharded-manifest
    checkpoint, and each job's batch cursor fast-forwards to the exact
    (epoch, index) the preempted run stopped at."""
    from building_llm_from_scratch_tpu.data.tokenizers import (
        build_tokenizer,
    )
    from building_llm_from_scratch_tpu.training.resilience import (
        find_latest_valid_checkpoint,
    )

    tok = build_tokenizer("GPT2", None, fallback_byte=True)

    def make(ckpt_dir=None):
        eng = FusedLoRATrainer(
            cfg, base_params, tokenizer=tok, capacity=2, rank=RANK,
            alpha=ALPHA, rows_per_job=2, log_every=1,
            export_dir=str(tmp_path / "adapters"),
            ckpt_dir=ckpt_dir, compile_telemetry=False)
        for job in _ckpt_jobs(cfg, tok):
            eng.add_job(job)
        return eng

    reference = {}
    _tracked_run(make(), reference)
    assert len(reference) == 12          # 2 jobs x (8//2) x 3 epochs

    ckpt_dir = str(tmp_path / "ckpts")
    resumed = {}
    first = _tracked_run(make(ckpt_dir), resumed, signal_at=5)
    assert first.preempted
    assert all(j.status == "running" for j in first.jobs)
    found = find_latest_valid_checkpoint(ckpt_dir)
    assert found is not None and found.endswith("model_pg_5")

    second = make(ckpt_dir)
    second.restore(found)
    assert second.global_step == 5
    _tracked_run(second, resumed)
    assert not second.preempted
    assert all(j.status == "done" for j in second.jobs)
    assert resumed == reference          # bit-for-bit, pre AND post resume


def test_fleet_restore_refuses_mismatched_shape(cfg, base_params,
                                                tmp_path):
    """A checkpoint from a different fleet geometry (capacity/rank) or a
    non-fleet checkpoint refuses loudly instead of silently restoring
    the wrong pool."""
    from building_llm_from_scratch_tpu.data.tokenizers import (
        build_tokenizer,
    )

    tok = build_tokenizer("GPT2", None, fallback_byte=True)
    eng = FusedLoRATrainer(cfg, base_params, tokenizer=tok, capacity=2,
                           rank=RANK, alpha=ALPHA, rows_per_job=2,
                           ckpt_dir=str(tmp_path),
                           compile_telemetry=False)
    for job in _ckpt_jobs(cfg, tok, n_epochs=1):
        eng.add_job(job)
    eng._admit_pending()
    path = eng.save_checkpoint()
    assert path is not None

    other = FusedLoRATrainer(cfg, base_params, tokenizer=tok, capacity=3,
                             rank=RANK, alpha=ALPHA, rows_per_job=2,
                             compile_telemetry=False)
    with pytest.raises(ValueError, match="capacity/rank"):
        other.restore(path)

    # a non-fleet manifest (no fleet flag) refuses before touching state
    from building_llm_from_scratch_tpu.training.checkpoint import (
        save_checkpoint,
    )

    plain = str(tmp_path / "model_pg_99")
    save_checkpoint(plain, {"w": jnp.zeros((2,))},
                    extra_metadata={"global_step": 99})
    with pytest.raises(ValueError, match="not a fleet checkpoint"):
        eng.restore(plain)


def test_resume_discovery_filters_by_run_mode(cfg, base_params, tmp_path):
    """Trainer and fleet checkpoints share the model_pg_ prefix and often
    one --output_dir: each mode's AUTO-discovery must skip the other's
    checkpoints quietly (start fresh / find an older matching one)
    instead of picking the wrong type and dying in the restore."""
    from building_llm_from_scratch_tpu.data.tokenizers import (
        build_tokenizer,
    )
    from building_llm_from_scratch_tpu.training.checkpoint import (
        save_checkpoint,
    )
    from building_llm_from_scratch_tpu.training.resilience import (
        resolve_resume,
    )

    out = str(tmp_path)
    fleet_pred = lambda meta: bool(meta.get("fleet"))      # noqa: E731
    train_pred = lambda meta: not meta.get("fleet")        # noqa: E731

    # a TRAINER checkpoint alone: fleet auto-resume starts fresh
    save_checkpoint(os.path.join(out, "model_pg_7"),
                    {"w": jnp.zeros((2,))},
                    extra_metadata={"global_step": 7})
    assert resolve_resume("auto", None, out, predicate=fleet_pred) is None
    # ...while trainer auto-resume finds it
    got = resolve_resume("auto", None, out, predicate=train_pred)
    assert got is not None and got.endswith("model_pg_7")

    # add a NEWER fleet checkpoint: each mode now finds its own
    tok = build_tokenizer("GPT2", None, fallback_byte=True)
    eng = FusedLoRATrainer(cfg, base_params, tokenizer=tok, capacity=2,
                           rank=RANK, alpha=ALPHA, rows_per_job=2,
                           ckpt_dir=out, compile_telemetry=False)
    for job in _ckpt_jobs(cfg, tok, n_epochs=1):
        eng.add_job(job)
    eng._admit_pending()
    eng.global_step = 9
    eng.save_checkpoint()
    got = resolve_resume("auto", None, out, predicate=fleet_pred)
    assert got is not None and got.endswith("model_pg_9")
    got = resolve_resume("auto", None, out, predicate=train_pred)
    assert got is not None and got.endswith("model_pg_7")
    # an EXPLICIT wrong-type path still refuses loudly in restore()
    with pytest.raises(ValueError, match="not a fleet checkpoint"):
        eng.restore(os.path.join(out, "model_pg_7"))


def test_resume_discovery_survives_vanished_candidate(tmp_path,
                                                      monkeypatch):
    """Discovery must never raise: a candidate that becomes unreadable
    between listing and the predicate's metadata read (a concurrent
    run's retention GC deleting it) is skipped like any other invalid
    checkpoint instead of crashing --resume auto."""
    from building_llm_from_scratch_tpu.training import (
        checkpoint as ckpt_mod,
    )
    from building_llm_from_scratch_tpu.training.resilience import (
        find_latest_valid_checkpoint,
    )

    out = str(tmp_path)
    for step in (3, 5):
        ckpt_mod.save_checkpoint(
            os.path.join(out, f"model_pg_{step}"),
            {"w": jnp.zeros((2,))}, extra_metadata={"global_step": step})

    # model_pg_5 survives LISTING (first metadata read per path) but
    # "vanishes" before the predicate's own read (the second) — exactly
    # the GC race window
    real_metadata = ckpt_mod.checkpoint_metadata
    calls = {}

    def racing_metadata(path):
        calls[path] = calls.get(path, 0) + 1
        if path.endswith("model_pg_5") and calls[path] >= 2:
            raise ValueError("manifest.json is missing (deleted by GC)")
        return real_metadata(path)

    monkeypatch.setattr(ckpt_mod, "checkpoint_metadata", racing_metadata)
    got = find_latest_valid_checkpoint(out, predicate=lambda meta: True)
    assert got is not None and got.endswith("model_pg_3")
