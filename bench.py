"""Benchmarks: tokens/sec/chip for the five BASELINE.json configs.

Usage:
  python bench.py            # headline: GPT2-124M pretrain bf16 (one JSON line)
  python bench.py cfg1       # GPT2-124M fp32 bs4 ctx1024 (BASELINE #1)
  python bench.py cfg2       # GPT2-774M bf16 + remat (BASELINE #2)
  python bench.py cfg3       # LLaMA3.2-1B LoRA r8 SFT bf16 (BASELINE #3)
  python bench.py cfg4       # LLaMA3-8B-arch fsdp slice (BASELINE #4, see note)
  python bench.py cfg5       # LLaMA2-7B-arch zero1 slice (BASELINE #5, see note)
  python bench.py trainer    # Trainer-loop path (vs raw-step, VERDICT r2 #3)
  python bench.py serve      # continuous-batching engine vs sequential decode
  python bench.py serve_fleet  # router replica sweep (1/2/4 replicas,
                               # one forced-host device per replica)
  python bench.py micro_train  # debug-size perf-gate micro-bench (CI)
  python bench.py all        # everything, one JSON line each

Runner flags (the perf observatory, obs/perf.py):
  --repeats K   run each bench K times; the result row carries
                min/median/mean/stddev repeat stats (timing-gate noise floor)
  --json OUT    append schema'd BenchResult rows to OUT (JSONL; a
                run-metadata header row is written first), or into
                OUT/<name>.jsonl when OUT is a directory (trajectory layout)
  --quick       shrink iteration/request counts (never shapes — the
                structural fingerprint is quick-invariant); the CI gate mode

Every bench returns an ``obs/perf.BenchResult``: headline value + unit,
named extra metrics, the bench's arm-detail dict, and — filled by the
runner — env metadata (jax version, backend, device kind/count, mesh, git
sha, argv), repeat stats, and a structural HLO fingerprint (per-program
cost-analysis FLOPs, memory breakdown, arg signatures, recompile count)
captured via ``obs/compile.CompileWatcher``. ``scripts/perf_gate.py``
compares those fingerprints against PERF_BASELINE.json in CI.

Configs #4/#5 target multi-chip pods; here they run the exact fsdp/zero1
code paths on the largest model slice that fits one v5e chip (reduced layer
count, recorded in the metric name). ``__graft_entry__.dryrun_multichip``
is a CPU rehearsal of the sharding RULES on a virtual mesh at toy width: it
shows the modes trace and agree, not that the full-size program compiles
for or runs on chips (tests/test_tpu_compile.py and chip_smoke.py --chips 4
do that).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import numpy as np

from building_llm_from_scratch_tpu.obs import perf

#: --quick: shrink iteration/request counts so the CI perf gate finishes
#: in seconds. NEVER shrinks shapes (batch size, context, slots) — the
#: structural fingerprint must be identical in quick and full mode.
_QUICK = False


def _q_iters(warmup: int, iters: int):
    """Quick-mode iteration budget: fewer timed steps, same shapes."""
    if _QUICK:
        return min(warmup, 1), min(iters, 4)
    return warmup, iters


def _result(name: str, metric: str, value, unit: str = "tokens/sec/chip",
            mfu=None, detail=None) -> perf.BenchResult:
    """Build the BenchResult every BENCHES entry returns (the old
    ``(metric, value[, mfu])`` tuple contract, made schema'd)."""
    res = perf.BenchResult(name=name, metric=metric, value=float(value),
                           unit=unit, detail=detail)
    if mfu is not None:
        res.add_metric("mfu", round(float(mfu), 4), "fraction")
    return res

# Per-chip peak FLOPs + HBM bandwidth come from the ONE device-spec table
# in obs/mfu.py (deduplicated this round — bench kept a private copy that
# had already drifted from the trainer's). MFU below is MODEL-flops
# utilization: 6*N_matmul per token for full training, 4*N_matmul for LoRA
# (no dW for frozen weights; dx still flows), plus causal attention matmul
# flops; remat recompute is NOT counted (standard MFU convention), so remat
# configs understate hardware efficiency.


def _device_specs():
    from building_llm_from_scratch_tpu.obs import mfu as _mfu

    spec = _mfu.device_specs()
    if spec is None:
        dev = jax.devices()[0]
        raise RuntimeError(
            f"no peak FLOP/s and HBM bandwidth on record for device "
            f"'{dev.device_kind}' ({dev.platform}): MFU and roofline shares "
            f"need a row in obs/mfu.DEVICE_SPECS — a device that is not in "
            f"the table is an error, not a default")
    return spec


def _cpu_child_env(bench: str) -> dict:
    """Environment for the benches whose arms run in CHILD processes on a
    forced multi-device host platform (virtual CPU meshes: a rehearsal of
    routing and sharding, by construction not a device measurement). This
    process has initialised JAX, so on an accelerator host it holds the
    chip and a child could only fall back to the CPU — refused, rather
    than print a CPU number where a device number is read."""
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            f"bench '{bench}' runs its arms in child processes on a virtual "
            f"CPU mesh; this process holds the {jax.default_backend()} "
            f"device, which belongs to one process at a time. Run it with "
            f"JAX_PLATFORMS=cpu (it measures nothing about the chip).")
    repo = os.path.dirname(os.path.abspath(__file__))
    # the workers import the package from the repo root (running them by
    # path puts scripts/ at sys.path[0], not the repo)
    return dict(os.environ, JAX_PLATFORMS="cpu",
                PYTHONPATH=repo + os.pathsep
                + os.environ.get("PYTHONPATH", ""))


def _model_flops_per_token(cfg, lora: bool = False) -> float:
    D, F, hd = cfg.emb_dim, cfg.hidden_dim, cfg.head_dim
    Hq, Hkv, T = cfg.n_heads, cfg.n_kv_groups, cfg.context_length
    per_layer = (D * Hq * hd + 2 * D * Hkv * hd + Hq * hd * D  # wq wk wv wo
                 + (3 if cfg.activation == "swiglu" else 2) * D * F)
    n_matmul = cfg.n_layers * per_layer + D * cfg.vocab_size    # + head
    # causal attention: q.k^T and p.v, ~T/2 keys per query, fwd+bwd(2x)
    attn = cfg.n_layers * 2 * 2 * (T / 2) * (Hq * hd) * 3
    factor = 4 if lora else 6
    return factor * n_matmul + attn


def _mfu(tps: float, cfg, lora: bool = False) -> float:
    peak_flops, _ = _device_specs()
    return tps * _model_flops_per_token(cfg, lora) / peak_flops


def _time_steps(step, state, batch, warmup=3, iters=20):
    for _ in range(max(1, warmup)):
        state, metrics = step(state, batch)
    float(metrics["loss"])
    t0 = time.perf_counter()
    for _ in range(iters):
        state, metrics = step(state, batch)
    float(metrics["loss"])
    return time.perf_counter() - t0


def _batch(cfg, batch_size, seed=0, sft_mask=False):
    rng = np.random.default_rng(seed)
    T = cfg.context_length
    w = np.ones((batch_size, T), np.float32)
    if sft_mask:
        # instruction finetune: prompt tokens carry no loss (collator 0/1
        # weights); mask the first half like a typical Alpaca prompt
        w[:, : T // 2] = 0.0
    return {
        "inputs": rng.integers(0, cfg.vocab_size, (batch_size, T)).astype(
            np.int32),
        "targets": rng.integers(0, cfg.vocab_size, (batch_size, T)).astype(
            np.int32),
        "weights": w,
    }


def _pretrain_tps(cfg, batch_size, policy=None, warmup=3, iters=20,
                  shard_mode=None, lora_rank=None, lora_alpha=None,
                  sft_mask=False, grad_accum=1):
    from building_llm_from_scratch_tpu.models import init_params
    from building_llm_from_scratch_tpu.parallel import build_mesh_plan
    from building_llm_from_scratch_tpu.training import (
        build_optimizer,
        init_train_state,
        make_train_step,
    )

    params = init_params(cfg, jax.random.PRNGKey(0))
    if lora_rank is not None:
        from building_llm_from_scratch_tpu.models.lora import init_lora_params

        trainable = init_lora_params(cfg, params, jax.random.PRNGKey(1),
                                     rank=lora_rank)
        frozen = params
    else:
        trainable, frozen = params, None
    opt = build_optimizer(total_steps=warmup + iters + 1)
    state = init_train_state(trainable, opt, jax.random.PRNGKey(0),
                             frozen=frozen, policy=policy)
    batch = _batch(cfg, batch_size, sft_mask=sft_mask)
    if shard_mode is not None:
        plan = build_mesh_plan(shard_mode)
        state = plan.shard_state(state)
        batch = plan.shard_batch(batch)
    step = make_train_step(cfg, opt, policy=policy, lora_rank=lora_rank,
                           lora_alpha=lora_alpha, grad_accum=grad_accum)
    # CompileWatcher-wrap the step (obs/compile.py): the AOT capture makes
    # the line carry XLA's own cost accounting next to the measured tok/s
    # (compile seconds, HLO FLOPs, HBM breakdown), an active
    # FingerprintCollector (obs/perf.py) records it into the bench's
    # structural fingerprint, and the timed executable is the AOT-compiled
    # one (one compile either way; on capture failure the watcher falls
    # back to the plain jit path itself).
    from building_llm_from_scratch_tpu.obs.compile import CompileWatcher

    step = CompileWatcher(step, label="bench_step")
    warmup, iters = _q_iters(warmup, iters)
    dt = _time_steps(step, state, batch, warmup, iters)
    return batch_size * cfg.context_length * iters / dt / jax.device_count()


def bench_cfg1():
    """BASELINE #1: GPT2-124M single-device pretrain, fp32, no LoRA/ckpt.

    batch 4 == the reference's default (args.py:53); fp32 + no remat at
    batch 8 exceeds one v5e chip's 16GB HBM.
    """
    from building_llm_from_scratch_tpu.configs import get_config

    cfg = get_config("GPT2", "124M", dtype="fp32")
    tps = _pretrain_tps(cfg, batch_size=4)
    return _result("cfg1", "tokens/sec/chip GPT2-124M pretrain fp32 bs4 "
                   "ctx1024", tps, mfu=_mfu(tps, cfg))


def bench_headline():
    """Headline: GPT2-124M pretrain in bf16 — the dtype a TPU user would
    actually run (MXU-native), per round-2 VERDICT #3.

    bs8 since round 4: the fused attention kernel generates dropout masks
    in-kernel (ops/fused_attention.py), so the bs8 mask-temp HBM pressure
    that made bs4 faster in round 3 is gone (r4 measured: bs8 76.6k vs
    bs4 72.7k tok/s/chip)."""
    from building_llm_from_scratch_tpu.configs import get_config
    from building_llm_from_scratch_tpu.training import get_policy

    cfg = get_config("GPT2", "124M", dtype="fp32")
    tps = _pretrain_tps(cfg, batch_size=8, policy=get_policy("bf16"))
    return _result("headline", "tokens/sec/chip GPT2-124M pretrain bf16 "
                   "bs8 ctx1024", tps, mfu=_mfu(tps, cfg))


def bench_cfg2():
    """BASELINE #2: GPT2-774M pretrain, bf16 + activation ckpt (remat)."""
    from building_llm_from_scratch_tpu.configs import get_config
    from building_llm_from_scratch_tpu.training import get_policy

    cfg = get_config("GPT2", "774M", dtype="bf16", use_actv_ckpt=True)
    tps = _pretrain_tps(cfg, batch_size=8, warmup=2, iters=10,
                        policy=get_policy("bf16"))
    return _result("cfg2", "tokens/sec/chip GPT2-774M pretrain bf16+remat "
                   "bs8 ctx1024", tps, mfu=_mfu(tps, cfg))


def bench_cfg3():
    """BASELINE #3: LLaMA3.2-1B instruction SFT with LoRA rank 8, bf16
    (the second north-star metric)."""
    from building_llm_from_scratch_tpu.configs import get_config
    from building_llm_from_scratch_tpu.training import get_policy

    # remat: without it the scan saves (L=16, B, T, hidden=8192) activation
    # tensors for backward — 12GB+ of HLO temps, over one chip's 16GB
    cfg = get_config("llama3_2", "1B", dtype="bf16", use_actv_ckpt=True,
                     target_context_length=1024)
    tps = _pretrain_tps(cfg, batch_size=8, warmup=2, iters=10,
                        policy=get_policy("bf16"), lora_rank=8,
                        lora_alpha=16, sft_mask=True)
    return _result("cfg3", "tokens/sec/chip LLaMA3.2-1B LoRA-r8 SFT bf16 "
                   "bs8 ctx1024", tps, mfu=_mfu(tps, cfg, lora=True))


def bench_cfg4():
    """BASELINE #4: LLaMA3-8B fsdp — 8B does not fit one 16GB chip, so this
    runs the exact fsdp code path on the deepest 8B-architecture slice that
    fits (full 4096-dim layers, reduced layer count; the name records it).
    (The 8-way sharding RULES are rehearsed on a virtual CPU mesh at toy
    width in dryrun_multichip; nothing full-size runs there.)"""
    from building_llm_from_scratch_tpu.configs import get_config
    from building_llm_from_scratch_tpu.training import get_policy

    cfg = get_config("llama3", "8B", dtype="bf16", use_actv_ckpt=True,
                     target_context_length=1024).replace(n_layers=2)
    tps = _pretrain_tps(cfg, batch_size=4, warmup=2, iters=10,
                        policy=get_policy("bf16"), shard_mode="fsdp")
    return _result("cfg4", "tokens/sec/chip LLaMA3-8B-arch[2/32 layers] "
                   "SFT bf16 fsdp bs4 ctx1024", tps, mfu=_mfu(tps, cfg))


def bench_cfg5():
    """BASELINE #5: LLaMA2-7B zero1 — same one-chip constraint as #4; runs
    the zero1 (optimizer-state sharding) path on the deepest 7B-architecture
    slice that fits."""
    from building_llm_from_scratch_tpu.configs import get_config
    from building_llm_from_scratch_tpu.training import get_policy

    cfg = get_config("llama2", "7B", dtype="bf16", use_actv_ckpt=True,
                     target_context_length=1024).replace(n_layers=4)
    tps = _pretrain_tps(cfg, batch_size=4, warmup=2, iters=10,
                        policy=get_policy("bf16"), shard_mode="zero1")
    return _result("cfg5", "tokens/sec/chip LLaMA2-7B-arch[4/32 layers] "
                   "pretrain bf16 zero1 bs4 ctx1024", tps,
                   mfu=_mfu(tps, cfg))


def bench_accum():
    """--grad_accum: global batch 32 as 4 scanned microbatches of 8 — the
    large-global-batch/small-microbatch regime pods want (round-5 VERDICT
    #7). Activation memory is one bs-8 microbatch's; throughput should sit
    near the bs8 headline (the scan adds one fp32 grad accumulator
    read-modify-write per micro)."""
    from building_llm_from_scratch_tpu.configs import get_config
    from building_llm_from_scratch_tpu.training import get_policy

    cfg = get_config("GPT2", "124M", dtype="fp32")
    tps = _pretrain_tps(cfg, batch_size=32, warmup=2, iters=10,
                        policy=get_policy("bf16"), grad_accum=4)
    return _result("accum", "tokens/sec/chip GPT2-124M pretrain bf16 bs32 "
                   "grad_accum4", tps, mfu=_mfu(tps, cfg))


def _trainer_run(n_steps=60, prefetch=0, async_ckpt=False, save_every=None):
    """One Trainer-loop run; returns (mean steady-state tok/s, stats dict
    with the overlap accounting bench_prefetch A/Bs). ``save_every`` turns
    on periodic checkpointing (sync or async per ``async_ckpt``); default
    off so the headline bench_trainer figure stays comparable to history."""
    import tempfile

    from building_llm_from_scratch_tpu.configs import get_config
    from building_llm_from_scratch_tpu.data import ByteTokenizer, PretrainLoader
    from building_llm_from_scratch_tpu.models import init_params
    from building_llm_from_scratch_tpu.training import Trainer, get_policy

    if _QUICK:
        n_steps = min(n_steps, 12)
    cfg = get_config("GPT2", "124M", dtype="fp32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    tok = ByteTokenizer()
    loader = PretrainLoader(tok, batch_size=4, max_length=cfg.context_length)
    with tempfile.TemporaryDirectory() as d:
        path = f"{d}/corpus.txt"
        # enough bytes for > n_steps batches of 8x1024 tokens
        with open(path, "w") as f:
            f.write("the quick brown fox jumps over the lazy dog. "
                    * (n_steps * 4 * 1024 // 44 + 200))
        trainer = Trainer(cfg, params, tok, loader, output_dir=d,
                          policy=get_policy("bf16"),
                          eval_freq=20, eval_iters=1,
                          print_sample_iter=10 ** 9,
                          save_ckpt_freq=save_every or 10 ** 9,
                          warmup_steps=2, show_progress=False,
                          prefetch=prefetch, async_ckpt=async_ckpt)
        trainer.train_model([path], n_epochs=1)
        # drop the first window (compile); average the steady-state windows
        tps_windows = trainer.throughput_tokens_per_s[1:]
    tps = float(np.mean(tps_windows)) if tps_windows else 0.0
    steps = max(trainer.global_step, 1)
    stats = {
        "data_wait_s_per_step": round(
            trainer.data_wait_total_s / steps, 6),
        "data_wait_frac": round(
            trainer.data_wait_total_s / max(trainer.step_seconds_total,
                                            1e-9), 4),
        "prefetch_stalls": trainer.prefetch_stall_total,
        "steps": trainer.global_step,
    }
    return tps, stats


def bench_trainer(n_steps=60):
    """The Trainer-loop path (cadence work, metric tracking, data pipeline)
    — must be within ~5% of the raw-step headline (round-2 VERDICT #3).
    Runs with the CLI-default --prefetch 2 since the host-overlap round."""
    tps, stats = _trainer_run(n_steps, prefetch=2)
    return _result("trainer", "tokens/sec/chip GPT2-124M Trainer-loop bf16 "
                   "bs4 ctx1024", tps, detail=stats)


def bench_prefetch(n_steps=60):
    """Host-overlap A/B: the identical Trainer workload with --prefetch 0
    (strict synchronous data path, blocking saves) vs --prefetch 2 + async
    checkpoints. Both arms checkpoint every n_steps//3 steps so the save
    cost is actually in the measurement — sync pays the full write barrier
    in-loop, async pays only the snapshot. The JSON line carries per-step
    data_wait and its fraction of step time for BOTH runs — the overlap
    win the BENCH history tracks — alongside the prefetched tok/s the
    headline metric reports."""
    save_every = max(n_steps // 3, 1)
    tps_off, off = _trainer_run(n_steps, prefetch=0, save_every=save_every)
    tps_on, on = _trainer_run(n_steps, prefetch=2, async_ckpt=True,
                              save_every=save_every)
    wait_off = max(off["data_wait_s_per_step"], 1e-9)
    detail = {
        "prefetch_off": dict(off, tok_s=round(tps_off, 1)),
        "prefetch_on": dict(on, tok_s=round(tps_on, 1)),
        "data_wait_speedup": round(
            wait_off / max(on["data_wait_s_per_step"], 1e-9), 1),
    }
    print(json.dumps(detail), flush=True)
    return _result("prefetch", "tokens/sec/chip GPT2-124M Trainer-loop "
                   "prefetch2+async_ckpt bf16 bs4 ctx1024", tps_on,
                   detail=detail)


def bench_decode(max_new=256):
    """Generation throughput: jitted KV-cache greedy decode on GPT2-124M
    (beyond reference parity — its generate.py re-runs the FULL forward per
    token with no cache, generate.py:36-45).

    Also logs per-seq tok/s and % of the weight-streaming roofline
    (param bytes measured from the actual tree, HBM bandwidth from the
    detected device kind — round-4 ADVICE low #4; for GPT2-124M bf16 on
    v5e: 248MB/step over ~820GB/s -> ~3,300 steps/s ceiling at
    bs-independent decode)."""
    import time

    from building_llm_from_scratch_tpu.configs import get_config
    from building_llm_from_scratch_tpu.generate import generate
    from building_llm_from_scratch_tpu.models import init_params

    if _QUICK:
        max_new = min(max_new, 64)
    cfg = get_config("GPT2", "124M", dtype="bf16")
    params = init_params(cfg, jax.random.PRNGKey(0))
    param_bytes = sum(leaf.size * leaf.dtype.itemsize
                      for leaf in jax.tree_util.tree_leaves(params))
    prompt = np.arange(32, dtype=np.int32)[None].repeat(8, 0)  # bs8
    kw = dict(max_new_tokens=max_new, context_size=cfg.context_length)
    out = generate(params, cfg, prompt, **kw)       # compile + warm
    # best-of-3 of whole calls (generate() ends in one device_get)
    dt = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        out = generate(params, cfg, prompt, **kw)
        dt = min(dt, time.perf_counter() - t0)
    n_steps = out.shape[1] - prompt.shape[1]
    n_tok = n_steps * prompt.shape[0]
    _, hbm_bw = _device_specs()
    roofline_steps = hbm_bw / param_bytes           # HBM BW / weight bytes

    # Device-side rate: every generate() call pays a fixed host cost
    # (dispatch, prompt transfer, the final fetch) that a 256-token decode
    # does not amortize; differencing two budgets cancels it, isolating the
    # per-token device time the roofline actually bounds. (Not re-measured
    # on the current stack: how large that fixed cost is with a local chip
    # is an open question in PERF.md.)
    def best_wall(budget):
        kw2 = dict(kw, max_new_tokens=budget)
        o = generate(params, cfg, prompt, **kw2)
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            o = generate(params, cfg, prompt, **kw2)
            best = min(best, time.perf_counter() - t0)
        assert o.shape[1] - prompt.shape[1] == budget
        return best

    lo, hi = (32, 96) if _QUICK else (128, 384)
    t_low, t_high = best_wall(lo), best_wall(hi)
    dev_steps_s = (hi - lo) / max(t_high - t_low, 1e-9)
    detail = {
        "decode_per_seq_tok_s": round(n_steps / dt, 1),
        "decode_pct_of_weight_stream_roofline":
            round(100 * (n_steps / dt) / roofline_steps, 1),
        "decode_device_per_seq_tok_s": round(dev_steps_s, 1),
        "decode_device_pct_of_weight_stream_roofline":
            round(100 * dev_steps_s / roofline_steps, 1),
    }
    print(json.dumps(detail), flush=True)
    return _result("decode", "decode tokens/sec GPT2-124M bf16 bs8 "
                   "kv-cache greedy", n_tok / dt, unit="tokens/sec",
                   detail=detail)


def bench_serve(n_requests=8, max_new=32, prompt_len=16):
    """Continuous-batching serving (serving/engine.py) vs the naive
    sequential baseline: the SAME n_requests prompts decoded one
    ``generate()`` call at a time (bs1 — what the repo could do before the
    engine existed) vs pumped through the slot engine at growing
    concurrency. Reports aggregate tok/s + p50/p99 e2e latency per arm;
    the acceptance bar is the engine beating sequential at >= 4 slots.

    bf16 on TPU, fp32 elsewhere (CPU bf16 is emulated and would distort
    the A/B)."""
    import time

    from building_llm_from_scratch_tpu.configs import get_config
    from building_llm_from_scratch_tpu.generate import _bucket, generate
    from building_llm_from_scratch_tpu.models import init_params
    from building_llm_from_scratch_tpu.serving import (
        DecodeEngine,
        SamplingParams,
    )

    if _QUICK:
        n_requests, max_new = min(n_requests, 4), min(max_new, 8)
    dtype = "bf16" if jax.default_backend() == "tpu" else "fp32"
    cfg = get_config("GPT2", "124M", dtype=dtype)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size,
                           (n_requests, prompt_len)).astype(np.int32)
    sp = SamplingParams(max_new_tokens=max_new, ignore_eos=True)

    # sequential baseline (eos disabled so both arms decode the full
    # budget — the A/B measures throughput, not stopping luck). Latency
    # is e2e from batch start (request i waits for 0..i-1), the same
    # all-submitted-at-t0 semantics as the engine arm's e2e_hist — NOT
    # per-call decode time, which would flatter the sequential tail
    generate(params, cfg, prompts[0][None], max_new_tokens=max_new)  # warm
    lat_seq = []
    t0 = time.perf_counter()
    for p in prompts:
        out = generate(params, cfg, p[None], max_new_tokens=max_new)
        assert out.shape[1] == prompt_len + max_new
        lat_seq.append(time.perf_counter() - t0)
    dt_seq = time.perf_counter() - t0
    seq_tok_s = n_requests * max_new / dt_seq
    detail = {"sequential": {
        "tok_s": round(seq_tok_s, 1),
        "p50_s": round(float(np.percentile(lat_seq, 50)), 4),
        "p99_s": round(float(np.percentile(lat_seq, 99)), 4),
    }}

    engine_at_4 = None
    for slots in (1, 4, 8):
        engine = DecodeEngine(cfg, params, n_slots=slots,
                              max_len=_bucket(prompt_len + max_new),
                              max_queue=n_requests,
                              warmup_prompt_cap=prompt_len)
        engine.warmup()
        t0 = time.perf_counter()
        handles = [engine.submit(p, sp, block=True) for p in prompts]
        engine.run_until_idle()
        dt = time.perf_counter() - t0
        for h in handles:
            assert len(h.output_ids) == max_new, h.finish_reason
        tok_s = n_requests * max_new / dt
        e2e_pct = engine.e2e_hist.percentiles((50, 99))
        detail[f"engine_slots{slots}"] = {
            "tok_s": round(tok_s, 1),
            "p50_s": e2e_pct.get("p50"),
            "p99_s": e2e_pct.get("p99"),
            "vs_sequential": round(tok_s / seq_tok_s, 2),
            "recompiles": engine.n_recompiles,
        }
        if slots == 4:
            engine_at_4 = tok_s
        engine.shutdown()
    print(json.dumps(detail), flush=True)
    return _result("serve", f"serve tokens/sec GPT2-124M {dtype} "
                   f"{n_requests}req x {max_new}new continuous-batching "
                   "slots4", engine_at_4, unit="tokens/sec", detail=detail)


def bench_serve_load(n_slots=4, max_new=24, prompt_len=16,
                     n_requests=40, deadline_factor=2.0):
    """Open-loop Poisson-arrival load sweep (the load-harness seed for
    the scale-out serving roadmap item): requests arrive on a Poisson
    schedule regardless of completions — unlike the closed-loop
    ``bench.py serve`` arm, this can actually SEE saturation, because
    offered load keeps coming when the engine falls behind.

    Arms sweep offered load at 0.5x / 1.0x / 1.5x the engine's measured
    closed-loop capacity. Every request carries a deadline
    (``deadline_factor`` x its ideal solo service time), so the overload
    arm exercises the real admission stack: SLO shedding at submit,
    TTL expiry in the queue, 429-style queue-full rejection. Reported
    per arm: offered/completed rps, shed/expired/rejected counts, and
    TTFT/TPOT/e2e percentiles — the latency-vs-throughput curve.

    Each arm writes its own metrics JSONL (reported as
    ``metrics_jsonl`` in the arm detail), so the per-arm tick-phase
    breakdown, request span trees and SLO burn are renderable after the
    fact: ``python scripts/summarize_metrics.py <arm.jsonl> --trace
    <arm.trace.json>``.

    fp32 on CPU, bf16 on TPU (same policy as ``bench_serve``)."""
    import tempfile
    import time

    from building_llm_from_scratch_tpu.configs import get_config
    from building_llm_from_scratch_tpu.generate import _bucket
    from building_llm_from_scratch_tpu.models import init_params
    from building_llm_from_scratch_tpu.serving import (
        DecodeEngine,
        QueueFullError,
        SLOShedError,
        SamplingParams,
    )
    from building_llm_from_scratch_tpu.serving.request import (
        RequestExpiredError,
    )

    if _QUICK:
        n_requests, max_new = min(n_requests, 12), min(max_new, 8)
    dtype = "bf16" if jax.default_backend() == "tpu" else "fp32"
    cfg = get_config("GPT2", "124M", dtype=dtype)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size,
                           (n_requests, prompt_len)).astype(np.int32)

    def new_engine():
        # metrics_every=8: short arms still emit tick-breakdown cadence
        # rows into their per-arm JSONL (the default 32 would leave a
        # small sweep with request events but no tick phases)
        eng = DecodeEngine(cfg, params, n_slots=n_slots,
                           max_len=_bucket(prompt_len + max_new),
                           max_queue=max(2 * n_slots, 16),
                           warmup_prompt_cap=prompt_len,
                           metrics_every=8)
        eng.warmup()
        return eng

    # measure closed-loop capacity first: n_slots requests decoded flat out
    eng = new_engine()
    t0 = time.perf_counter()
    sp = SamplingParams(max_new_tokens=max_new, ignore_eos=True)
    handles = [eng.submit(p, sp, block=True) for p in prompts[:n_slots]]
    eng.run_until_idle()
    cap_tok_s = n_slots * max_new / (time.perf_counter() - t0)
    cap_rps = cap_tok_s / max_new            # requests/sec at saturation
    solo_s = max_new / (cap_tok_s / n_slots)  # ideal one-request service
    eng.shutdown()
    detail = {"capacity": {"tok_s": round(cap_tok_s, 1),
                           "rps": round(cap_rps, 3)}}

    deadline_s = deadline_factor * solo_s
    completed_at_1x = 0.0
    from building_llm_from_scratch_tpu.obs import configure_metrics

    jsonl_dir = tempfile.mkdtemp(prefix="bench_serve_load_")
    for load in (0.5, 1.0, 1.5):
        lam = load * cap_rps                 # offered arrival rate
        arrivals = np.cumsum(rng.exponential(1.0 / lam, n_requests))
        # one telemetry file per arm: tick breakdown / span trees / SLO
        # burn stay attributable to THIS offered-load point
        arm_jsonl = os.path.join(jsonl_dir, f"load_{load:g}x.jsonl")
        configure_metrics(arm_jsonl, run_metadata={
            "bench": "serve_load", "offered_load_x": load,
            "n_slots": n_slots, "n_requests": n_requests})
        eng = new_engine()
        eng.start()
        handles, shed, rejected = [], 0, 0
        t0 = time.perf_counter()
        for i, (p, at) in enumerate(zip(prompts, arrivals)):
            delay = at - (time.perf_counter() - t0)
            if delay > 0:
                time.sleep(delay)            # open loop: arrivals wait
            try:                             # for the CLOCK, not the engine
                handles.append(eng.submit(p, SamplingParams(
                    max_new_tokens=max_new, ignore_eos=True,
                    deadline_s=deadline_s, seed=i)))
            except SLOShedError:
                shed += 1
            except QueueFullError:
                rejected += 1
        done, expired = 0, 0
        for h in handles:
            try:
                h.result(timeout=120)
                done += 1
            except RequestExpiredError:
                expired += 1
            except RuntimeError:
                pass
        dt = time.perf_counter() - t0
        eng.shutdown()
        configure_metrics(None)              # close + detach the arm sink
        stats = eng.stats()
        arm = {
            "offered_rps": round(lam, 3),
            "completed_rps": round(done / dt, 3),
            "done": done, "shed": shed, "expired": expired,
            "rejected": rejected,
            "shed_rate": round((shed + expired + rejected)
                               / n_requests, 3),
            "metrics_jsonl": arm_jsonl,
        }
        for key in ("ttft_s", "tpot_s", "e2e_s"):
            if key in stats:
                arm[key] = stats[key]
        detail[f"load_{load:g}x"] = arm
        if load == 1.0:
            completed_at_1x = done / dt
    print(json.dumps(detail), flush=True)
    return _result("serve_load", f"serve offered-load sweep GPT2-124M "
                   f"{dtype} {n_requests}req poisson slots{n_slots} "
                   "completed-rps@1.0x", completed_at_1x * max_new,
                   unit="tokens/sec", detail=detail)


def bench_serve_fleet(max_new=24, prompt_len=16, n_slots=4,
                      requests_per_replica=32, replica_counts=(1, 2, 4)):
    """Replica-scaling sweep through the fleet router (serving/router.py):
    the ``serve_load`` open-loop Poisson harness pointed at an
    ``EngineRouter`` at 1/2/4 replicas, offered load scaled with the
    replica count (per-replica capacity measured once by the 1-replica
    arm). Each arm runs in a SUBPROCESS with
    ``--xla_force_host_platform_device_count=8`` so every replica gets
    its own CPU device — per-device execution threads are independent
    and XLA releases the GIL, so this measures real concurrent replicas,
    not time-slicing (scripts/bench_fleet_worker.py). Aggregate
    completed-rps should scale near-linearly; the headline metric is the
    2-replica aggregate tokens/sec, ``speedup_2x``/``speedup_4x`` ride
    as extra metrics. This bench has no in-process fingerprint (the
    programs compile in the workers) — ``micro_router`` structurally
    gates the per-replica program family in CI instead."""
    import subprocess

    rpr, mnew = requests_per_replica, max_new
    if _QUICK:
        rpr, mnew = min(rpr, 8), min(mnew, 8)
    repo = os.path.dirname(os.path.abspath(__file__))
    worker = os.path.join(repo, "scripts", "bench_fleet_worker.py")
    env = _cpu_child_env("serve_fleet")
    detail = {}
    cap_rps = 0.0
    completed = {}
    for r in replica_counts:
        cmd = [sys.executable, worker, "--replicas", str(r),
               "--cap_rps", str(cap_rps),
               "--requests_per_replica", str(rpr),
               "--max_new", str(mnew), "--prompt_len", str(prompt_len),
               "--slots", str(n_slots), "--loads", "0.75,1.25"]
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=1800, env=env)
        if proc.returncode != 0:
            raise RuntimeError(
                f"fleet worker (replicas={r}) failed rc="
                f"{proc.returncode}:\n{proc.stderr[-2000:]}")
        row = json.loads(proc.stdout.strip().splitlines()[-1])
        if cap_rps <= 0:
            cap_rps = row["cap_rps"]
            detail["capacity"] = row.get("capacity")
        detail[f"replicas_{r}"] = row["arms"]
        completed[r] = row["arms"]["load_1.25x"]["completed_rps"]
    for r in replica_counts[1:]:
        if completed.get(1):
            detail[f"speedup_{r}x"] = round(completed[r] / completed[1], 3)
    # cross-process arm: the SAME sweep at 2 replicas through a
    # ProcessFleet of supervised worker subprocesses (serving/fleet.py)
    # reusing the in-process capacity point — the ratio vs the
    # in-process router bounds RPC-transport + supervision overhead
    crossproc_ratio = None
    if 2 in completed:
        cmd = [sys.executable, worker, "--replicas", "2",
               "--transport", "process", "--cap_rps", str(cap_rps),
               "--requests_per_replica", str(rpr),
               "--max_new", str(mnew), "--prompt_len", str(prompt_len),
               "--slots", str(n_slots), "--loads", "1.25"]
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=1800, env=env)
        if proc.returncode != 0:
            raise RuntimeError(
                f"fleet worker (crossproc) failed rc="
                f"{proc.returncode}:\n{proc.stderr[-2000:]}")
        row = json.loads(proc.stdout.strip().splitlines()[-1])
        detail["crossproc_2"] = row["arms"]
        cp = row["arms"]["load_1.25x"]["completed_rps"]
        crossproc_ratio = round(cp / completed[2], 3) if completed[2] \
            else None
        detail["crossproc_ratio"] = crossproc_ratio
    print(json.dumps(detail), flush=True)
    res = _result("serve_fleet", "fleet aggregate tokens/sec GPT2-124M "
                  f"router {len(replica_counts)}-arm sweep slots{n_slots} "
                  "completed@1.25x 2-replicas",
                  completed.get(2, completed[replica_counts[0]]) * mnew,
                  unit="tokens/sec", detail=detail)
    for r in replica_counts[1:]:
        if f"speedup_{r}x" in detail:
            res.add_metric(f"speedup_{r}x", detail[f"speedup_{r}x"],
                           "ratio")
    if crossproc_ratio is not None:
        res.add_metric("crossproc_ratio", crossproc_ratio, "ratio")
    return res


def bench_micro_router(n_replicas=2):
    """Debug-size fleet router (2 replicas x 2 slots, 8 mixed requests):
    the gate workload for the scale-out tier. ``watch_compiles="first"``
    wraps only replica 0's programs, so the captured fingerprint is the
    PER-REPLICA compiled-program family — replica-count invariant by
    construction (a 3-replica router fingerprints identically,
    test-pinned), while a change to what one replica compiles (router
    construction altering cache placement, an extra program, a warmup
    recompile) fails the structural gate with the program named."""
    from building_llm_from_scratch_tpu.configs import get_config
    from building_llm_from_scratch_tpu.models import init_params
    from building_llm_from_scratch_tpu.serving import (
        EngineRouter,
        SamplingParams,
    )

    n_requests, max_new, prompt_len = 8, 4, 4
    cfg = get_config("GPT2", "124M", dtype="fp32", debug=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size,
                           (n_requests, prompt_len)).astype(np.int32)
    sp = SamplingParams(max_new_tokens=max_new, ignore_eos=True)
    router = EngineRouter.build(cfg, params, n_replicas=n_replicas,
                                n_slots=2, max_queue=n_requests,
                                warmup_prompt_cap=prompt_len,
                                metrics_every=2,
                                watch_compiles="first")
    router.warmup()
    t0 = time.perf_counter()
    handles = [router.submit(p, sp, block=True) for p in prompts]
    router.run_until_idle()
    dt = time.perf_counter() - t0
    for h in handles:
        assert len(h.output_ids) == max_new, h.finish_reason
    detail = {"recompiles": router.n_recompiles,
              "routed_total": router.routed_total}
    router.shutdown()
    return _result("micro_router", "fleet tokens/sec GPT2-debug fp32 "
                   f"{n_requests}req x {max_new}new "
                   f"{n_replicas}replicas x slots2",
                   n_requests * max_new / dt, unit="tokens/sec",
                   detail=detail)


def bench_serve_lora(n_adapters=3, n_requests=16, max_new=24,
                     prompt_len=16, rank=8, n_slots=4):
    """Multi-tenant LoRA serving A/B (serving/adapters.py): the SAME
    request set decoded (a) by the historical registry-less engine,
    (b) by an adapter-pooled engine serving base-only traffic (the pure
    overhead of carrying the pool through the compiled programs), and
    (c) mixed traffic round-robining ``n_adapters`` adapters + base —
    the multi-tenant case a merge-based LoRA deployment cannot co-batch
    at all. Every arm must finish with ZERO recompiles (adapter identity
    is data, not a compile signature).

    bf16 on TPU, fp32 elsewhere (same policy as ``bench_serve``)."""
    import tempfile
    import time

    from building_llm_from_scratch_tpu.configs import get_config
    from building_llm_from_scratch_tpu.generate import _bucket
    from building_llm_from_scratch_tpu.models import init_params
    from building_llm_from_scratch_tpu.models.lora import (
        init_lora_params,
        save_adapter,
    )
    from building_llm_from_scratch_tpu.serving import (
        AdapterRegistry,
        DecodeEngine,
        SamplingParams,
    )

    if _QUICK:
        n_requests, max_new = min(n_requests, 8), min(max_new, 8)
    dtype = "bf16" if jax.default_backend() == "tpu" else "fp32"
    cfg = get_config("GPT2", "124M", dtype=dtype)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size,
                           (n_requests, prompt_len)).astype(np.int32)

    art_dir = tempfile.mkdtemp(prefix="bench_serve_lora_")
    specs = {}
    for i in range(n_adapters):
        lora = init_lora_params(cfg, params, jax.random.PRNGKey(100 + i),
                                rank=rank)
        lora = jax.tree_util.tree_map(
            lambda a, i=i: a + 0.02 * jax.random.normal(
                jax.random.PRNGKey(200 + i), a.shape, a.dtype), lora)
        path = os.path.join(art_dir, f"adapter_{i}.npz")
        save_adapter(path, lora, rank=rank, alpha=2.0 * rank, cfg=cfg)
        specs[f"tenant{i}"] = path

    def run_arm(adapters, names):
        eng = DecodeEngine(cfg, params, n_slots=n_slots,
                           max_len=_bucket(prompt_len + max_new),
                           max_queue=n_requests,
                           warmup_prompt_cap=prompt_len, adapters=adapters)
        eng.warmup()
        t0 = time.perf_counter()
        handles = [eng.submit(p, SamplingParams(
            max_new_tokens=max_new, ignore_eos=True, seed=i,
            adapter=names[i % len(names)]), block=True)
            for i, p in enumerate(prompts)]
        eng.run_until_idle()
        dt = time.perf_counter() - t0
        for h in handles:
            assert len(h.output_ids) == max_new, h.error
        assert eng.n_recompiles == 0, "adapter traffic recompiled"
        tok_s = n_requests * max_new / dt
        eng.shutdown()
        return tok_s

    base_tok_s = run_arm(None, [None])
    reg = AdapterRegistry.from_artifacts(cfg, params, specs)
    pool_tok_s = run_arm(reg, [None])
    mixed_names = [None] + list(specs)
    mixed_tok_s = run_arm(reg, mixed_names)
    detail = {
        "no_registry": {"tok_s": round(base_tok_s, 1)},
        "registry_base_only": {
            "tok_s": round(pool_tok_s, 1),
            "vs_no_registry": round(pool_tok_s / base_tok_s, 3)},
        "mixed_adapters": {
            "tok_s": round(mixed_tok_s, 1),
            "n_adapters": n_adapters, "rank": rank,
            "vs_no_registry": round(mixed_tok_s / base_tok_s, 3)},
        "recompiles": 0,
    }
    print(json.dumps(detail), flush=True)
    return _result("serve_lora", f"serve_lora tokens/sec GPT2-124M {dtype} "
                   f"{n_requests}req x {max_new}new {n_adapters}adapters"
                   f"+base slots{n_slots}", mixed_tok_s,
                   unit="tokens/sec", detail=detail)


def bench_serve_prefix(n_requests=10, prefix_len=192, suffix_len=8,
                       max_new=16, n_slots=4, chunk=64):
    """Shared-system-prompt A/B for the KV-cache memory engine
    (serving/kvcache.py): ``n_requests`` requests share one
    ``prefix_len``-token system prompt and differ only in a short
    suffix — the workload millions-of-users serving is made of.

    Three arms over the SAME requests:
      - ``unchunked``: the historical monolithic bucketed prefill
        (baseline for the per-tick prefill stall);
      - ``chunk_only``: chunked prefill (C=``chunk``), prefix cache OFF
        — isolates the head-of-line bound;
      - ``prefix_on``: chunked prefill + prefix cache — the first
        request prefills the prefix once, every successor copies its
        panes and chunk-prefills only the suffix.

    Reported per arm: TTFT p50/p95, per-tick prefill-wall p50/p95
    (``tick_prefill_hist`` — the head-of-line metric chunking bounds),
    prefix hit count, recompiles. The headline value is the prefix-ON
    aggregate tok/s; the acceptance bar is prefix_on TTFT p95 <
    chunk_only TTFT p95 (cached span skips its forward) with zero
    recompiles after warmup, and chunked tick-prefill p95 < unchunked.

    bf16 on TPU, fp32 elsewhere (same policy as ``bench_serve``)."""
    import time

    from building_llm_from_scratch_tpu.configs import get_config
    from building_llm_from_scratch_tpu.generate import _bucket
    from building_llm_from_scratch_tpu.models import init_params
    from building_llm_from_scratch_tpu.serving import (
        DecodeEngine,
        KVCachePolicy,
        SamplingParams,
    )

    if _QUICK:
        n_requests, max_new = min(n_requests, 6), min(max_new, 8)
    dtype = "bf16" if jax.default_backend() == "tpu" else "fp32"
    cfg = get_config("GPT2", "124M", dtype=dtype)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prefix = rng.integers(0, cfg.vocab_size, (prefix_len,)).astype(np.int32)
    prompts = [np.concatenate([
        prefix, rng.integers(0, cfg.vocab_size,
                             (suffix_len,)).astype(np.int32)])
        for _ in range(n_requests)]
    sp = SamplingParams(max_new_tokens=max_new, ignore_eos=True)
    cap = prefix_len + suffix_len
    max_len = _bucket(cap + max_new)

    arms = {
        "unchunked": KVCachePolicy(),
        "chunk_only": KVCachePolicy(prefill_chunk=chunk),
        "prefix_on": KVCachePolicy(prefill_chunk=chunk, prefix_cache=True),
    }
    detail = {}
    headline = None
    for arm, policy in arms.items():
        engine = DecodeEngine(cfg, params, n_slots=n_slots,
                              max_len=max_len, max_queue=n_requests,
                              warmup_prompt_cap=cap, kv_policy=policy)
        engine.warmup()
        t0 = time.perf_counter()
        handles = [engine.submit(p, sp, block=True) for p in prompts]
        engine.run_until_idle()
        dt = time.perf_counter() - t0
        for h in handles:
            assert len(h.output_ids) == max_new, h.finish_reason
        tok_s = n_requests * max_new / dt
        ttft = engine.ttft_hist.percentiles((50, 95))
        tick_pf = engine.tick_prefill_hist.percentiles((50, 95))
        row = {
            "tok_s": round(tok_s, 1),
            "ttft_p50_s": ttft.get("p50"),
            "ttft_p95_s": ttft.get("p95"),
            "tick_prefill_p50_s": tick_pf.get("p50"),
            "tick_prefill_p95_s": tick_pf.get("p95"),
            "recompiles": engine.n_recompiles,
        }
        if engine.prefix_store is not None:
            st = engine.prefix_store.stats()
            row["prefix_hits"] = st["hits"]
            row["prefix_misses"] = st["misses"]
            row["prefix_bytes"] = st["bytes"]
        detail[arm] = row
        if arm == "prefix_on":
            headline = tok_s
        engine.shutdown()
    off, on = detail["chunk_only"], detail["prefix_on"]
    if off.get("ttft_p95_s") and on.get("ttft_p95_s"):
        detail["ttft_p95_speedup_prefix"] = round(
            off["ttft_p95_s"] / on["ttft_p95_s"], 2)
    un, ch = detail["unchunked"], detail["chunk_only"]
    if un.get("tick_prefill_p95_s") and ch.get("tick_prefill_p95_s"):
        detail["tick_prefill_p95_ratio_chunked"] = round(
            ch["tick_prefill_p95_s"] / un["tick_prefill_p95_s"], 3)
    print(json.dumps(detail), flush=True)
    return _result("serve_prefix", f"serve_prefix tokens/sec GPT2-124M "
                   f"{dtype} {n_requests}req shared-{prefix_len}tok-prefix "
                   f"chunk{chunk} prefix-cache", headline,
                   unit="tokens/sec", detail=detail)


def bench_serve_mem(n_requests=12, prefix_len=192, suffix_len=8,
                    max_new=16, n_slots=4, chunk=64):
    """Shared-prefix LIVE-BYTES A/B for the memory observatory
    (obs/memory.py): the same workload as ``serve_prefix`` —
    ``n_requests`` requests sharing one ``prefix_len``-token system
    prompt — but the measured quantity is MEMORY, not latency. Every
    number comes off the engine's ``MemoryLedger`` (byte-exact pytree
    ``nbytes`` sums), never re-derived from shape formulas.

    Two arms over the SAME requests:
      - ``prefix_off``: chunked prefill, prefix cache OFF — every slot
        recomputes AND stores its own copy of the shared prefix;
      - ``prefix_on``: prefix cache ON — the store holds ONE pane set,
        successors copy it into their slot instead of prefilling it.

    Reported per arm: slot-cache resident bytes (the fixed carve-out),
    per-tenant live-KV peak from the ledger's labeled series, and the
    summed ``kv_bytes_peak`` over request_done. The prefix arm adds
    ``prefix_bytes_saved`` (KV bytes NOT re-prefilled thanks to hits)
    and ``pane_copy_duplication_x`` — live KV at peak still holds up to
    ``n_slots`` COPIES of panes the store holds once, because the hit
    path copies panes into the slot carve-out. That duplication factor
    is the committed baseline a paged/shared-block KV design (ROADMAP
    item 1) must collapse toward 1x; the headline is total
    ``prefix_bytes_saved`` so the trajectory row records today's
    copy-based savings next to the duplication it leaves on the table.

    bf16 on TPU, fp32 elsewhere (same policy as ``bench_serve``)."""
    import tempfile

    from building_llm_from_scratch_tpu.configs import get_config
    from building_llm_from_scratch_tpu.generate import _bucket
    from building_llm_from_scratch_tpu.models import init_params
    from building_llm_from_scratch_tpu.serving import (
        DecodeEngine,
        KVCachePolicy,
        SamplingParams,
    )

    if _QUICK:
        n_requests, max_new = min(n_requests, 6), min(max_new, 8)
    dtype = "bf16" if jax.default_backend() == "tpu" else "fp32"
    cfg = get_config("GPT2", "124M", dtype=dtype)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prefix = rng.integers(0, cfg.vocab_size, (prefix_len,)).astype(np.int32)
    prompts = [np.concatenate([
        prefix, rng.integers(0, cfg.vocab_size,
                             (suffix_len,)).astype(np.int32)])
        for _ in range(n_requests)]
    sp = SamplingParams(max_new_tokens=max_new, ignore_eos=True)
    cap = prefix_len + suffix_len
    max_len = _bucket(cap + max_new)

    arms = {
        "prefix_off": KVCachePolicy(prefill_chunk=chunk),
        "prefix_on": KVCachePolicy(prefill_chunk=chunk, prefix_cache=True),
        # the ROADMAP-item-1 arm: page-table KV — prefix hits are TABLE
        # WRITES against refcounted shared pages, so the duplication the
        # prefix_on arm leaves on the table collapses to ~1x
        "paged": KVCachePolicy(prefill_chunk=chunk, prefix_cache=True,
                               paged=True, page_tokens=16),
    }
    detail = {}
    headline = None
    from building_llm_from_scratch_tpu.obs import configure_metrics

    jsonl_dir = tempfile.mkdtemp(prefix="bench_serve_mem_")
    for arm, policy in arms.items():
        # one telemetry file per arm (serve_load idiom): the
        # memory_snapshot stream stays attributable to THIS arm
        configure_metrics(os.path.join(jsonl_dir, f"{arm}.jsonl"),
                          run_metadata={"bench": "serve_mem", "arm": arm,
                                        "n_slots": n_slots,
                                        "n_requests": n_requests})
        # metrics_every=1: the ledger observes every tick, so the
        # labeled kv_live_bytes peak is tick-accurate, not cadence-lossy
        engine = DecodeEngine(cfg, params, n_slots=n_slots,
                              max_len=max_len, max_queue=n_requests,
                              warmup_prompt_cap=cap, kv_policy=policy,
                              metrics_every=1)
        engine.warmup()
        on_token = None
        if policy.paged:
            # physical prefix residency, sampled at every token commit:
            # the distinct PHYSICAL pages backing the shared prefix span
            # across all active slots. Contiguous arms hold one pane
            # COPY per sharer; shared refcounted pages keep this at the
            # store's own page count (duplication_x == 1.0)
            n_prefix_pages = prefix_len // policy.page_tokens
            peak_prefix_pages = [0]

            def on_token(_req, _tok, _txt):
                tab, cols = engine._page_table, engine._slot_cols
                pages = set()
                for s in range(n_slots):
                    if cols[s] >= n_prefix_pages:
                        pages.update(
                            int(p) for p in tab[s, :n_prefix_pages])
                pages.discard(0)
                if len(pages) > peak_prefix_pages[0]:
                    peak_prefix_pages[0] = len(pages)

        handles = [engine.submit(p, sp, block=True, on_token=on_token)
                   for p in prompts]
        engine.run_until_idle()
        for h in handles:
            assert len(h.output_ids) == max_new, h.finish_reason
        ledger = engine.memory_ledger
        snap = ledger.snapshot()
        gauges = ledger.gauges()
        live_peak = max(
            ledger.labeled_peaks.get("kv_live_bytes", {}).values(),
            default=0)
        row = {
            "slot_kv_bytes": (snap["page_pool"] if policy.paged
                              else snap["slot_kv"] + snap.get("kv_scales",
                                                              0)),
            "kv_live_peak_bytes": live_peak,
            "kv_bytes_peak_sum": sum(h.kv_bytes_peak for h in handles),
            "mem_total_bytes": gauges["mem_total_bytes"],
            "recompiles": engine.n_recompiles,
        }
        if engine.prefix_store is not None:
            st = engine.prefix_store.stats()
            saved = sum(h.prefix_bytes_saved for h in handles)
            row["prefix_store_bytes"] = (
                engine.prefix_store.bytes_total if policy.paged
                else snap["prefix_store"])
            row["prefix_hits"] = st["hits"]
            row["prefix_bytes_saved"] = saved
            if policy.paged:
                # shared pages make duplication PHYSICAL, so it is
                # measured physically: distinct pages backing the
                # prefix span at peak / the store's own page count
                pool = engine.page_pool.stats()
                row["page_pool_peak_bytes"] = (pool["peak_used"]
                                               * pool["page_bytes"])
                row["pane_copies"] = engine.pane_copies
                row["pane_copy_duplication_x"] = round(
                    peak_prefix_pages[0] / n_prefix_pages, 2)
            elif snap["prefix_store"]:
                # peak live KV / the single stored pane set: how many
                # resident COPIES of the shared prefix the slot
                # carve-out holds at peak (the paged-KV target is ~1)
                row["pane_copy_duplication_x"] = round(
                    live_peak / snap["prefix_store"], 2)
            if arm == "prefix_on":
                headline = float(saved)
        detail[arm] = row
        engine.shutdown()
        configure_metrics(None)              # close + detach the arm sink
    off, on = detail["prefix_off"], detail["prefix_on"]
    if off["kv_live_peak_bytes"]:
        detail["live_peak_ratio_prefix"] = round(
            on["kv_live_peak_bytes"] / off["kv_live_peak_bytes"], 3)
        # physical pool bytes at peak vs the contiguous arm's live KV:
        # the oversubscription headroom paged KV actually buys
        detail["physical_peak_ratio_paged"] = round(
            detail["paged"]["page_pool_peak_bytes"]
            / off["kv_live_peak_bytes"], 3)
    print(json.dumps(detail), flush=True)
    return _result("serve_mem", f"serve_mem prefix_bytes_saved GPT2-124M "
                   f"{dtype} {n_requests}req shared-{prefix_len}tok-prefix "
                   f"chunk{chunk} slots{n_slots}", headline,
                   unit="bytes", detail=detail)


def _spec_bench_model(ctx=128, train_steps=60, period=7, seed=0):
    """A tiny byte-ish model TRAINED briefly on a cyclic token stream —
    the honest 'repetitive/greedy workload' for the speculative-decoding
    A/B. An untrained model's greedy output is position-dependent noise
    (random learned positions), which no self-history drafter can
    predict; ~30 train steps on a short cycle make greedy decode
    actually CONTINUE the cycle, so the n-gram drafter earns its
    acceptance the same way it does on real templated/extractive
    traffic. Returns (cfg, trained_params, token_stream)."""
    from building_llm_from_scratch_tpu.configs import ModelConfig
    from building_llm_from_scratch_tpu.models import init_params
    from building_llm_from_scratch_tpu.training import (
        build_optimizer,
        init_train_state,
        make_train_step,
    )

    cfg = ModelConfig(name="spec-bench-tiny", vocab_size=96,
                      context_length=ctx, emb_dim=32, n_heads=2,
                      n_layers=2, hidden_dim=64, n_kv_groups=2,
                      norm="layernorm", positional="learned",
                      activation="gelu", drop_rate=0.0, eos_id=1)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(seed)
    cycle = rng.integers(2, cfg.vocab_size, (period,)).astype(np.int32)
    stream = np.tile(cycle, (4 * ctx) // period + 2)

    def batch(bs=4):
        starts = rng.integers(0, period, (bs,))
        rows = np.stack([stream[s: s + ctx + 1] for s in starts])
        return {"inputs": rows[:, :-1].astype(np.int32),
                "targets": rows[:, 1:].astype(np.int32),
                "weights": np.ones((bs, ctx), np.float32)}

    opt = build_optimizer(total_steps=train_steps + 2)
    state = init_train_state(params, opt, jax.random.PRNGKey(0))
    step = make_train_step(cfg, opt)
    for _ in range(train_steps):
        state, m = step(state, batch())
    jax.device_get(m["loss"])
    return cfg, state["trainable"], stream


def bench_serve_spec(n_requests=8, max_new=96, prompt_len=24, n_slots=4,
                     ks=(2, 4, 8)):
    """Speculative-decoding A/B (serving/spec.py + verify_slots): the
    SAME repetitive greedy request set decoded spec-off vs spec-on at
    k in ``ks`` — per arm: decode tok/s, TPOT p50/p95 (the per-token
    latency speculation exists to attack), acceptance rate, recompiles.

    The workload is what prompt-lookup drafting is FOR: a briefly
    trained tiny model whose greedy continuation repeats its context
    (templated prompts / extraction / code in miniature) — see
    ``_spec_bench_model``. Tokens are bit-identical across arms (the
    accept rule is exact; test-pinned in tests/test_spec.py), so every
    arm decodes the same work. Acceptance bar: >= 1.3x decode tok/s at
    k=4 with ZERO recompiles across acceptance churn.

    CPU numbers (tiny model, dispatch-bound ticks) UNDERSTATE the TPU
    win: there decode is weight-streaming-bound, so k+1 verify
    positions cost ~one decode step while committing up to k+1
    tokens."""
    import time

    from building_llm_from_scratch_tpu.generate import _bucket
    from building_llm_from_scratch_tpu.serving import (
        DecodeEngine,
        SamplingParams,
    )

    if _QUICK:
        n_requests, max_new = min(n_requests, 4), min(max_new, 16)
    t_train = time.perf_counter()
    # quick mode also trims the drafter-training iterations (acceptance
    # drops a little; the fingerprint-relevant shapes are unchanged)
    cfg, params, stream = _spec_bench_model(
        train_steps=20 if _QUICK else 60)
    train_s = time.perf_counter() - t_train
    prompts = [stream[s: s + prompt_len].astype(np.int32)
               for s in range(n_requests)]
    sp = SamplingParams(max_new_tokens=max_new, ignore_eos=True)

    def run_arm(spec_k):
        eng = DecodeEngine(cfg, params, n_slots=n_slots,
                           max_queue=n_requests,
                           max_len=_bucket(prompt_len + max_new),
                           warmup_prompt_cap=prompt_len, spec_k=spec_k)
        eng.warmup()
        t0 = time.perf_counter()
        handles = [eng.submit(p, sp, block=True) for p in prompts]
        eng.run_until_idle()
        dt = time.perf_counter() - t0
        for h in handles:
            assert len(h.output_ids) == max_new, h.finish_reason
        stats = eng.stats()
        # exact per-request TPOT (the engine histogram's sub-ms buckets
        # are too coarse to resolve a tiny model's per-token latency)
        tpots = [t for t in (h.tpot_s() for h in handles)
                 if t is not None]
        row = {
            "tok_s": round(n_requests * max_new / dt, 1),
            "ticks": stats["n_ticks"],
            "tpot_mean_ms": round(1e3 * float(np.mean(tpots)), 4),
            "recompiles": eng.n_recompiles,
        }
        if spec_k:
            row["acceptance"] = stats.get("spec_acceptance_ratio", 0.0)
            row["drafted"] = stats.get("spec_tokens_drafted", 0)
            row["accepted"] = stats.get("spec_tokens_accepted", 0)
        assert eng.n_recompiles == 0, "spec traffic recompiled"
        eng.shutdown()
        return row

    detail = {"train_seconds": round(train_s, 2),
              "spec_off": run_arm(0)}
    headline = None
    for k in ks:
        detail[f"spec_k{k}"] = run_arm(k)
        if k == 4:
            headline = detail["spec_k4"]["tok_s"]
    off = detail["spec_off"]
    if "spec_k4" in detail:
        on = detail["spec_k4"]
        detail["decode_tok_s_speedup_k4"] = round(
            on["tok_s"] / off["tok_s"], 2)
        if off.get("tpot_mean_ms") and on.get("tpot_mean_ms"):
            detail["tpot_speedup_k4"] = round(
                off["tpot_mean_ms"] / on["tpot_mean_ms"], 2)
    print(json.dumps(detail), flush=True)
    return _result("serve_spec", f"serve_spec tokens/sec spec-bench-tiny "
                   f"fp32 {n_requests}req x {max_new}new repetitive-greedy "
                   "slots4 k4", headline, unit="tokens/sec", detail=detail)


def _fleet_batches(cfg, k, rows, seed=0):
    """Per-job synthetic SFT batches (random tokens, Alpaca-style
    prompt-half loss mask) — the same rows feed both A/B arms."""
    rng = np.random.default_rng(seed)
    T = cfg.context_length
    out = []
    for _ in range(k):
        w = np.ones((rows, T), np.float32)
        w[:, : T // 2] = 0.0
        out.append({
            "inputs": rng.integers(0, cfg.vocab_size,
                                   (rows, T)).astype(np.int32),
            "targets": rng.integers(0, cfg.vocab_size,
                                    (rows, T)).astype(np.int32),
            "weights": w,
        })
    return out


def bench_lora_fusion(k=4, rows=2, rank=4, n_steps=12):
    """Fused multi-LoRA training A/B (training/lora_fusion.py): train the
    SAME k jobs (identical per-job batches, rank, hyperparameters)
    (a) the pre-fusion way — k sequential solo LoRA finetune runs, each
    its own merged-weights train step, its own XLA compile, its own
    dispatch stream — vs (b) ONE fused run whose step carries all k
    jobs' rows with per-row job_ids, gradients flowing only to the
    stacked adapter pool.

    Debug-size on CPU (the micro-bench convention), sized like real
    tenant jobs: small per-job batches, short horizons. The HEADLINE is
    aggregate adapter-training throughput for the WHOLE FLEET — fleet
    tokens / fleet wall, where each solo finetune is a fresh run and so
    pays its own compile (that is what 'k sequential solo finetunes'
    costs; the fused service compiles once, ever, and every later tenant
    hot-joins the same program). Also reported: steady-state tok/s per
    arm (compile excluded — on CPU this is compute-bound and near-even;
    the fused win there is the HLO FLOPs line, not wall), and the HLO
    cost-analysis FLOPs: fused FLOPs/step vs k x solo FLOPs/step — < 1.0
    because the frozen base never materializes dense weight gradients
    (the merged solo path pays the full dW as the merge chain's backward
    intermediate: ~6N vs ~4N per token)."""
    from building_llm_from_scratch_tpu.configs import get_config
    from building_llm_from_scratch_tpu.models import init_params
    from building_llm_from_scratch_tpu.models.lora import init_lora_params
    from building_llm_from_scratch_tpu.obs.compile import CompileWatcher
    from building_llm_from_scratch_tpu.training import (
        build_optimizer,
        init_train_state,
        make_train_step,
    )
    from building_llm_from_scratch_tpu.training.lora_fusion import (
        init_fleet_state,
        make_fused_train_step,
    )

    if _QUICK:
        n_steps = min(n_steps, 6)
    alpha = 2.0 * rank
    cfg = get_config("GPT2", "124M", dtype="fp32", debug=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    T = cfg.context_length
    batches = _fleet_batches(cfg, k, rows)
    fleet_tokens = k * rows * T * n_steps

    # -- arm A: k sequential solo finetunes (merged-lora step each) ------
    solo_steady_s, solo_total_s, solo_flops = 0.0, 0.0, None
    for j in range(k):
        t_run = time.perf_counter()
        opt = build_optimizer(total_steps=n_steps + 2)
        lora = init_lora_params(cfg, params, jax.random.PRNGKey(10 + j),
                                rank=rank)
        # the donated step consumes the state's buffers — every solo run
        # (and the fused arm after them) needs the base params alive
        state = init_train_state(
            lora, opt, jax.random.PRNGKey(j),
            frozen=jax.tree_util.tree_map(lambda x: x.copy(), params))
        step = CompileWatcher(
            make_train_step(cfg, opt, lora_rank=rank, lora_alpha=alpha),
            label="solo_step")
        state, m = step(state, batches[j])      # compile + warm
        float(jax.device_get(m["loss"]))
        t0 = time.perf_counter()
        for _ in range(n_steps):
            state, m = step(state, batches[j])
        float(jax.device_get(m["loss"]))
        solo_steady_s += time.perf_counter() - t0
        solo_total_s += time.perf_counter() - t_run
        if solo_flops is None:
            solo_flops = step.hlo_flops_per_step

    # -- arm B: one fused run, all k jobs per step -----------------------
    t_run = time.perf_counter()
    fstate = init_fleet_state(cfg, params, capacity=k,
                              rng=jax.random.PRNGKey(0), rank=rank)
    for j in range(k):
        lora = init_lora_params(cfg, params, jax.random.PRNGKey(10 + j),
                                rank=rank)
        fstate["trainable"] = jax.tree_util.tree_map(
            lambda pool, leaf, j=j: pool.at[j].set(leaf),
            fstate["trainable"], lora)
    from building_llm_from_scratch_tpu.training.lora_fusion import (
        stack_fleet_batch,
    )

    fbatch = stack_fleet_batch(batches, capacity=k, scaling=alpha / rank,
                               horizon=n_steps + 2)
    fstep = CompileWatcher(make_fused_train_step(cfg, capacity=k),
                           label="fused_step")
    fstate, fm = fstep(fstate, fbatch)          # compile + warm
    jax.device_get(fm["loss"])
    t0 = time.perf_counter()
    for _ in range(n_steps):
        fstate, fm = fstep(fstate, fbatch)
    jax.device_get(fm["loss"])
    fused_steady_s = time.perf_counter() - t0
    fused_total_s = time.perf_counter() - t_run
    fused_flops = fstep.hlo_flops_per_step

    detail = {
        "k": k, "rows_per_job": rows, "rank": rank, "n_steps": n_steps,
        "solo_sequential": {
            "fleet_tok_s": round(fleet_tokens / solo_total_s, 1),
            "steady_tok_s": round(fleet_tokens / solo_steady_s, 1),
            "fleet_wall_s": round(solo_total_s, 3),
            "flops_per_step": solo_flops,
        },
        "fused": {
            "fleet_tok_s": round(fleet_tokens / fused_total_s, 1),
            "steady_tok_s": round(fleet_tokens / fused_steady_s, 1),
            "fleet_wall_s": round(fused_total_s, 3),
            "flops_per_step": fused_flops,
            "recompiles": fstep.n_recompiles,
        },
        "agg_throughput_speedup": round(solo_total_s / fused_total_s, 2),
        "steady_state_speedup": round(solo_steady_s / fused_steady_s, 2),
    }
    if solo_flops and fused_flops:
        # fused step carries k jobs' tokens; k solo steps carry the same —
        # < 1.0 means the shared frozen base is cheaper fused than merged
        detail["fused_flops_vs_k_solo_steps"] = round(
            fused_flops / (k * solo_flops), 3)
        detail["per_token_flops_ratio"] = round(
            (fused_flops / (k * rows * T)) / (solo_flops / (rows * T)), 3)
    print(json.dumps(detail), flush=True)
    return _result("lora_fusion", f"fused multi-LoRA agg adapter-train "
                   f"tokens/sec (fleet wall) GPT2-debug fp32 k{k} x "
                   f"{rows}rows rank{rank}",
                   fleet_tokens / fused_total_s, unit="tokens/sec",
                   detail=detail)


# ---------------------------------------------------------------------------
# Micro-benches: the CI perf-gate workloads (scripts/perf_gate.py)
# ---------------------------------------------------------------------------

def bench_micro_train():
    """Debug-size GPT2 raw train step (ctx 16, emb 32, 2 layers): seconds
    on CPU, so the structural perf gate can run it on every CI pass. The
    tok/s number is meaningless as throughput — what matters is the
    fingerprint: the step's HLO FLOPs, program count and HBM breakdown
    must match PERF_BASELINE.json exactly."""
    from building_llm_from_scratch_tpu.configs import get_config

    cfg = get_config("GPT2", "124M", dtype="fp32", debug=True)
    tps = _pretrain_tps(cfg, batch_size=4, warmup=1, iters=4)
    return _result("micro_train", "tokens/sec GPT2-debug pretrain fp32 "
                   "bs4 ctx16", tps, unit="tokens/sec")


def bench_micro_accum():
    """Debug-size grad-accum step (2 scanned microbatches): a second,
    structurally DIFFERENT program for the gate — accumulation bugs that
    change the compiled graph (a dropped scan, a dtype drift in the
    accumulator) show up as a FLOP/memory diff here."""
    from building_llm_from_scratch_tpu.configs import get_config

    cfg = get_config("GPT2", "124M", dtype="fp32", debug=True)
    tps = _pretrain_tps(cfg, batch_size=8, warmup=1, iters=4, grad_accum=2)
    return _result("micro_accum", "tokens/sec GPT2-debug pretrain fp32 "
                   "bs8 grad_accum2 ctx16", tps, unit="tokens/sec")


def bench_micro_serve():
    """Debug-size continuous-batching engine (2 slots, 6 requests): the
    gate workload for the serving tier — its fingerprint covers the
    engine's whole compiled-program family (bucketed prefill + decode),
    so a bucket-set change, an extra program, or a warmup recompile
    fails the structural gate with the program named."""
    from building_llm_from_scratch_tpu.configs import get_config
    from building_llm_from_scratch_tpu.models import init_params
    from building_llm_from_scratch_tpu.serving import (
        DecodeEngine,
        SamplingParams,
    )

    n_requests, max_new, prompt_len = 6, 4, 4
    cfg = get_config("GPT2", "124M", dtype="fp32", debug=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size,
                           (n_requests, prompt_len)).astype(np.int32)
    sp = SamplingParams(max_new_tokens=max_new, ignore_eos=True)
    engine = DecodeEngine(cfg, params, n_slots=2, max_queue=n_requests,
                          warmup_prompt_cap=prompt_len, metrics_every=2)
    engine.warmup()
    t0 = time.perf_counter()
    handles = [engine.submit(p, sp, block=True) for p in prompts]
    engine.run_until_idle()
    dt = time.perf_counter() - t0
    for h in handles:
        assert len(h.output_ids) == max_new, h.finish_reason
    detail = {"recompiles": engine.n_recompiles}
    engine.shutdown()
    return _result("micro_serve", "serve tokens/sec GPT2-debug fp32 "
                   f"{n_requests}req x {max_new}new slots2",
                   n_requests * max_new / dt, unit="tokens/sec",
                   detail=detail)


def bench_micro_paged():
    """Debug-size paged-KV engine (2 slots, 6 shared-prefix requests):
    the gate workload for the page-table serving tier — its fingerprint
    covers the paged compiled-program family (paged chunk prefill +
    paged decode), so page-identity leaking into shapes (a table-churn
    recompile), an extra program, or FLOP growth in the gather path
    fails the structural gate with the program named."""
    from building_llm_from_scratch_tpu.configs import get_config
    from building_llm_from_scratch_tpu.models import init_params
    from building_llm_from_scratch_tpu.serving import (
        DecodeEngine,
        KVCachePolicy,
        SamplingParams,
    )

    n_requests, max_new = 6, 4
    cfg = get_config("GPT2", "124M", dtype="fp32", debug=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prefix = rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32)
    prompts = [np.concatenate([prefix, rng.integers(
        0, cfg.vocab_size, (1,)).astype(np.int32)])
        for _ in range(n_requests)]
    sp = SamplingParams(max_new_tokens=max_new, ignore_eos=True)
    policy = KVCachePolicy(paged=True, page_tokens=8, prefill_chunk=8,
                           prefix_cache=True)
    engine = DecodeEngine(cfg, params, n_slots=2, max_queue=n_requests,
                          warmup_prompt_cap=9, kv_policy=policy,
                          metrics_every=2)
    engine.warmup()
    t0 = time.perf_counter()
    handles = [engine.submit(p, sp, block=True) for p in prompts]
    engine.run_until_idle()
    dt = time.perf_counter() - t0
    for h in handles:
        assert len(h.output_ids) == max_new, h.finish_reason
    assert engine.pane_copies == 0, "paged hit copied panes"
    detail = {"recompiles": engine.n_recompiles,
              "prefix_hits": engine.prefix_store.stats()["hits"],
              "page_pool": engine.page_pool.stats()}
    engine.shutdown()
    return _result("micro_paged", "paged serve tokens/sec GPT2-debug "
                   f"fp32 {n_requests}req x {max_new}new slots2 page8",
                   n_requests * max_new / dt, unit="tokens/sec",
                   detail=detail)


def bench_micro_lora_fusion():
    """Debug-size fused multi-LoRA train step (2 jobs x 2 rows, rank 4):
    the gate workload for the fused-finetune tier. Its fingerprint pins
    the fused step's HLO — a lost gather (adapters silently merged), a
    dense base-weight gradient sneaking into the backward, or a
    per-job-identity recompile all show up as FLOP/program diffs with
    the program named."""
    from building_llm_from_scratch_tpu.configs import get_config
    from building_llm_from_scratch_tpu.models import init_params
    from building_llm_from_scratch_tpu.obs.compile import CompileWatcher
    from building_llm_from_scratch_tpu.training.lora_fusion import (
        init_fleet_state,
        make_fused_train_step,
        stack_fleet_batch,
    )

    k, rows, rank = 2, 2, 4
    cfg = get_config("GPT2", "124M", dtype="fp32", debug=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    T = cfg.context_length
    batches = _fleet_batches(cfg, k, rows)
    state = init_fleet_state(cfg, params, capacity=k, rank=rank,
                             rng=jax.random.PRNGKey(0))
    batch = stack_fleet_batch(batches, capacity=k, scaling=2.0, horizon=8)
    step = CompileWatcher(make_fused_train_step(cfg, capacity=k),
                          label="fused_step")
    warmup, iters = _q_iters(1, 4)
    for _ in range(max(1, warmup)):
        state, m = step(state, batch)
    jax.device_get(m["loss"])
    t0 = time.perf_counter()
    for _ in range(iters):
        state, m = step(state, batch)
    jax.device_get(m["loss"])
    dt = time.perf_counter() - t0
    return _result("micro_lora_fusion", "fused multi-LoRA tokens/sec "
                   f"GPT2-debug fp32 k{k} x {rows}rows rank{rank} ctx16",
                   k * rows * T * iters / dt, unit="tokens/sec",
                   detail={"recompiles": step.n_recompiles})


def bench_micro_spec():
    """Debug-size speculative serving engine (2 slots, 6 requests,
    k=4): the gate workload for the spec tier — its fingerprint pins
    the Tq=k+1 verify program's HLO next to the bucketed prefill, so a
    verify-graph change (a lost candidate position, an accidental extra
    program, a warmup recompile, acceptance leaking into a compile
    signature) fails the structural gate with the program named. The
    model is untrained (acceptance ~0 — irrelevant: structure only)."""
    from building_llm_from_scratch_tpu.configs import get_config
    from building_llm_from_scratch_tpu.models import init_params
    from building_llm_from_scratch_tpu.serving import (
        DecodeEngine,
        SamplingParams,
    )

    n_requests, max_new, prompt_len = 6, 4, 4
    cfg = get_config("GPT2", "124M", dtype="fp32", debug=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size,
                           (n_requests, prompt_len)).astype(np.int32)
    sp = SamplingParams(max_new_tokens=max_new, ignore_eos=True)
    engine = DecodeEngine(cfg, params, n_slots=2, max_queue=n_requests,
                          warmup_prompt_cap=prompt_len, metrics_every=2,
                          spec_k=4)
    engine.warmup()
    t0 = time.perf_counter()
    handles = [engine.submit(p, sp, block=True) for p in prompts]
    engine.run_until_idle()
    dt = time.perf_counter() - t0
    for h in handles:
        assert len(h.output_ids) == max_new, h.finish_reason
    detail = {"recompiles": engine.n_recompiles,
              "acceptance": engine.stats().get("spec_acceptance_ratio",
                                               0.0)}
    engine.shutdown()
    return _result("micro_spec", "serve tokens/sec GPT2-debug fp32 "
                   f"{n_requests}req x {max_new}new slots2 spec-k4",
                   n_requests * max_new / dt, unit="tokens/sec",
                   detail=detail)


def bench_micro_longctx(sp=2, warmup=1, iters=4):
    """Debug-size sequence-sharded train step (longctx-32k architecture
    shrunk, sp=2 over the seq mesh axis): the gate workload for the
    long-context tier — its fingerprint pins the ring-attention step's
    HLO (the ppermute ring schedule, the online-softmax rescale chain,
    the seq-sharded batch signature) so a ring-graph change, a dropped
    collective, or a signature-churn recompile fails the structural
    gate with the program named. Needs a multi-device host: the gate
    (scripts/perf_gate.py) forces 8 CPU devices before importing jax."""
    from building_llm_from_scratch_tpu.configs import get_config
    from building_llm_from_scratch_tpu.models import init_params
    from building_llm_from_scratch_tpu.obs.compile import CompileWatcher
    from building_llm_from_scratch_tpu.parallel import build_mesh_plan
    from building_llm_from_scratch_tpu.training import (
        build_optimizer,
        init_train_state,
        make_train_step,
    )

    if jax.device_count() < 2:
        raise RuntimeError(
            "micro_longctx needs >= 2 devices for the seq mesh axis; "
            "run under XLA_FLAGS=--xla_force_host_platform_device_count=8 "
            "(scripts/perf_gate.py sets this itself).")
    cfg = get_config("longctx", "32k", dtype="fp32", debug=True)
    batch_size = 4                      # divides the data axis (8/sp)
    plan = build_mesh_plan("dp", sp=sp)
    opt = build_optimizer(total_steps=warmup + iters + 1)
    state = plan.shard_state(init_train_state(
        init_params(cfg, jax.random.PRNGKey(0)), opt, jax.random.PRNGKey(0)))
    batch = plan.shard_batch(_batch(cfg, batch_size))
    step = CompileWatcher(make_train_step(cfg, opt, sp_mesh=plan.sp_mesh),
                          label="longctx_step")
    warmup, iters = _q_iters(warmup, iters)
    dt = _time_steps(step, state, batch, warmup, iters)
    assert step.n_recompiles == 0, step.n_recompiles
    return _result("micro_longctx", "tokens/sec longctx-debug pretrain "
                   f"fp32 bs{batch_size} ctx{cfg.context_length} sp{sp}",
                   batch_size * cfg.context_length * iters / dt,
                   unit="tokens/sec",
                   detail={"sp": sp, "mesh": dict(plan.mesh.shape)})


def _longctx_worker(arm: str, extra_args, timeout=1800) -> dict:
    """Run one scripts/bench_longctx_worker.py arm (subprocess: the arm
    needs a forced multi-device host set before jax imports; the parent
    bench process's device count is pinned by the perf-gate baselines)."""
    import subprocess

    repo = os.path.dirname(os.path.abspath(__file__))
    worker = os.path.join(repo, "scripts", "bench_longctx_worker.py")
    env = _cpu_child_env(f"longctx {arm}")
    cmd = [sys.executable, worker, "--arm", arm] + \
        [str(a) for a in extra_args]
    proc = subprocess.run(cmd, capture_output=True, text=True,
                          timeout=timeout, env=env)
    if proc.returncode != 0:
        raise RuntimeError(f"longctx worker ({arm}) failed rc="
                           f"{proc.returncode}:\n{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def bench_pretrain_longctx(ctx=1024, sp=4, steps=3, batch=4):
    """Long-context pretrain A/B (ROADMAP item 2): the SAME batches
    through an unsharded reference step and a sequence-sharded one
    (dp x sp mesh, ring attention). Asserts the loss trajectories agree
    to rtol 2e-4 — NOT bit-identical, and deliberately so: the ring's
    online softmax reduces KV panes in ring order while the dense
    reference reduces the full row at once, a floating-point
    REASSOCIATION of the same sum (the pinned tolerance matches
    tests/test_ring_attention.py's parity suite) — and that neither arm
    recompiles after step 1. Both arms run on a virtual CPU mesh, where
    the sp arm is SLOWER (host collectives, no real interconnect): the
    tok/s of either says nothing about a chip."""
    steps = max(2, min(steps, 2) if _QUICK else steps)
    row = _longctx_worker("train", ["--sp", sp, "--ctx", ctx,
                                    "--steps", steps, "--batch", batch])
    rel = max(abs(a - b) / max(abs(b), 1e-9)
              for a, b in zip(row["losses_sp"], row["losses_ref"]))
    assert rel <= 2e-4, (rel, row)
    assert row["recompiles_ref"] == 0, row
    assert row["recompiles_sp"] == 0, row
    print(json.dumps(row), flush=True)
    res = _result("pretrain_longctx",
                  f"tokens/sec longctx pretrain fp32 bs{batch} "
                  f"ctx{row['ctx']} sp{sp} vs unsharded ref",
                  row["tok_s_sp"], unit="tokens/sec", detail=row)
    res.add_metric("tok_s_ref", row["tok_s_ref"], "tokens/sec")
    res.add_metric("loss_parity_max_rel", round(rel, 9), "fraction")
    res.add_metric("recompiles_sp", row["recompiles_sp"], "count")
    return res


def bench_serve_longctx(sp=2, max_len=512, n_long=4, n_short=8,
                        max_new=16):
    """Seq-sharded prefill under mixed traffic: one sp=2 engine serving
    interleaved long prompts (384 tokens — beyond one device's 256-token
    pane, the admission the long-context tier exists for) and short
    ones. Asserts zero post-warmup recompiles (the sharding constraint
    is static — long prompts reuse the same chunk program) and reports
    the long-vs-short TTFT split next to aggregate tok/s."""
    if _QUICK:
        n_long, n_short, max_new = 2, 4, 8
    row = _longctx_worker("serve", ["--sp", sp, "--max_len", max_len,
                                    "--n_long", n_long,
                                    "--n_short", n_short,
                                    "--max_new", max_new])
    assert row["recompiles"] == 0, row
    assert row["n_long"] == n_long and row["n_short"] == n_short, row
    print(json.dumps(row), flush=True)
    res = _result("serve_longctx",
                  f"serve tokens/sec GPT2-124M sp{sp} mixed traffic "
                  f"{n_long}long+{n_short}short maxlen{max_len}",
                  row["tok_s"], unit="tokens/sec", detail=row)
    res.add_metric("ttft_long_p50", row["ttft_long_p50"], "seconds")
    res.add_metric("ttft_short_p50", row["ttft_short_p50"], "seconds")
    res.add_metric("max_prompt", row["max_prompt"], "tokens")
    return res


BENCHES = {
    "headline": bench_headline,
    "cfg1": bench_cfg1,
    "cfg2": bench_cfg2,
    "cfg3": bench_cfg3,
    "cfg4": bench_cfg4,
    "cfg5": bench_cfg5,
    "accum": bench_accum,
    "trainer": bench_trainer,
    "prefetch": bench_prefetch,
    "decode": bench_decode,
    "serve": bench_serve,
    "serve_load": bench_serve_load,
    "serve_fleet": bench_serve_fleet,
    "serve_lora": bench_serve_lora,
    "serve_prefix": bench_serve_prefix,
    "serve_mem": bench_serve_mem,
    "serve_spec": bench_serve_spec,
    "lora_fusion": bench_lora_fusion,
    "micro_train": bench_micro_train,
    "micro_accum": bench_micro_accum,
    "micro_serve": bench_micro_serve,
    "micro_paged": bench_micro_paged,
    "micro_lora_fusion": bench_micro_lora_fusion,
    "micro_spec": bench_micro_spec,
    "micro_router": bench_micro_router,
    "micro_longctx": bench_micro_longctx,
    "pretrain_longctx": bench_pretrain_longctx,
    "serve_longctx": bench_serve_longctx,
}

#: Micro-benches excluded from ``all`` (they are gate workloads, not
#: performance claims — their tok/s on a debug model means nothing).
#: micro_longctx additionally needs a multi-device host (the gate
#: forces one; plain ``bench.py all`` runs may not have it).
MICRO_BENCHES = ("micro_train", "micro_accum", "micro_serve",
                 "micro_paged", "micro_lora_fusion", "micro_spec",
                 "micro_router", "micro_longctx")


def run_bench(name: str, repeats: int = 1, quick: bool = False
              ) -> perf.BenchResult:
    """Run one bench ``repeats`` times; returns the final repeat's
    BenchResult carrying repeat stats over the headline values, the env
    block, and the structural fingerprint (obs/perf.py). The programmatic
    entry the perf gate uses — ``run()`` is the printing CLI wrapper."""
    global _QUICK
    prev_quick, _QUICK = _QUICK, bool(quick)
    fn = BENCHES[name]
    try:
        values, results, digests = [], [], []
        for _ in range(max(1, int(repeats))):
            with perf.FingerprintCollector() as col:
                res = fn()
            if not isinstance(res, perf.BenchResult):
                raise TypeError(f"bench '{name}' must return a BenchResult,"
                                f" got {type(res).__name__}")
            res.fingerprint = col.fingerprint()
            digests.append(perf.fingerprint_digest(res.fingerprint))
            values.append(res.value)
            results.append(res)
    finally:
        _QUICK = prev_quick
    final = results[-1]
    final.repeats = perf.repeat_stats(values)
    # a fingerprint that drifts BETWEEN repeats of the same bench is a
    # nondeterministic compile (data-dependent shapes, a cache-warmup
    # recompile) — exactly what the gate exists to catch, so record it
    final.fingerprint["stable_across_repeats"] = len(set(digests)) == 1
    final.env = perf.bench_env()
    final.quick = bool(quick)
    final.time = time.time()
    perf.emit_bench_result(final)
    return final


def _legacy_line(res: perf.BenchResult) -> dict:
    """The one-JSON-line stdout format: metric/value/unit (+mfu and the
    HLO efficiency fields when the capture produced them)."""
    line = {
        "metric": res.metric,
        "value": round(res.value, 1),
        "unit": res.unit,
    }
    mfu = res.metric_value("mfu")
    if mfu is not None:
        line["mfu"] = round(mfu, 3)
    fp = res.fingerprint or {}
    # the chronologically LAST bench_step capture is the executable the
    # timed loop actually ran (after any mid-run recompile); the sorted
    # programs list is the deterministic fallback
    last = fp.get("last_program")
    step_progs = [p for p in ([last] if last else [])
                  + list(fp.get("programs", ()))
                  if p["label"] == "bench_step" and p.get("flops")]
    if step_progs:
        from building_llm_from_scratch_tpu.obs.mfu import mfu_from_flops

        prog = step_progs[0]
        line["hlo_flops_per_step"] = prog["flops"]
        compile_s = (res.fingerprint.get("timing") or {}).get(
            "compile_seconds_total")
        if compile_s is not None:
            line["compile_seconds"] = round(compile_s, 2)
        if prog.get("tokens_per_step"):
            # per-chip tps against the device's own peak (None, and so no
            # line, off-TPU: the CPU micro benches have no utilization),
            # with XLA's counted FLOPs — the delta vs "mfu" is formula
            # drift
            mfu_hlo = mfu_from_flops(
                res.value, prog["flops"] / prog["tokens_per_step"],
                n_devices=1)
            if mfu_hlo is not None:
                line["mfu_hlo"] = round(mfu_hlo, 3)
    if res.repeats and res.repeats.get("n", 1) > 1:
        line["repeats"] = {k: res.repeats[k]
                           for k in ("n", "min", "median", "stddev")}
    return line


def run(name: str, repeats: int = 1, quick: bool = False,
        json_out=None) -> perf.BenchResult:
    res = run_bench(name, repeats=repeats, quick=quick)
    print(json.dumps(_legacy_line(res)), flush=True)
    if json_out is not None:
        json_out.write(json.dumps(res.to_row(), sort_keys=True) + "\n")
        json_out.flush()
    return res


def _open_json_out(path: str, name: str):
    """``--json`` sink: a directory gets the trajectory layout (one
    ``<name>.jsonl`` per bench, appended — the results/perf convention);
    a file path gets every row plus one run-metadata header. A
    not-yet-existing extensionless path (``--json results/perf``) is
    treated as a directory — writing a FILE named like the intended
    trajectory dir would break every later store open against it."""
    if (os.path.isdir(path) or path.endswith(os.sep)
            or "." not in os.path.basename(path)):
        store = perf.TrajectoryStore(path.rstrip(os.sep))
        os.makedirs(store.root, exist_ok=True)
        return open(store.path(name), "a")
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    f = open(path, "a")
    if f.tell() == 0:
        f.write(json.dumps(perf.header_row(), sort_keys=True) + "\n")
    return f


def main(argv):
    from building_llm_from_scratch_tpu.utils.seeding import (
        configure_default_prng,
    )

    p = argparse.ArgumentParser(
        description="bench runner (see module docstring)")
    p.add_argument("which", nargs="?", default="headline",
                   help="bench name from BENCHES, or 'all'")
    p.add_argument("--repeats", type=int, default=1, metavar="K",
                   help="repeat each bench K times; rows carry "
                        "min/median/stddev stats")
    p.add_argument("--json", default=None, metavar="OUT",
                   help="append BenchResult JSONL rows to OUT (a "
                        "*.json/*.jsonl file gets rows + one header; "
                        "anything else is a directory and gets the "
                        "results/perf one-file-per-bench trajectory "
                        "layout)")
    p.add_argument("--quick", action="store_true",
                   help="shrink iteration counts (CI gate mode; shapes — "
                        "and so fingerprints — are unchanged)")
    args = p.parse_args(argv[1:])

    from building_llm_from_scratch_tpu.obs import configure_compile_cache

    configure_compile_cache()
    configure_default_prng()   # rbg PRNG: dropout at full speed (seeding.py)
    # run-metadata header FIRST (jax version, backend, device kind/count,
    # git sha, argv): the BENCH_*.json driver snapshots capture stdout, so
    # every archived bench line is self-describing about where it ran
    print(json.dumps(perf.header_row(), sort_keys=True), flush=True)
    names = list(BENCHES) if args.which == "all" else [args.which]
    if args.which == "all":
        names = [n for n in names if n not in MICRO_BENCHES]
    for name in names:
        if name not in BENCHES:
            p.error(f"unknown bench '{name}' "
                    f"(choose from {', '.join(BENCHES)})")
        json_out = (_open_json_out(args.json, name)
                    if args.json else None)
        try:
            run(name, repeats=args.repeats, quick=args.quick,
                json_out=json_out)
        finally:
            if json_out is not None:
                json_out.close()


if __name__ == "__main__":
    main(sys.argv)
