#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two main paths once on ONE TPU chip, through the entry point a
user calls (``building_llm_from_scratch_tpu.main.main(get_args([...]))`` —
the path of ``python -m building_llm_from_scratch_tpu``), at GPT2-124M's
published widths (768 x 12 layers x 12 heads, ctx 1024, vocab 50257), bf16,
random-init weights and synthetic data made from a fixed seed:

  train    a few dozen optimizer steps at batch 8 with the default
           ``--attn_impl auto`` and default dropout (the fused attention and
           fused dropout kernels), eval, sample, checkpoint; then a second
           ``main.main`` call that resumes from that checkpoint.
  serve    ``--mode serve`` over a JSONL file of mixed-length greedy and
           sampled requests; greedy output is checked against one-shot
           ``generate()`` and against the one-shot forward's logits.
  kernels  the pallas kernels of the default training path (fused
           attention, fused dropout) and of the decode tick (lane-window
           append, live-block attention) against their XLA references at
           the real shapes, plus the repo's own ``needs_tpu`` test cases,
           in this same process.

``--chips 4`` (builder-run; the driver never passes it) runs ONLY the
multi-chip paths and what they are compared with: the train steps on a
4-device fsdp mesh against the one-device step on the same batches, and the
serve requests through ``--serve_tp 4`` against the one-chip engine.
``--phase train_remat`` runs GPT2-774M bf16 with ``--use_actv_ckpt`` (the
rolled scan + jax.checkpoint branch) for a few steps; as of PR 21 that step
compiles and is refused at load, out of HBM (PERF.md, ROADMAP S2).

Exits non-zero, printing no result line, when JAX finds no TPU, when the
package is not importable, or when any phase fails. One process: this one
holds the chip and starts no other. The LAST line of stdout is one JSON
object: {"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
#: scratch for data, checkpoints and outputs: inside the checkout (git-
#: ignored), removed when the run ends
WORK = os.path.join(HERE, ".chip_smoke")
SEED = 123
GPT2_124M = ["--model", "GPT2", "--num_params", "124M",
             "--mixed_precision", "bf16", "--byte_tokenizer",
             "--seed", str(SEED)]
#: greedy parity, as honest as bf16 allows: a token the engine picked must
#: be the argmax of the one-shot forward's fp32 logits (same bf16 params)
#: up to this many logit units. The engine decodes token by token against
#: a slot-batched KV cache, the reference runs one causal pass through the
#: fused attention kernel; the two round differently (bf16 has 8 mantissa bits: ~0.004
#: at |logit| ~ 1, accumulated over 12 layers), so a near-tie may flip —
#: anything further off is a wrong cache, mask or position. Random-init
#: logits are nearly flat, which makes this the hard case: on the chip the
#: worst token sat 0.0008 below the argmax and 2 of 3 requests matched
#: ``generate()`` token for token (PERF.md, PR 21).
GREEDY_LOGIT_MARGIN = 0.01
#: fsdp-on-4 vs one device, same batches and seed: eval losses (no dropout
#: in eval) may differ by bf16 reduction order and by the per-shard dropout
#: masks the TRAINING steps in between drew
FSDP_LOSS_TOL = 0.05


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class Failure(AssertionError):
    """A phase's check did not hold."""


def check(cond, msg: str) -> None:
    if not cond:
        raise Failure(msg)


# ---------------------------------------------------------------------------
# synthetic inputs, from a seed
# ---------------------------------------------------------------------------

def make_corpus(path: str, n_bytes: int, seed: int = SEED) -> None:
    """Zipf-distributed words over a random lowercase lexicon: cheap,
    seed-determined, and learnable (a byte-level LM's loss falls within a
    few dozen steps)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    lexicon = ["".join(chr(97 + c) for c in rng.integers(0, 26, n))
               for n in rng.integers(2, 10, 2000)]
    ranks = np.minimum(rng.zipf(1.3, n_bytes // 4), len(lexicon)) - 1
    text = " ".join(lexicon[r] for r in ranks)[:n_bytes]
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(text)


#: (prompt tokens, max_new_tokens, temperature): mixed lengths, greedy and
#: sampled, more requests than the engine's 8 default slots so admission
#: happens mid-stream
REQUEST_SHAPES = [(12, 24, 0.0), (90, 24, 0.0), (300, 24, 0.0),
                  (700, 24, 0.0), (40, 48, 0.8), (150, 16, 0.8),
                  (500, 32, 1.0), (25, 8, 0.0), (220, 40, 0.7),
                  (64, 24, 0.0)]


def make_requests(path: str, shapes=REQUEST_SHAPES, seed: int = SEED) -> list:
    import numpy as np

    rng = np.random.default_rng(seed)
    reqs = []
    for i, (n_prompt, n_new, temp) in enumerate(shapes):
        rec = {"prompt_ids": [int(t) for t in rng.integers(32, 127, n_prompt)],
               "max_new_tokens": n_new, "temperature": temp,
               "seed": i + 1, "ignore_eos": True}
        if temp > 0:
            rec["top_k"] = 40
        reqs.append(rec)
    with open(path, "w") as f:
        for rec in reqs:
            f.write(json.dumps(rec) + "\n")
    return reqs


class CacheCounter:
    """Persistent-compilation-cache hits and misses, from jax's own
    monitoring events."""

    def __init__(self):
        import jax.monitoring

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, name: str, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def peak_hbm_gib() -> str:
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return "/".join(f"{s.get('peak_bytes_in_use', float('nan')) / 2**30:.2f}"
                    for s in stats)


def custom_calls(watcher) -> int:
    """tpu_custom_call sites in a CompileWatcher's captured programs — the
    kernels are IN the compiled step, not a reference path."""
    return sum(exe.as_text().count("tpu_custom_call")
               for exe in watcher.executables)


def run_main(argv: list):
    from building_llm_from_scratch_tpu.args import get_args
    from building_llm_from_scratch_tpu.main import main

    return main(get_args(argv))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def train_args(out_dir: str, batch: int, extra=()) -> list:
    return GPT2_124M + [
        "--data_dir", os.path.join(WORK, "data"), "--output_dir", out_dir,
        "--batch_size", str(batch), "--warmup_steps", "5",
        "--eval_freq", "10", *extra]


def check_trainer(trainer, label: str, expect_kernels: bool,
                  max_recompiles: int = 0) -> None:
    import numpy as np

    w = trainer._compile_watcher
    losses = trainer.train_losses + trainer.val_losses
    check(len(trainer.train_losses) >= 2, f"{label}: fewer than two evals")
    check(bool(np.isfinite(losses).all()), f"{label}: non-finite loss")
    check(trainer.train_losses[-1] <= trainer.train_losses[0] + 0.02,
          f"{label}: loss went up: {trainer.train_losses}")
    check(w is not None and w.n_compiles >= 1
          and w.n_recompiles <= max_recompiles,
          f"{label}: train step compiled {w and w.n_compiles}x, "
          f"{w and w.n_recompiles} recompiles (allowed {max_recompiles})")
    n_kernels = custom_calls(w)
    check(n_kernels > 0 or not expect_kernels,
          f"{label}: no tpu_custom_call in the compiled train step")
    log(f"{label}: {trainer.global_step} steps, train loss "
        f"{trainer.train_losses[0]:.3f} -> {trainer.train_losses[-1]:.3f}, "
        f"val {trainer.val_losses[-1]:.3f}, compile "
        f"{w.compile_seconds_total:.1f}s, {w.n_recompiles} recompiles, "
        f"{n_kernels} "
        f"tpu_custom_call, peak HBM {peak_hbm_gib()} GiB")
    setup_log(label)


def setup_log(label: str, record=None) -> None:
    """Where set-up went, by the program's own books (obs/timeline.py):
    ``record``, or the newest one the metrics hub holds (a trainer's)."""
    from building_llm_from_scratch_tpu.obs import (
        get_metrics,
        program_table,
        setup_line,
    )

    if record is None:
        record = (get_metrics().recent("setup") or [None])[-1]
    log(f"{label}: {setup_line(record, program_table())}")


def phase_train(expect_kernels: bool = True, model=(),
                corpus_bytes: int = 400_000) -> None:
    make_corpus(os.path.join(WORK, "data", "corpus.txt"), corpus_bytes)
    out = os.path.join(WORK, "ckpt")
    argv = train_args(out, 8, ["--n_epochs", "1", "--print_sample_iter", "20",
                               "--save_ckpt_freq", "30", *model])
    t0 = time.perf_counter()
    trainer = run_main(argv)
    check_trainer(trainer, "train", expect_kernels)
    steps, tokens = trainer.global_step, trainer.tokens_seen
    ckpts = [n for n in os.listdir(out) if n.startswith("model_pg_")]
    check(len(ckpts) >= 2, f"train: expected a cadence and a final "
                           f"checkpoint, found {ckpts}")
    log(f"train: {time.perf_counter() - t0:.1f}s, checkpoints {sorted(ckpts)}")

    # the same command again: --resume auto (default) picks the final
    # checkpoint up and counts on from its step and tokens
    t0 = time.perf_counter()
    resumed = run_main(argv)
    check(resumed.resume_from is not None
          and resumed.resume_from.endswith("model_pg_final"),
          f"resume: resumed from {resumed.resume_from}")
    check(resumed.global_step > steps and resumed.tokens_seen > tokens,
          f"resume: ended at step {resumed.global_step} / "
          f"{resumed.tokens_seen} tokens, started from {steps} / {tokens}")
    check_trainer(resumed, "resume", expect_kernels)
    log(f"resume: from {os.path.basename(resumed.resume_from)} at step "
        f"{steps}, {time.perf_counter() - t0:.1f}s")


def phase_train_remat() -> None:
    make_corpus(os.path.join(WORK, "data", "corpus.txt"), 400_000)
    argv = [a if a != "124M" else "774M" for a in train_args(
        os.path.join(WORK, "ckpt_remat"), 8,
        ["--use_actv_ckpt", "--n_epochs", "1", "--print_sample_iter",
         "1000", "--save_ckpt_freq", "1000"])]
    t0 = time.perf_counter()
    check_trainer(run_main(argv), "train_remat (GPT2-774M)", True)
    log(f"train_remat: {time.perf_counter() - t0:.1f}s")


def read_results(path: str, n: int, label: str) -> list:
    with open(path) as f:
        results = [json.loads(line) for line in f if line.strip()]
    check(len(results) == n, f"{label}: {len(results)}/{n} result lines")
    for i, r in enumerate(results):
        check("error" not in r, f"{label}: request {i} failed: {r}")
    return results


def serve(label: str, reqs_path: str, n: int, extra=(), model=()):
    out = os.path.join(WORK, f"{label}.out.jsonl")
    t0 = time.perf_counter()
    engine = run_main(GPT2_124M + [
        "--mode", "serve", "--serve_prompts", reqs_path, "--serve_out", out,
        *extra, *model])
    results = read_results(out, n, label)
    serve_line(label, engine, results, t0)
    return engine, results


def serve_line(label: str, engine, results: list, t0: float) -> None:
    log(f"{label}: {len(results)} requests, "
        f"{sum(len(r['token_ids']) for r in results)} tokens, "
        f"{engine.n_recompiles} bucket-miss compiles after warmup, "
        f"kv_append {engine.kv_append}, "
        f"decode_attention {engine.decode_attention}, "
        f"chunk_attention {engine.chunk_attention}, "
        f"linear_attention {engine.linear_attention}, "
        f"state_step {engine.state_step}, "
        f"expert_dispatch {engine.expert_dispatch}, "
        f"{time.perf_counter() - t0:.1f}s, peak HBM {peak_hbm_gib()} GiB")
    setup_log(label, engine.setup_books()["record"])


def check_serve(engine, reqs: list, results: list, label: str,
                n_generate: int) -> None:
    """Every request ran to its length; greedy tokens are argmaxes of the
    one-shot forward (to GREEDY_LOGIT_MARGIN) and, for the first
    ``n_generate`` greedy requests, are compared with ``generate()``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from building_llm_from_scratch_tpu.generate import generate
    from building_llm_from_scratch_tpu.models import forward
    from building_llm_from_scratch_tpu.parallel.collectives import (
        trace_under_mesh,
    )

    cfg, params = engine.cfg, engine.params
    # prompts past the engine's warmup cap compile their bucket on first
    # arrival and report it as a recompile (a bucket miss, by design);
    # anything beyond those is a real one
    # (a chunked prefill has one shape and no bucket to miss)
    misses = (set() if engine.kv_policy.prefill_chunk else
              {engine._bucket_len(len(r["prompt_ids"])) for r in reqs}
              - set(engine.prompt_buckets()))
    check(engine.n_recompiles == len(misses),
          f"{label}: {engine.n_recompiles} recompiles after warmup, "
          f"expected the {len(misses)} bucket misses {sorted(misses)}")
    T = cfg.context_length
    # a tp engine's params live on its mesh: the one-shot forward's fused
    # attention kernel has to see that mesh, like any step over them
    mesh = engine.mesh_plan.mesh if engine.mesh_plan is not None else None
    logits_fn = jax.jit(trace_under_mesh(
        lambda p, t: forward(p, cfg, t), mesh))
    worst, exact, compared = 0.0, 0, 0
    for i, (req, res) in enumerate(zip(reqs, results)):
        toks = res["token_ids"]
        check(len(toks) == req["max_new_tokens"]
              and res["finish_reason"] == "length",
              f"{label}: request {i} stopped early: {res}")
        check(all(0 <= t < cfg.vocab_size for t in toks),
              f"{label}: request {i} token out of range")
        if req["temperature"] > 0:
            continue
        prompt = req["prompt_ids"]
        seq = np.zeros((1, T), np.int32)
        seq[0, :len(prompt) + len(toks)] = prompt + toks
        logits = np.asarray(jax.device_get(
            logits_fn(params, jnp.asarray(seq))), np.float32)[0]
        for j, tok in enumerate(toks):
            row = logits[len(prompt) - 1 + j]
            worst = max(worst, float(row.max() - row[tok]))
        if compared < n_generate:
            compared += 1
            solo = generate(params, cfg, np.asarray(prompt)[None],
                            max_new_tokens=len(toks), eos_id=None,
                            rng=jax.random.PRNGKey(req["seed"]))
            exact += [int(t) for t in solo[0, len(prompt):]] == toks
    log(f"{label}: greedy tokens within {worst:.4f} logit units of the "
        f"one-shot forward's argmax (margin {GREEDY_LOGIT_MARGIN}); "
        f"{exact}/{compared} greedy requests token-identical to generate()")
    check(worst <= GREEDY_LOGIT_MARGIN,
          f"{label}: a greedy token is {worst:.4f} below the one-shot "
          f"argmax (> {GREEDY_LOGIT_MARGIN})")


def phase_serve(model=(), shapes=REQUEST_SHAPES) -> None:
    reqs_path = os.path.join(WORK, "requests.jsonl")
    os.makedirs(WORK, exist_ok=True)
    reqs = make_requests(reqs_path, shapes)
    engine, results = serve("serve", reqs_path, len(reqs), model=model)
    check_serve(engine, reqs, results, "serve", n_generate=3)


#: the parallel block of window and full layers with sparse experts, at the
#: debug size of ``configs.get_config`` (two periods, window 8, 8 experts
#: top-2, 2 shared, context 64)
MOE_DEBUG = ["--model", "command_a_plus", "--num_params", "218B", "--debug"]


def phase_serve_moe(shapes=((5, 12, 0.0), (30, 20, 0.0), (17, 8, 0.8),
                            (40, 16, 0.0), (9, 20, 0.7), (23, 30, 0.0))
                    ) -> None:
    """Rings and experts on the chip, no timing: chunked prefill (chunks of
    4 into rings of 12, which every prompt here wraps) and the decode tick
    of the new block, its greedy tokens held to the one-shot forward and to
    ``generate()`` like the dense model's."""
    reqs_path = os.path.join(WORK, "requests_moe.jsonl")
    os.makedirs(WORK, exist_ok=True)
    reqs = make_requests(reqs_path, shapes)
    engine, results = serve("serve_moe", reqs_path, len(reqs),
                            extra=["--serve_prefill_chunk", "4"],
                            model=MOE_DEBUG)
    layout = engine.layout()
    check(layout["kv_positions"] == {"full": 64, "ring": 12}
          and len(layout["experts"]["held"]) == 8,
          f"serve_moe: the engine's layout is {layout}")
    check_serve(engine, reqs, results, "serve_moe", n_generate=2)


#: gated-delta-rule linear layers beside one gated NoPE attention layer and
#: sparse experts, at the debug size of ``configs.get_config`` (two periods
#: of full, linear, linear, linear; 4 heads of 16; 8 experts top-2, 1 shared,
#: context 64)
HYBRID_DEBUG = ["--model", "solar_open2", "--num_params", "250B", "--debug"]


def phase_serve_hybrid(shapes=((5, 12, 0.0), (30, 20, 0.0), (17, 8, 0.8),
                               (40, 16, 0.0), (9, 20, 0.7), (23, 30, 0.0))
                       ) -> None:
    """A recurrent state beside keys and values on the chip, no timing:
    chunked prefill in chunks of 16 (a chunk boundary inside four of the
    prompts, a padded last chunk in all), the decode tick's one-token step
    of the recurrence, six requests over four slots (so a slot is used
    again and must start from a zero state), greedy tokens held to the
    one-shot forward (the chunked form over the whole sequence) and to
    ``generate()`` like the dense model's."""
    reqs_path = os.path.join(WORK, "requests_hybrid.jsonl")
    os.makedirs(WORK, exist_ok=True)
    reqs = make_requests(reqs_path, shapes)
    engine, results = serve("serve_hybrid", reqs_path, len(reqs),
                            extra=["--serve_prefill_chunk", "16"],
                            model=HYBRID_DEBUG)
    import jax

    layout = engine.layout()
    # any 'linear' state is walked by the decoding rows on a TPU
    walk = "live_rows" if jax.default_backend() == "tpu" else "whole_buffer"
    check(layout["kv_positions"] == {"full": 64}
          and layout["state"]["layers"] == 6
          and engine.linear_attention == {"tick": "step",
                                          "prefill": "chunked"}
          and engine.state_step == walk,
          f"serve_hybrid: the engine's layout is {layout}, its linear "
          f"layers' forms {engine.linear_attention}, the tick's state step "
          f"{engine.state_step}")
    check_serve(engine, reqs, results, "serve_hybrid", n_generate=2)


#: Mamba-1 selective-state-space layers around one multi-query attention
#: layer, dense, tied, at the debug size of ``configs.get_config`` (one
#: period of fourteen layers; 64 channels of 8 states; context 64)
SSM_DEBUG = ["--model", "jamba2", "--num_params", "3B", "--debug"]


def phase_serve_ssm(shapes=((5, 12, 0.0), (30, 20, 0.0), (17, 8, 0.8),
                            (40, 16, 0.0), (9, 20, 0.7), (23, 30, 0.0))
                    ) -> None:
    """``phase_serve_hybrid`` for the other recurrent state: thirteen
    state-space layers' states and tails beside one layer's keys and
    values, the scan and the tick's state step in their XLA forms (the debug
    width is under the kernels'; ``phase_kernels`` holds both kernels at the
    cell's)."""
    reqs_path = os.path.join(WORK, "requests_ssm.jsonl")
    os.makedirs(WORK, exist_ok=True)
    reqs = make_requests(reqs_path, shapes)
    engine, results = serve("serve_ssm", reqs_path, len(reqs),
                            extra=["--serve_prefill_chunk", "16"],
                            model=SSM_DEBUG)
    layout = engine.layout()
    check(layout["kv_positions"] == {"full": 64}
          and layout["state"]["layers"] == 13
          and engine.selective_scan == {"tick": "step", "prefill": "scan"}
          and engine.state_step == "whole_buffer",
          f"serve_ssm: the engine's layout is {layout}, its state-space "
          f"layers' forms {engine.selective_scan}, the tick's state step "
          f"{engine.state_step}")
    check_serve(engine, reqs, results, "serve_ssm", n_generate=2)


def phase_serve_rows(shapes=((500, 12, 0.0), (40, 20, 0.0), (300, 8, 0.8),
                             (17, 16, 0.0), (450, 10, 0.7))) -> None:
    """The ``head_dim``-128 tick on the chip, no timing: the parallel block
    of rings and experts at its debug size but with heads of 128 and a
    context of 640 (``main`` has no flag for either: the engine is built
    here), chunks of 128 into rings of 384 that three prompts wrap, five
    requests over three slots. On a TPU the tick attends with
    ``live_block_attention``'s sublane form in every layer (a ring by
    position, the rows that do not decode unread) and says so; greedy
    tokens are held to the one-shot forward and to ``generate()`` like the
    dense model's."""
    import jax

    from building_llm_from_scratch_tpu.configs import get_config
    from building_llm_from_scratch_tpu.models import init_params
    from building_llm_from_scratch_tpu.serving import (
        DecodeEngine,
        KVCachePolicy,
        SamplingParams,
    )

    os.makedirs(WORK, exist_ok=True)
    reqs = make_requests(os.path.join(WORK, "requests_rows.jsonl"), shapes)
    cfg = get_config("command_a_plus", "218B", debug=True).replace(
        attn_head_dim=128, context_length=640, sliding_window=256)
    t0 = time.perf_counter()
    engine = DecodeEngine(cfg, init_params(cfg, jax.random.PRNGKey(SEED)),
                          n_slots=3, max_len=640, max_queue=len(reqs),
                          kv_policy=KVCachePolicy(prefill_chunk=128))
    handles = [engine.submit(r["prompt_ids"], SamplingParams(**{
        k: v for k, v in r.items() if k != "prompt_ids"})) for r in reqs]
    engine.run_until_idle()
    results = [{"token_ids": list(h.output_ids),
                "finish_reason": h.finish_reason} for h in handles]
    serve_line("serve_rows", engine, results, t0)
    layout = engine.layout()
    check(layout["kv_positions"] == {"full": 640, "ring": 384},
          f"serve_rows: the engine's layout is {layout}")
    if jax.default_backend() == "tpu":
        check((engine.decode_attention, engine.chunk_attention,
               engine.kv_append) == ("live_blocks", "live_blocks", "scatter"),
              f"serve_rows: decode_attention {engine.decode_attention}, "
              f"chunk_attention {engine.chunk_attention}, kv_append "
              f"{engine.kv_append} at head_dim 128 on a TPU")
    check_serve(engine, reqs, results, "serve_rows", n_generate=2)


def _load_tests(name: str):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(HERE, "tests", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run_repo_tpu_tests() -> int:
    """The repo's own ``needs_tpu`` test cases (pytest skips them off-chip;
    here they run in the process that holds the chip)."""
    def cases(fn):
        marks = {m.name: m for m in getattr(fn, "pytestmark", [])}
        if "parametrize" not in marks:
            return marks, [()]
        return marks, [c if isinstance(c, tuple) else (c,)
                       for c in marks["parametrize"].args[1]]

    n = 0
    for file in ("test_fused_attention", "test_decode_step",
                 "test_attention_impls"):
        for name, fn in sorted(vars(_load_tests(file)).items()):
            marks, args = cases(fn)
            if name.startswith("test_") and "needs_tpu" in marks:
                for case in args:
                    fn(*case)
                    n += 1
    return n


def phase_kernels() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from building_llm_from_scratch_tpu.ops.attention import (
        _xla_attention,
        decode_attention,
        ring_positions,
    )
    from building_llm_from_scratch_tpu.ops.chunk_attention import (
        chunk_live_attention,
    )
    from building_llm_from_scratch_tpu.ops.decode_step import (
        lane_window_append,
        live_block_attention,
        slot_cache_append,
    )
    from building_llm_from_scratch_tpu.ops.fused_attention import (
        fused_causal_attention,
    )
    from building_llm_from_scratch_tpu.ops.fused_dropout import (
        fused_dropout_add,
    )

    t0 = time.perf_counter()
    f32 = lambda x: np.asarray(x, np.float32)
    # fused attention at the train step's shape (GPT2-124M bs8) vs the
    # dense XLA oracle, forward and gradients
    ks = jax.random.split(jax.random.PRNGKey(SEED), 6)
    B, T, H, D = 8, 1024, 12, 64
    q, k, v = (jax.random.normal(kk, (B, T, H, D), jnp.bfloat16)
               for kk in ks[:3])
    oracle = lambda q, k, v: _xla_attention(
        q, k, v, q_positions=None, kv_length=None, dropout_rate=0.0,
        dropout_rng=None, deterministic=True)
    loss = lambda fn: (lambda *a: jnp.sum(fn(*a).astype(jnp.float32) ** 2))
    got = jax.jit(fused_causal_attention)(q, k, v)
    np.testing.assert_allclose(f32(got), f32(jax.jit(oracle)(q, k, v)),
                               atol=2e-2, rtol=2e-2)
    gf = jax.jit(jax.grad(loss(fused_causal_attention), (0, 1, 2)))(q, k, v)
    go = jax.jit(jax.grad(loss(oracle), (0, 1, 2)))(q, k, v)
    for a, b in zip(gf, go):
        scale = max(1.0, float(np.abs(f32(b)).max()))
        check(float(np.abs(f32(a) - f32(b)).max()) / scale < 2e-2,
              "kernels: fused attention gradient off the oracle")
    # with dropout: deterministic per rng, different across rngs, finite
    drop = jax.jit(lambda q, k, v, r: fused_causal_attention(
        q, k, v, dropout_rate=0.1, dropout_rng=r))
    d1, d2, d3 = (f32(drop(q, k, v, jax.random.PRNGKey(s)))
                  for s in (1, 1, 2))
    check(np.array_equal(d1, d2) and not np.array_equal(d1, d3)
          and bool(np.isfinite(d1).all()),
          "kernels: fused attention dropout not a function of its rng")

    # fused residual dropout: out - x is h/(1-p) where kept, 0 elsewhere;
    # keep fraction 1-p; the backward redraws the same mask
    x, h = (jax.random.normal(kk, (B, T, 768), jnp.bfloat16)
            for kk in ks[3:5])
    h = jnp.abs(h) + 1.0
    rng = jax.random.PRNGKey(7)
    out, dh = jax.jit(lambda x, h: jax.value_and_grad(
        lambda h: fused_dropout_add(x, h, 0.1, rng).astype(
            jnp.float32).sum())(h))(x, h)
    kept = f32(dh) != 0
    check(abs(float(kept.mean()) - 0.9) < 5e-3,
          f"kernels: fused dropout keeps {kept.mean():.4f}, wants 0.9")
    fwd = f32(jax.jit(lambda x, h: fused_dropout_add(x, h, 0.1, rng))(x, h))
    want = f32(x) + np.where(kept, f32(h) / 0.9, 0.0)
    np.testing.assert_allclose(fwd, want, atol=6e-2, rtol=2e-2)

    # the lane-window append (what the engine's tick program writes the
    # cache with) at the engine's shape (8 slots, Tmax 1024, per-row
    # lengths) vs the scatter it replaces: pure data movement, so exact,
    # with the caches donated as the engine donates them. Window edges,
    # both ends, and fp32 as well as bf16
    S, Tmax = 8, 1024
    kn, vn = (jax.random.normal(kk, (S, 1, H, D), jnp.bfloat16)
              for kk in ks[1:3])
    K, V = (jax.random.normal(kk, (S, H, Tmax, D), jnp.bfloat16)
            for kk in ks[3:5])
    lengths = jnp.asarray([0, 127, 128, 255, 133, 512, 1000, 1023],
                          jnp.int32)
    append = jax.jit(lane_window_append, donate_argnums=(0, 1))
    for dt in (jnp.float32, jnp.bfloat16):     # bf16 last: it donates K, V
        Kd, Vd, knd, vnd = (a.astype(dt) for a in (
            K, V, kn.transpose(0, 2, 1, 3), vn.transpose(0, 2, 1, 3)))
        K2 = slot_cache_append(Kd, knd, lengths)
        V2 = slot_cache_append(Vd, vnd, lengths)
        Ko, Vo = append(Kd, Vd, knd, vnd, lengths)
        check(np.array_equal(f32(Ko), f32(K2))
              and np.array_equal(f32(Vo), f32(V2)),
              f"kernels: lane-window append wrote the {jnp.dtype(dt).name} "
              f"cache differently from the scatter")

    # the live-block attention (what the engine's tick program attends
    # with) vs decode_attention, at the serving cells' pane (GPT2-1.5B, 32
    # slots): block edges, both ends, a free slot, and lengths as a tick's
    S, H = 32, 25
    lengths = jnp.asarray(
        [0, 1, 127, 128, 129, 255, 256, 257, 511, 512, 513, 1023, 1024]
        + np.random.default_rng(SEED).integers(1, 1025, S - 13).tolist(),
        jnp.int32)
    attend = jax.jit(live_block_attention)
    whole = jax.jit(lambda q, K, V, n: decode_attention(
        q, K, V, q_positions=(n - 1)[:, None], kv_length=n))
    # one tolerance: float32 products run in bfloat16 passes on this chip,
    # in both (measured 7.8e-3 apart on float32 panes, 2e-3 on bfloat16)
    for dt in (jnp.float32, jnp.bfloat16):
        q1 = jax.random.normal(ks[0], (S, 1, H, D), dt)
        K, V = (jax.random.normal(kk, (S, H, Tmax, D), dt)
                for kk in ks[1:3])
        got, want = (f32(fn(q1, K, V, lengths))[1:] for fn in (attend, whole))
        check(float(np.abs(got - want).max()) < 2e-2,
              f"kernels: live-block attention is "
              f"{float(np.abs(got - want).max()):.2e} off decode_attention "
              f"on {jnp.dtype(dt).name} panes")

    # the same kernel in the layout head_dim 128 has (positions on the
    # sublanes: what the rag, longdoc and widechat ticks attend with) vs
    # decode_attention, at their buffers: a ring wrapped and not, block
    # edges, a full buffer, rows that do not decode beside long ones
    rows = jax.jit(live_block_attention, static_argnames="window")
    for S, Hq, Hkv, Tmax, window in ((48, 128, 8, 4608, 4096),
                                     (48, 64, 8, 33792, None),
                                     (192, 20, 1, 3072, None)):
        top = 4 * Tmax if window else Tmax
        n = jnp.asarray(([1, 511, 512, 513, top] + np.random.default_rng(
            SEED).integers(1, top + 1, S - 5).tolist()), jnp.int32)
        decodes = jnp.arange(S) % 3 != 1
        q1 = jax.random.normal(ks[0], (S, 1, Hq, 128), jnp.bfloat16)
        K, V = (jax.random.normal(kk, (S, Hkv, Tmax, 128), jnp.bfloat16)
                for kk in ks[1:3])
        ring_kw = ({"kv_positions": ring_positions(n - 1, Tmax),
                    "window": window} if window else {})
        want = jax.jit(lambda q, K, V, n: decode_attention(
            q, K, V, q_positions=(n - 1)[:, None], kv_length=n,
            **ring_kw))(q1, K, V, n)
        got = f32(rows(q1, K, V, n, live=decodes, window=window))
        gap = float(np.abs(got - f32(want))[np.asarray(decodes)].max())
        check(gap < 2e-2 and not got[~np.asarray(decodes)].any(),
              f"kernels: live-block attention on sublanes is {gap:.2e} off "
              f"decode_attention on a buffer of {Tmax}, or a row that does "
              f"not decode reads something")

    # the chunk kernel (what a head_dim-128 engine's chunk program attends
    # with) vs decode_attention on the sliced row, at the rag cell's two
    # buffers: a ring that has wrapped and a full layer, the prompt ending
    # inside the chunk, another slot than 0
    C, Hq, Hkv, hd, slot = 512, 128, 8, 128, 2
    chunk = jax.jit(chunk_live_attention, static_argnames="window")
    for Tmax, ring, start in ((4608, True, 9216), (20480, False, 6144)):
        window = 4096 if ring else None
        kv_len = start + C - 37
        qc = jax.random.normal(ks[0], (1, C, Hq, hd), jnp.bfloat16)
        K, V = (jax.random.normal(kk, (4, Hkv, Tmax, hd), jnp.bfloat16)
                for kk in ks[1:3])
        ring_kw = ({"kv_positions": ring_positions(
            jnp.reshape(start + C - 1, (1,)), Tmax), "window": window}
            if ring else {})
        want = jax.jit(lambda q, K, V: decode_attention(
            q, K[slot:slot + 1], V[slot:slot + 1],
            q_positions=(start + jnp.arange(C))[None],
            kv_length=jnp.asarray([kv_len]), **ring_kw))(qc, K, V)
        got = chunk(qc, K, V, slot, start, kv_len, window=window)
        gap = float(np.abs(f32(got) - f32(want))[:, :kv_len - start].max())
        check(gap < 2e-2, f"kernels: chunk attention is {gap:.2e} off "
              f"decode_attention on a buffer of {Tmax}")

    # the grouped expert product (what a sparse engine's chunk program
    # reaches its held experts with) vs the loop of conditionals, at the
    # longdoc cell's layer: 512 rows top-8 of 320, 20 held, a padded tail
    from building_llm_from_scratch_tpu.configs import get_config
    from building_llm_from_scratch_tpu.models import moe

    cfg = get_config("solar_open2", "250B", dtype="bf16",
                     target_context_length=None).replace(
        experts_held=tuple(range(20)))
    N, width, F = 512, cfg.emb_dim, cfg.hidden_dim
    check(moe.expert_dispatch_path(cfg, N, jnp.bfloat16) == "grouped"
          and moe.expert_dispatch_path(cfg, 48, jnp.bfloat16) == "per_expert",
          "kernels: a 512-row chunk is not grouped or a 48-row tick is")
    experts = {name: 0.02 * jax.random.normal(kk, (2, 20) + shape,
                                              jnp.bfloat16)
               for name, kk, shape in (("gate", ks[0], (width, F)),
                                       ("up", ks[1], (width, F)),
                                       ("down", ks[2], (F, width)))}
    experts["layer"] = 1
    rows = jax.random.normal(ks[3], (N, width), jnp.bfloat16)
    ids, weights = moe.route(cfg, 0.2 * jax.random.normal(
        ks[4], (width, cfg.n_routed_experts)), rows)
    live = jnp.arange(N) < N - 37
    (got, n_got), (want, n_want) = (
        jax.jit(lambda p, form=form: form(cfg, p, rows, ids, weights, live))(
            experts) for form in (moe._routed, moe._per_expert))
    gap = float(np.abs(f32(got) - f32(want)).max())
    check(np.array_equal(n_got, n_want) and int(n_want.sum()) > 100
          and gap < 2e-2 * max(1.0, float(np.abs(f32(want)).max())),
          f"kernels: the grouped experts are {gap:.2e} off the per-expert "
          f"form, rows {n_got.tolist()} against {n_want.tolist()}")

    # the selective scan (what a state-space engine's chunk program runs)
    # vs the step under a lax.scan, at the widechat cell's layer: 512 tokens
    # of 5120 channels x 16 states from a non-zero state, the family's A,
    # steps from 0.001 to 1, a padded tail (delta 0)
    from building_llm_from_scratch_tpu.ops import selective_scan as ss

    T, I, N = 512, 5120, 16
    check(ss.selective_scan_path(T, I, N) == "kernel"
          and ss.selective_scan_path(1, I, N) == "step",
          "kernels: a 512-token chunk is not on the scan kernel's path")
    delta = jnp.exp(jax.random.uniform(ks[1], (1, T, I), minval=np.log(1e-3),
                                       maxval=0.0))
    delta = jnp.where(jnp.arange(T)[None, :, None] < T - 37, delta, 0.0)
    args = (jax.random.normal(ks[0], (1, T, I)), delta,
            -jnp.broadcast_to(jnp.arange(1.0, N + 1)[:, None], (N, I)),
            jax.random.normal(ks[2], (1, T, N)),
            jax.random.normal(ks[3], (1, T, N)),
            jax.random.normal(ks[4], (I,)),
            jax.random.normal(ks[5], (1, N, I)))
    (y_got, s_got), (y_want, s_want) = (
        jax.jit(form)(*args)
        for form in (ss.selective_scan_kernel, ss.selective_scan))
    gap = max(float(np.abs(f32(y_got) - f32(y_want)).max()),
              float(np.abs(f32(s_got) - f32(s_want)).max()))
    check(gap < 1e-4, f"kernels: the selective-scan kernel is {gap:.2e} off "
          "the step under a lax.scan")

    # the tick's walk over the decoding rows (what a state-space engine's
    # tick program runs) vs the step over every row and a select, at the
    # widechat cell's layer: 192 rows of 5120 channels x 16 states, 72 of
    # them decoding; the others keep their states bit for bit
    S = 192
    live = jnp.zeros((S,), bool).at[
        jax.random.permutation(ks[0], S)[:72]].set(True)
    token = (jax.random.normal(ks[1], (S, I)),
             jnp.exp(jax.random.uniform(ks[2], (S, I), minval=np.log(1e-3),
                                        maxval=0.0)),
             args[2], jax.random.normal(ks[3], (S, N)),
             jax.random.normal(ks[4], (S, N)), args[5])
    state = jax.random.normal(ks[5], (S, N, I))
    y_want, s_want = jax.jit(ss.selective_step)(*token, state)
    y_got, s_got = jax.jit(lambda *a: ss.selective_step_rows(
        *a, ss.live_rows_table(live)))(*token, state)
    on = np.asarray(live)
    gap = max(float(np.abs(f32(y_got) - f32(y_want))[on].max()),
              float(np.abs(f32(s_got) - f32(s_want))[on].max()))
    check(ss.supports_step_rows(I, N) and gap < 1e-4
          and np.array_equal(f32(s_got)[~on], f32(state)[~on])
          and not f32(y_got)[~on].any(),
          f"kernels: the walk over the decoding rows is {gap:.2e} off the "
          "step, or a row that does not decode moved")

    n = run_repo_tpu_tests()
    log(f"kernels: fused attention fwd/grad/dropout, fused dropout-add, "
        f"lane-window append, live-block and chunk attention, the "
        f"grouped experts, the selective scan and the tick's walk over the "
        f"decoding rows "
        f"match their XLA references at the real shapes; "
        f"{n} needs_tpu repo test cases pass; "
        f"{time.perf_counter() - t0:.1f}s")


def device_bytes(tree) -> list:
    """Bytes each local device holds of ``tree`` (from addressable_shards)."""
    import jax

    per = {d.id: 0 for d in jax.local_devices()}
    for leaf in jax.tree_util.tree_leaves(tree):
        for shard in getattr(leaf, "addressable_shards", ()):
            per[shard.device.id] += shard.data.nbytes
    return [per[k] for k in sorted(per)]


def phase_chips4(expect_kernels: bool = True, model=(),
                 corpus_bytes: int = 700_000, shapes=REQUEST_SHAPES) -> None:
    import jax
    import numpy as np

    check(len(jax.devices()) == 4,
          f"--chips 4 needs four devices, found {len(jax.devices())}")
    make_corpus(os.path.join(WORK, "data", "corpus.txt"), corpus_bytes)
    quiet = ["--n_epochs", "1", "--print_sample_iter", "1000",
             "--save_ckpt_freq", "1000", *model]
    t0 = time.perf_counter()
    fsdp = run_main(train_args(os.path.join(WORK, "ckpt_fsdp"), 16, quiet + [
        "--run_type", "multi_chip", "--shard_mode", "fsdp"]))
    # under fsdp the optimizer state's shardings change once, between the
    # first step (placed replicated) and the second (pinned sharded by
    # the step): one recompile at step 2, documented in obs/compile.py
    check_trainer(fsdp, "fsdp x4", expect_kernels, max_recompiles=1)
    hlo = "".join(e.as_text() for e in fsdp._compile_watcher.executables)
    colls = {c: hlo.count(c) for c in ("all-gather", "reduce-scatter",
                                       "all-reduce")}
    check(colls["all-gather"] > 0 and (colls["reduce-scatter"] > 0
                                       or colls["all-reduce"] > 0),
          f"fsdp x4: no collectives in the compiled step: {colls}")
    for name in ("trainable", "opt_state"):
        per = device_bytes(fsdp.state[name])
        check(min(per) > 0 and max(per) < 0.4 * sum(per),
              f"fsdp x4: {name} not spread over four devices: {per}")
        log(f"fsdp x4: {name} bytes per device {per}")
    log(f"fsdp x4: collectives in the step {colls}, "
        f"{time.perf_counter() - t0:.1f}s")

    t0 = time.perf_counter()
    one = run_main(train_args(os.path.join(WORK, "ckpt_one"), 16, quiet))
    check_trainer(one, "one device", expect_kernels)
    check(one.global_step == fsdp.global_step, "arms ran different steps")
    gap = float(np.max(np.abs(np.asarray(fsdp.train_losses + fsdp.val_losses)
                              - np.asarray(one.train_losses
                                           + one.val_losses))))
    log(f"fsdp x4 vs one device: eval-loss trajectories differ by at most "
        f"{gap:.4f} (tolerance {FSDP_LOSS_TOL}); fsdp {fsdp.train_losses} "
        f"one {one.train_losses}; {time.perf_counter() - t0:.1f}s")
    check(gap <= FSDP_LOSS_TOL, f"fsdp x4 loss trajectory off by {gap:.4f}")

    reqs_path = os.path.join(WORK, "requests.jsonl")
    reqs = make_requests(reqs_path, shapes)
    tp, tp_res = serve("serve_tp4", reqs_path, len(reqs),
                       ["--serve_tp", "4"], model=model)
    per = device_bytes(tp.params)
    whole = sum(x.nbytes for x in jax.tree_util.tree_leaves(tp.params))
    check(0 < min(per) and max(per) < whole,
          f"serve_tp4: params not sharded over four devices: {per} of "
          f"{whole} bytes")
    check_serve(tp, reqs, tp_res, "serve_tp4", n_generate=0)
    hlo = "".join(e.as_text() for e in tp._decode.executables)
    check("all-reduce" in hlo, "serve_tp4: no all-reduce in the decode step")
    one_eng, one_res = serve("serve_one", reqs_path, len(reqs), model=model)
    check_serve(one_eng, reqs, one_res, "serve_one", n_generate=0)
    same = sum(a["token_ids"] == b["token_ids"]
               for a, b, r in zip(tp_res, one_res, reqs)
               if r["temperature"] == 0)
    log(f"serve_tp4 vs one chip: {same}/"
        f"{sum(r['temperature'] == 0 for r in reqs)} greedy requests "
        f"token-identical (both within the logit margin of the one-shot "
        f"forward); params bytes per device {per}")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: run only the multi-chip paths and what they "
                         "are compared with (builder-run)")
    ap.add_argument("--phase", action="append",
                    choices=["train", "serve", "serve_moe", "serve_hybrid",
                             "serve_ssm", "serve_rows", "kernels",
                             "train_remat"],
                    help="run only these one-chip phases (default: train, "
                         "serve, serve_moe, serve_hybrid, serve_ssm, "
                         "serve_rows, kernels)")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r} "
              f"({dev.device_kind}); nothing was run.", file=sys.stderr)
        return 2
    try:
        from building_llm_from_scratch_tpu.obs import configure_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the package is not importable from "
              f"{HERE}: {e}", file=sys.stderr)
        return 3

    cache_dir = configure_compile_cache()
    cache = CacheCounter()
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"device {device}, jax {jax.__version__}, compile cache {cache_dir}")
    if args.chips == 4:
        phases = {"chips4": phase_chips4}
    else:
        table = {"train": phase_train, "serve": phase_serve,
                 "serve_moe": phase_serve_moe,
                 "serve_hybrid": phase_serve_hybrid,
                 "serve_ssm": phase_serve_ssm,
                 "serve_rows": phase_serve_rows,
                 "kernels": phase_kernels, "train_remat": phase_train_remat}
        phases = {n: table[n] for n in (
            args.phase or ["train", "serve", "serve_moe", "serve_hybrid",
                           "serve_ssm", "serve_rows", "kernels"])}
    shutil.rmtree(WORK, ignore_errors=True)
    failed = []
    t_all = time.perf_counter()
    try:
        for name, phase in phases.items():
            t0 = time.perf_counter()
            try:
                phase()
                log(f"phase {name}: ok in {time.perf_counter() - t0:.1f}s")
            except Exception:                 # noqa: BLE001 — report, go on
                import traceback

                traceback.print_exc()
                failed.append(name)
                log(f"phase {name}: FAILED after "
                    f"{time.perf_counter() - t0:.1f}s")
    finally:
        shutil.rmtree(WORK, ignore_errors=True)
    log(f"compile cache: {cache.hits} hits, {cache.misses} misses; "
        f"wall {time.perf_counter() - t_all:.1f}s")
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
