"""The run orchestrator (reference main.py:37-193).

One entry point drives the whole framework:

  seed -> distributed init -> build components (config + params [+ HF
  weights] [+ LoRA] + tokenizer + MeshPlan + precision policy) -> discover
  training files -> build loader -> Trainer [-> resume] -> warm-up sample
  -> train/finetune -> plot losses.pdf + peak-HBM log -> final export.

TPU-first differences from the reference:
  - no ``mp.spawn``/NCCL rendezvous (main.py:22-29,185-193): on TPU pods
    each host runs this same command and ``jax.distributed.initialize``
    auto-discovers peers; parallelism is the MeshPlan, not process wiring;
  - run artifacts (losses.pdf, peak memory, final export) are written by
    the coordinator process (the reference's ``rank == 0`` gating);
  - ``--resume_from`` restores params + optimizer state + step — a path
    the reference lacks entirely (SURVEY §5) — and ``--resume auto``
    (default) discovers the latest valid checkpoint in ``--output_dir``
    so a preempted job relaunches with its original command; SIGTERM/
    SIGINT checkpoint at the next step boundary and exit 0
    (training/resilience.py);
  - ``--profile`` captures a jax.profiler trace of the first steps — with
    named spans and per-step annotations since the obs/ round;
  - observability (obs/): ``--metrics_jsonl`` structured telemetry
    (header + metrics + health + events; scripts/summarize_metrics.py
    renders it), ``--log_every`` throughput/MFU/memory cadence decoupled
    from eval, ``--stall_timeout`` per-host hung-step flight recorder,
    per-layer-group training health + AOT compile/recompile telemetry
    (obs/health.py, obs/compile.py), persistent XLA compilation cache
    (``JAX_COMPILATION_CACHE_DIR``, else ``--compile_cache_dir``, else
    ``.jax_cache`` in the checkout).

Usage:  python -m building_llm_from_scratch_tpu --data_dir ... [flags]
"""

from __future__ import annotations

import os

import numpy as np

from building_llm_from_scratch_tpu.args import get_args
from building_llm_from_scratch_tpu.build_components import build_components
from building_llm_from_scratch_tpu.data.instruct import InstructLoader
from building_llm_from_scratch_tpu.obs import (
    StallDetector,
    configure_compile_cache,
    configure_metrics,
    emit_event,
    run_metadata,
)
from building_llm_from_scratch_tpu.data.pretrain import PretrainLoader
from building_llm_from_scratch_tpu.parallel import (
    initialize_distributed,
    is_coordinator,
    sync_global_devices,
)
from building_llm_from_scratch_tpu.training.resilience import (
    GracefulStopper,
    LossWatchdog,
    resolve_resume_agreed,
)
from building_llm_from_scratch_tpu.training.trainer import Trainer
from building_llm_from_scratch_tpu.utils.io import discover_training_files
from building_llm_from_scratch_tpu.utils.logging import setup_logger
from building_llm_from_scratch_tpu.utils.memory import log_device_memory
from building_llm_from_scratch_tpu.utils.plotting import plot_losses
from building_llm_from_scratch_tpu.utils.seeding import (
    configure_default_prng,
    set_seed,
)

logger = setup_logger("main")


def main(args):
    """Run one job from parsed args: training/finetuning (returns the
    Trainer with its loss history) or --mode serve (returns the
    DecodeEngine with its serve stats) for callers/tests."""
    import jax

    # BEFORE any compile (the component build device_puts and the first
    # train step both lower programs): a relaunched preempted job skips
    # its multi-minute XLA compiles entirely
    cache_dir = configure_compile_cache(args.compile_cache_dir)

    # 1. distributed runtime + reproducibility (reference main.py:49-58)
    initialize_distributed()
    configure_default_prng()
    set_seed(args.seed)

    # 2. observability sink first (--metrics_jsonl; a no-op sink when
    #    unset, so emit_event callers never care): configured BEFORE the
    #    component build so fetch/retry events are captured — they buffer
    #    until the run-metadata header lands below. Then components
    #    (reference main.py:63).
    metric_logger = configure_metrics(args.metrics_jsonl)
    comps = build_components(args)
    cfg = comps.cfg
    metric_logger.write_header(
        **run_metadata(args=args, cfg=cfg, plan=comps.plan))

    # serve mode: the continuous-batching decode engine (serving/) owns
    # its own run loop — warmup + frontends on the components built above,
    # no trainer
    if getattr(args, "mode", "train") == "serve":
        from building_llm_from_scratch_tpu.serving.frontend import run_serve

        return run_serve(args, comps, metric_logger)

    # finetune_fleet mode: fused multi-LoRA training — k tenants' jobs
    # through ONE base forward/backward, per-job artifact export at each
    # job's own completion (training/lora_fusion.py)
    if getattr(args, "mode", "train") == "finetune_fleet":
        from building_llm_from_scratch_tpu.training.lora_fusion import (
            run_finetune_fleet,
        )

        if is_coordinator():
            os.makedirs(args.output_dir, exist_ok=True)
        return run_finetune_fleet(args, comps, metric_logger)

    # constructed here, STARTED just before training inside the
    # try/finally below: starting now would leak the watcher thread if
    # loader/trainer setup raises, and start() is what arms the
    # first-step-hang timer — arming should not charge setup time
    stall = (StallDetector(args.stall_timeout)
             if args.stall_timeout > 0 else None)

    # 3. training files (reference main.py:68-81)
    txt_files, json_files = discover_training_files(args.data_dir)
    files = json_files if args.finetune else txt_files
    if not files:
        raise FileNotFoundError(
            "No training files found in specified directory.")
    if is_coordinator():
        logger.info("Total training files detected: %d", len(files))

    # 4. loader (reference main.py:86-111)
    # pp maps the STAGE axis over hosts (parallel/pipeline.py): every host
    # runs the same data columns for its stage, so the loader must yield
    # IDENTICAL batches on every process — per-process row sharding is for
    # the dp/fsdp/zero1/tp modes, where hosts own disjoint batch rows
    pp_multihost = (args.shard_mode == "pp")
    loader_kwargs = dict(
        tokenizer=comps.tokenizer,
        batch_size=args.batch_size,
        max_length=cfg.context_length,
        train_ratio=0.9,
        process_index=0 if pp_multihost else jax.process_index(),
        process_count=1 if pp_multihost else jax.process_count(),
        seed=args.seed,
    )
    if args.finetune:
        # pad id comes from the model config — fixing the reference's
        # hardcoded GPT-2 pad id 50256 (defect §2.3 #8)
        loader = InstructLoader(pad_token_id=cfg.eos_id,
                                dataset_name=args.dataset, **loader_kwargs)
    else:
        loader = PretrainLoader(stride=cfg.context_length,
                                token_cache_dir=args.tokenizer_cache_dir,
                                **loader_kwargs)

    # 5. output dir (reference main.py:116-117)
    if is_coordinator():
        os.makedirs(args.output_dir, exist_ok=True)
    sync_global_devices("output_dir")

    # 5b. fault tolerance: auto-resume discovery (coordinator-resolved and
    #     shared via the output dir so every host restores the SAME
    #     checkpoint), loss watchdog, and the graceful-stop signal handler
    # predicate: a fleet (--mode finetune_fleet) checkpoint in the same
    # output_dir shares the model_pg_ prefix but cannot restore into the
    # trainer state — auto-discovery skips it instead of dying mid-load
    resume_from = resolve_resume_agreed(
        getattr(args, "resume", "auto"), args.resume_from,
        args.output_dir, predicate=lambda meta: not meta.get("fleet"))
    watchdog = None
    if getattr(args, "watchdog", "on") == "on" and not (
            comps.policy is not None and comps.policy.name == "fp16"):
        watchdog = LossWatchdog(spike_factor=args.loss_spike_factor,
                                window=args.watchdog_window)
    stopper = GracefulStopper()

    # 6. trainer (reference main.py:122-138); the warm-up sample
    #    (main.py:143-145) runs inside the trainer once state exists
    trainer = Trainer(
        cfg, comps.params, comps.tokenizer, loader,
        output_dir=args.output_dir,
        peak_lr=args.lr, initial_lr=args.initial_lr, min_lr=args.min_lr,
        warmup_steps=args.warmup_steps,
        eval_freq=args.eval_freq, eval_iters=5,
        print_sample_iter=args.print_sample_iter,
        save_ckpt_freq=args.save_ckpt_freq,
        lora_params=comps.lora_params,
        lora_alpha=args.lora_alpha if args.use_lora else None,
        lora_rank=args.lora_rank if args.use_lora else None,
        policy=comps.policy, plan=comps.plan, seed=args.seed,
        grad_accum=args.grad_accum,
        resume_from=resume_from,
        warmup_sample=True,
        profile_dir=(os.path.join(args.output_dir, "profile")
                     if args.profile else None),
        profile_steps=args.profile_steps,
        keep_ckpts=args.keep_ckpts,
        watchdog=watchdog,
        stopper=stopper,
        log_every=args.log_every,
        stall=stall,
        compile_cache_dir=cache_dir,
        prefetch=args.prefetch,
        async_ckpt=(args.async_ckpt == "on"),
    )

    # 7. train / finetune (reference main.py:150-157) under the graceful-
    #    stop handler: SIGTERM (preemption) / SIGINT checkpoint at the next
    #    step boundary and fall through here with trainer.preempted set
    try:
        if stall is not None:
            stall.start()
        with stopper:
            if args.finetune:
                trainer.finetune_model(files, n_epochs=args.n_epochs)
            else:
                trainer.train_model(files, n_epochs=args.n_epochs)
    finally:
        if stall is not None:
            stall.stop()

    if trainer.preempted:
        # the interrupted checkpoint is on disk; skip the final export so
        # the process exits 0 within the preemption grace window — the
        # relaunch picks the run back up via --resume auto
        logger.warning(
            "Run preempted at step %d; interrupted checkpoint written. "
            "Relaunch the same command to resume (--resume auto).",
            trainer.global_step)
        sync_global_devices("run_end")
        return trainer

    # 8. plot + peak memory on the coordinator (reference main.py:162-166)
    if is_coordinator():
        if trainer.train_losses:
            epochs_seen = np.linspace(0, args.n_epochs,
                                      len(trainer.train_losses))
            plot_losses(epochs_seen, trainer.track_tokens_seen,
                        trainer.train_losses, trainer.val_losses,
                        args.output_dir)
        logger.info("Training complete. Final model saved.")
        log_device_memory(logger, prefix="Peak device memory — ")

    # 9. final checkpoint + single-file export (reference main.py:171-172)
    trainer.save_checkpoint("final")
    trainer.export_final("model_pg_final.npz")
    if getattr(args, "save_adapter", None):
        # standalone LoRA artifact for multi-tenant serving
        # (--serve_adapters); export_final above stays the MERGED
        # single-tenant export
        trainer.export_adapter(args.save_adapter)
    emit_event("run_complete", step=trainer.global_step,
               tokens_seen=trainer.tokens_seen,
               final_train_loss=(trainer.train_losses[-1]
                                 if trainer.train_losses else None))

    # 10. barrier before exit (reference main.py:177-179)
    sync_global_devices("run_end")
    return trainer


def run(argv=None):
    """Console entry: parse flags, run."""
    return main(get_args(argv))


if __name__ == "__main__":
    run()
