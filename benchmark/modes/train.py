"""Train mode: the window drives ``Trainer.train_model`` itself.

One ``Trainer``, one compiled step, one state. The loader handed to the
trainer is the benchmark's: its batches come from the generator the traffic
file names, through the trainer's own prefetcher into the trainer's own loop.
The same stream carries, in order,

1. ``check_steps`` batches. After the first the optimizer's first moment gives
   the gradient as the optimizer got it; after the last the parameters give
   their change since the seed. Each step's loss is kept.
2. ``warm_steps`` batches, then a wait until the device is idle: set-up ends.
3. the window: batches until ``seconds`` have passed. The stream then ends,
   ``train_model`` returns, and a blocking fetch of the state closes the clock.

Afterwards, with the trainer's state freed, the configuration's plain
reference follows the check steps on the same batches and weights.
"""

from __future__ import annotations

import gc
import os
import shutil
import threading
import time
from typing import Any, Dict, List

import numpy as np

from benchmark import memory, spec, weights
from benchmark.trace import Capture

ADAM_B1 = 0.9          # optax.scale_by_adam's, as build_optimizer leaves it


class StreamLoader:
    """What ``Trainer.train_model`` asks of a loader, over one endless
    'file'. ``batches`` is where the benchmark drives the run from."""

    def __init__(self, stream):
        self._stream = stream

    def get_total_steps_epoch(self, files, eos_text=None) -> int:
        return self._stream.schedule_steps

    def create_datasets_for_file(self, path, eos_text=None):
        return "train", "val"

    def num_batches(self, ds):
        return None if ds == "train" else 0

    def batches(self, ds, shuffle=False, epoch=0):
        return iter(self._stream) if ds == "train" else iter(())


class Stream:
    """The batch stream and the run's clockwork (see the module docstring).
    Runs in the trainer's prefetch thread, or in its loop without one."""

    def __init__(self, cell: spec.Cell, seed: int, seconds: float, capture,
                 clock, compiles):
        t = cell.traffic
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.generator = spec.load_module("generators", t["generator"])
        self.model = cell.config["model"]
        self.schedule_steps = int(t["schedule_steps"])
        self.check_steps, self.warm_steps = t["check_steps"], t["warm_steps"]
        self.capture, self.clock, self.compiles = capture, clock, compiles
        self.trace_seconds = float(t.get("trace_seconds", 2.0))
        self.trainer = None
        self.losses: List[Any] = []
        self.first_grad_norms: Dict[str, float] = {}
        self.change_norms: Dict[str, float] = {}
        self.t_open = None
        self.window_steps = 0
        self.compiles_at_open = 0
        self.stamps: List[float] = []

    def batch(self, i: int):
        return self.generator.batch(self.cell.traffic, self.model, self.seed, i)

    def _await_step(self, n: int) -> None:
        while self.trainer.global_step < n:
            time.sleep(0.0005)

    def _spy_losses(self) -> None:
        """Keep the loss of each check step: the trainer fetches losses only
        for its watchdog, so the step is wrapped for these steps alone and
        then put back as it was."""
        trainer, inner = self.trainer, self.trainer.train_step

        def spy(state, batch):
            out = inner(state, batch)
            self.losses.append(out[1]["loss"])
            if len(self.losses) >= self.check_steps:
                trainer.train_step = inner
            return out

        trainer.train_step = spy

    def __iter__(self):
        import jax

        self._spy_losses()
        n_pre = self.check_steps + self.warm_steps
        for i in range(n_pre):
            if i == 1:
                self._await_step(1)
                mu = _adam_mu(self.trainer.state["opt_state"])
                self.first_grad_norms = {
                    k: v / (1.0 - ADAM_B1) for k, v in leaf_norms(mu).items()}
            if i == self.check_steps:
                self._await_step(self.check_steps)
                start = weights.make_params(self.cell.config, self.seed,
                                            np.float32)
                self.change_norms = leaf_norms(jax.tree_util.tree_map(
                    lambda a, b: a - b, self.trainer.state["trainable"],
                    start))
                del start
            yield self.batch(i)
        self._await_step(n_pre)
        jax.block_until_ready(self.trainer.state["step"])
        self.compiles_at_open = self.compiles.n
        self.clock.mark("first_steps")
        self.t_open = time.perf_counter()
        tracing = False
        while True:
            now = time.perf_counter() - self.t_open
            if now >= self.seconds:
                break
            if self.capture is not None:
                if not tracing and self.capture.t_start is None and now >= 1.0:
                    self.capture.start()
                    tracing = True
                elif tracing and (time.perf_counter() - self.capture.t_start
                                  >= self.trace_seconds):
                    self.capture.stop()
                    tracing = False
            self.stamps.append(now)
            yield self.batch(n_pre + self.window_steps)
            self.window_steps += 1
        if tracing:
            self.capture.stop()


def _adam_mu(opt_state):
    for part in opt_state:
        if hasattr(part, "mu"):
            return part.mu
    raise RuntimeError("no Adam first moment in the optimizer state")


def leaf_norms(tree) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp

    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in xs])([x for _, x in flat])
    return {jax.tree_util.keystr(p): float(n)
            for (p, _), n in zip(flat, jax.device_get(norms))}


def worst_leaf_gap(program: Dict[str, float], reference: Dict[str, float]
                   ) -> float:
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the median
    leaf, whichever is larger (some leaves' gradients are all but zero)."""
    floor = float(np.median(list(reference.values())))
    return max(abs(program[k] - reference[k]) / max(reference[k], floor)
               for k in reference)


def reference_steps(stream: Stream, cell: spec.Cell, seed: int,
                    precision: str = "float32") -> Dict[str, Any]:
    """The plain reference follows the check steps on the same batches and
    the same draws of the weights."""
    reference = spec.load_module("reference", cell.config["reference"])
    params = weights.make_params(cell.config, seed, np.float32)
    return reference.train_steps(
        params, stream.model,
        [stream.batch(i) for i in range(stream.check_steps)],
        cell.traffic["trainer"], stream.schedule_steps, precision=precision,
        dropout_seed=seed)


def worst_leaves(program: Dict[str, Any], ref: Dict[str, Any]
                 ) -> Dict[str, Any]:
    """Which leaf reads worst, for the record beside the numbers."""
    out = {}
    for key in ("first_grad_norms", "change_norms"):
        floor = float(np.median(list(ref[key].values())))
        gaps = {k: abs(program[key][k] - ref[key][k]) / max(ref[key][k], floor)
                for k in ref[key]}
        worst = sorted(gaps, key=gaps.get)[-3:]
        out[key] = {k: [gaps[k], ref[key][k]] for k in worst}
    return out


def median_leaf_gap(program: Dict[str, float], reference: Dict[str, float]
                    ) -> float:
    return float(np.median([abs(program[k] - reference[k]) / reference[k]
                            for k in reference if reference[k] > 0]))


def compare(program: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    """The numbers ``correct`` rests on: ``program`` against ``ref``, each
    with ``losses``, ``first_grad_norms`` and ``change_norms``."""
    return {
        "loss_step1_rel": abs(program["losses"][0] - ref["losses"][0])
        / ref["losses"][0],
        "loss_worst_rel": max(abs(a - b) / b for a, b in
                              zip(program["losses"], ref["losses"])),
        "first_grad_worst_leaf_rel": worst_leaf_gap(
            program["first_grad_norms"], ref["first_grad_norms"]),
        "first_grad_median_leaf_rel": median_leaf_gap(
            program["first_grad_norms"], ref["first_grad_norms"]),
        "change_worst_leaf_rel": worst_leaf_gap(
            program["change_norms"], ref["change_norms"]),
        "change_median_leaf_rel": median_leaf_gap(
            program["change_norms"], ref["change_norms"]),
    }


def build_trainer(cell: spec.Cell, seed: int, stream: Stream, out_dir: str):
    import jax

    from building_llm_from_scratch_tpu.configs import ModelConfig
    from building_llm_from_scratch_tpu.training.precision import get_policy
    from building_llm_from_scratch_tpu.training.trainer import Trainer

    cfg = ModelConfig(**cell.config["model"])
    params = weights.make_params(cell.config, seed, cfg.jax_dtype)
    jax.block_until_ready(params)
    opts = dict(cell.traffic["trainer"])
    never = 10 ** 9         # no eval, sample or checkpoint inside the window
    trainer = Trainer(
        cfg, params, None, StreamLoader(stream), output_dir=out_dir,
        eval_freq=never, print_sample_iter=never, save_ckpt_freq=never,
        policy=get_policy(cell.config["precision"]["policy"]),
        seed=seed % (2 ** 31), show_progress=False, warmup_sample=False,
        compile_cache_dir=None, **opts)
    stream.trainer = trainer
    return trainer


def run(cell: spec.Cell, *, seed: int, seconds: float, trace: bool, peaks,
        clock, say, compiles, control: str = "") -> Dict[str, Any]:
    import jax

    work = os.path.join(spec.ROOT, ".benchmark_work", cell.name)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work, exist_ok=True)
    capture = Capture(os.path.join(work, "trace")) if trace else None
    stream = Stream(cell, seed, seconds, capture, clock, compiles)
    trainer = build_trainer(cell, seed, stream, work)
    clock.mark("weights")
    n_threads0 = threading.active_count()
    trainer.train_model(["stream"], n_epochs=1)
    jax.block_until_ready(trainer.state)
    t_close = time.perf_counter()
    n_compiled = compiles.n - stream.compiles_at_open
    setup_s = stream.t_open - clock.t0
    elapsed = t_close - stream.t_open
    watcher = trainer._compile_watcher
    n_compiled = max(n_compiled, watcher.n_recompiles)
    tokens_per_step = cell.traffic["batch"] * cell.traffic["seq_len"]
    # every window step's loss is finite iff the parameters still are
    finite = bool(jax.device_get(jax.jit(lambda t: jax.numpy.all(
        jax.numpy.stack([jax.numpy.all(jax.numpy.isfinite(x)) for x in
                         jax.tree_util.tree_leaves(t)])))(
        trainer.state["trainable"])))
    peak = memory.read_peak(say, trainer.state, watcher.executables)
    result_trace = capture.result() if capture is not None else None

    # free the program's state before the reference takes the chip
    trainer.state = None
    stream.trainer = None
    del trainer
    gc.collect()
    t_ref = time.perf_counter()
    ref = reference_steps(stream, cell, seed)
    program = {"losses": [float(x) for x in stream.losses],
               "first_grad_norms": stream.first_grad_norms,
               "change_norms": stream.change_norms}
    numbers = compare(program, ref)
    if control:
        # the reference in the precision below, put in the program's place
        low = reference_steps(stream, cell, seed, control)
        say(control=compare(low, ref), control_worst_leaves=worst_leaves(
            low, ref))
    limits = cell.config["limits"]["train"]
    say(reference_s=round(time.perf_counter() - t_ref, 3),
        threads_left=threading.active_count() - n_threads0)
    shutil.rmtree(work, ignore_errors=True)
    # the loop takes batches in bursts, one log cadence at a time: the median
    # time of a cadence of steps is the loop's pace, whatever the profiler's
    # start and stop cost the traced run
    k = max(1, int(cell.traffic["trainer"].get("log_every") or 1))
    paces = np.diff(stream.stamps[::k]) / k
    step_ms = 1e3 * float(np.median(paces)) if len(paces) else float(
        1e3 * elapsed / max(1, stream.window_steps))
    return {
        "setup_s": setup_s,
        "attempted": stream.window_steps,
        "failed": 0 if finite else stream.window_steps,
        "sound": finite and n_compiled == 0,
        "compiles_in_window": n_compiled,
        "compared": [(k, numbers[k], limits[k]) for k in limits],
        "end_to_end": {
            "train_tok_s": stream.window_steps * tokens_per_step / elapsed,
            "setup_s": setup_s},
        "window": {"train_step_wall_ms": step_ms,
                   "tokens_per_step": tokens_per_step,
                   "seq_len": cell.traffic["seq_len"]},
        "summary": {"steps": stream.window_steps, "elapsed_s": elapsed,
                    "worst_leaves": worst_leaves(program, ref),
                    "step_wall_ms": step_ms,
                    "check_losses": [float(x) for x in stream.losses],
                    "compared": numbers},
        "memory_peak_bytes": peak,
        "trace": result_trace,
    }
