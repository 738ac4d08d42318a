"""The rest of a run, without the look for a chip, at the tiny test size:
sound it comes out ``correct``; with the timed path broken underneath (a train
step that returns its state unchanged, a served token altered where it is
produced) ``correct`` comes out false. And the control, the reference in the
precision below put in the program's place, reads far off what the sound
program reads."""

import jax
import pytest

from benchmark import run
from benchmark.tests.tiny import tiny_cell

SEED = 3_000_000_019


def exact(cell):
    """No dropout and float32 arithmetic in the program, so that at this size
    the sound program and the reference differ by rounding alone."""
    cell.config["model"].update(drop_rate=0.0, dtype="fp32")
    cell.config["precision"] = dict(cell.config["precision"], policy=None)
    return cell


def test_train_sound_then_step_that_keeps_its_state(monkeypatch, capsys):
    cell = exact(tiny_cell("train_gpt2_124m_pretrain"))
    sound = run.run_cell(cell, SEED, 0.5, False, None,
                         control=cell.config["precision"]["below"])
    assert sound["correct"] and sound["failed"] == 0
    out = capsys.readouterr().out
    said = [eval(line, {"null": None, "true": True, "false": False})
            for line in out.splitlines() if line.startswith("{")]
    control = next(d["control"] for d in said if "control" in d)
    program = next(d["window"]["compared"] for d in said if "window" in d)
    assert control["first_grad_worst_leaf_rel"] > 3 * max(
        program["first_grad_worst_leaf_rel"], 1e-6)

    from building_llm_from_scratch_tpu.training import trainer as trainer_mod

    real = trainer_mod.make_train_step

    def make_broken(*args, **kwargs):
        step = jax.jit(real(*args, **dict(kwargs, jit=False)))

        def broken(state, batch):
            _, metrics = step(state, batch)
            return state, metrics
        return broken

    monkeypatch.setattr(trainer_mod, "make_train_step", make_broken)
    broken = run.run_cell(cell, SEED, 0.5, False, None)
    assert not broken["correct"]


@pytest.mark.parametrize("name", ["serve_gpt2_1p5b_chat",
                                  "serve_gpt2_1p5b_batch"])
def test_serve_sound_then_token_altered(name, monkeypatch, capsys):
    cell = exact(tiny_cell(name))
    sound = run.run_cell(cell, SEED, 2.0, False, None,
                         control=cell.config["precision"]["below"])
    assert sound["correct"] and sound["failed"] == 0 and sound["attempted"] > 0
    said = [eval(line, {"null": None, "true": True, "false": False})
            for line in capsys.readouterr().out.splitlines()
            if line.startswith("{")]
    control = next(d["control"] for d in said if "reference_s" in d)
    program = next(d["window"] for d in said if "window" in d)
    # at this size greedy text soon repeats with wide margins, so the control
    # may flip no token at all; its reading at the cell's own size is PERF.md's
    assert control is not None and control >= program[
        "served_logit_widest_gap"]

    from building_llm_from_scratch_tpu.serving.engine import DecodeEngine

    real = DecodeEngine._accept_token
    vocab = cell.config["model"]["vocab_size"]
    monkeypatch.setattr(
        DecodeEngine, "_accept_token",
        lambda self, slot, req, tok, gen: real(
            self, slot, req, (tok + 1) % vocab if len(req.output_ids) == 2
            else tok, gen))
    broken = run.run_cell(cell, SEED, 2.0, False, None)
    assert not broken["correct"] and broken["failed"] == 0
