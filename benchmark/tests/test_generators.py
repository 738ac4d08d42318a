"""The generators: the same seed gives the same schedule, every seed the same
set of lengths and gaps in another order, lengths inside their clips."""

import numpy as np
import pytest

from benchmark import spec
from benchmark.generators import requests, token_stream

SERVING = ["serve_gpt2_1p5b_batch", "serve_gpt2_1p5b_chat"]


@pytest.mark.parametrize("name", SERVING)
def test_same_seed_same_plan_other_seed_same_work(name):
    cell = spec.load_cell(name)
    model = cell.config["model"]
    a, b, c = (requests.plan(cell.traffic, model, s, 40.0)
               for s in (3_000_000_019, 3_000_000_019, 7))
    assert [(p.due_s, p.max_new_tokens, p.seed, p.prompt_ids.tolist())
            for p in a] == [(p.due_s, p.max_new_tokens, p.seed,
                             p.prompt_ids.tolist()) for p in b]
    assert sorted(len(p.prompt_ids) for p in a) == sorted(
        len(p.prompt_ids) for p in c)
    assert sorted(p.max_new_tokens for p in a) == sorted(
        p.max_new_tokens for p in c)
    assert [len(p.prompt_ids) for p in a] != [len(p.prompt_ids) for p in c]


@pytest.mark.parametrize("name", SERVING)
def test_lengths_inside_clips_and_context(name):
    cell = spec.load_cell(name)
    t, model = cell.traffic, cell.config["model"]
    plan = requests.plan(t, model, 11, 40.0)
    for p in plan:
        assert t["prompt"]["min"] <= len(p.prompt_ids) <= t["prompt"]["max"]
        assert t["output"]["min"] <= p.max_new_tokens <= t["output"]["max"]
        assert len(p.prompt_ids) + p.max_new_tokens <= model["context_length"]
        assert 0 <= p.prompt_ids.min() and p.prompt_ids.max() < model["vocab_size"]
    greedy = [p for p in plan if p.temperature == 0.0]
    assert len(plan) // 9 <= len(greedy) <= len(plan) // 7 + 1


def test_open_loop_arrivals_fill_the_window_at_the_rate():
    cell = spec.load_cell("serve_gpt2_1p5b_chat")
    rate = cell.traffic["arrivals"]["rate_per_s"]
    for seed in (1, 2):
        plan = requests.plan(cell.traffic, cell.config["model"], seed, 40.0)
        due = [p.due_s for p in plan]
        assert len(plan) == round(rate * 40.0)
        assert due == sorted(due) and due[0] == 0.0 and due[-1] < 40.0
    gaps = requests.exponential_gaps(1000, 5.0, 200.0)
    # exponential: the median gap is ln 2 of the mean gap
    assert np.median(gaps) / gaps.mean() == pytest.approx(np.log(2), rel=0.02)


def test_token_stream_rows_differ_and_repeat():
    cell = spec.load_cell("train_gpt2_124m_pretrain")
    model = cell.config["model"]
    x, y = token_stream.batch(cell.traffic, model, 5, 0)
    x2, _ = token_stream.batch(cell.traffic, model, 5, 0)
    x3, _ = token_stream.batch(cell.traffic, model, 5, 1)
    assert x.shape == y.shape == (8, 1024) and (x == x2).all()
    assert (x[:, 1:] == y[:, :-1]).all() and not (x == x3).all()
    assert len({row.tobytes() for row in x}) == 8
