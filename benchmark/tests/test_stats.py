"""Percentile, token-gap and spread arithmetic."""

import math

import pytest

from benchmark import peaks, stats


def test_percentile():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 50) == 50.5
    assert stats.percentile(xs, 95) == pytest.approx(95.05)
    assert stats.percentile([3.0], 95) == 3.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_token_gaps_are_per_request():
    gaps = stats.token_gaps([[0.0, 0.1, 0.3], [5.0], [1.0, 1.5]])
    assert gaps == pytest.approx([0.1, 0.2, 0.5])


def test_longest_tick_gaps_merge_a_ticks_stamps():
    a = [10.0, 10.1, 10.25, 10.9]
    b = [10.0005, 10.1004, 10.2503, 10.9001, 11.0]
    assert stats.longest_tick_gaps([a, b], 10.0, n=2) == [
        [0.25, 650.0], [0.1, 150.0]]


def test_iqr_share():
    assert stats.iqr_share([10, 10, 10, 10, 10, 10]) == 0
    assert stats.iqr_share([98, 99, 100, 100, 101, 102]) == pytest.approx(0.025)


def test_parameter_and_flop_counts_of_the_published_sizes():
    from benchmark import spec

    small = spec.load_cell("train_gpt2_124m_pretrain").config["model"]
    xl = spec.load_cell("serve_gpt2_1p5b_chat").config["model"]
    assert peaks.n_params(small) == 163_009_536          # untied head
    assert peaks.n_params(xl) == 1_637_792_000
    assert peaks.train_flops_per_token(small, 1024) == pytest.approx(
        6 * 123_532_032 + 6 * 12 * 1024 * 768)
    assert peaks.kv_bytes_per_position(xl, 2) == 2 * 48 * 1600 * 2
    with pytest.raises(SystemExit):
        peaks.peaks_for("cpu")


def test_a_stall_of_a_twentieth_of_the_window_moves_first_token_p95_alone():
    """Why ``ttft_p95_ms`` is read per layer: 200 requests over 50 s, a token
    every 60 ms tick; then the same with the host standing still for 3 s."""
    tick, n_tokens = 0.06, 70

    def tails(stall_from=None, stall_s=0.0):
        def held(t):
            if stall_from is not None and stall_from <= t < stall_from + stall_s:
                return stall_from + stall_s
            return t
        ttft, stamps = [], []
        for i in range(200):
            due = i * 0.25 + 0.013 * (i % 5)
            first = math.ceil(due / tick) * tick + 0.01
            ts = [held(first + j * tick) for j in range(n_tokens)]
            ttft.append(ts[0] - due)
            stamps.append(ts)
        return (stats.percentile(ttft, 95),
                stats.percentile(stats.token_gaps(stamps), 95))

    quiet, stalled = tails(), tails(stall_from=20.0, stall_s=3.0)
    assert quiet[0] < 2 * tick and stalled[0] > 5 * quiet[0]
    assert abs(stalled[1] - quiet[1]) < 1e-6
