"""Mamba-1 selective-state-space layers around one NoPE multi-query attention
layer, a dense SwiGLU and a tied head (AI21-Jamba2-3B, ``model_type: jamba``)
at the debug size (one period of fourteen layers: seven 'ssm', one 'full',
six 'ssm'; 64 channels of 8 states; 4 query heads over ONE key-value head of
16), against its plain reference (``benchmark/reference/jamba.py``, which
imports nothing of the program and runs the recurrence token by token): the
three forms of the scan, the slot cache's state beside two layers' keys and
values, continuous batching over rows whose memory is no prefix, and what
the engine refuses to a layer that holds a recurrent state, either kind.

``init_params`` draws the family's init (a channel's decay rates A = 1..N,
its time step in [0.001, 0.1], the skip D = 1), so slow channels (a state
that remembers a thousand tokens) and the skip are held to the reference.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import jamba as ref
from building_llm_from_scratch_tpu.configs import (
    UNSUPPORTED,
    get_config,
    refuse_unsupported,
)
from building_llm_from_scratch_tpu.generate import generate
from building_llm_from_scratch_tpu.models import transformer as tf
from building_llm_from_scratch_tpu.obs.metrics import get_metrics
from building_llm_from_scratch_tpu.ops import selective_scan as ss
from building_llm_from_scratch_tpu.serving import (
    DecodeEngine,
    KVCachePolicy,
    SamplingParams,
)

CHUNK = 16
CHUNKED = KVCachePolicy(prefill_chunk=CHUNK)
GREEDY = dict(temperature=0.0, ignore_eos=True)
#: float32 against float32 under the highest matmul precision: rounding
#: alone (the readings are 1e-7 to 3e-6); the reference with bfloat16
#: operands reads 2e-3 and a bfloat16 state 3e-4 (the tests below), so either
#: fails it
TOL = 2e-5


def debug_cfg(**kw):
    return get_config("jamba2", "3B", debug=True, dtype="fp32").replace(**kw)


@pytest.fixture(scope="module")
def model():
    cfg = debug_cfg()
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    # biases the init leaves at zero, drawn: the convolution's is compared
    ssm = params["blocks"]["ssm"]
    ssm["conv_b"] = 0.1 * jax.random.normal(jax.random.PRNGKey(7),
                                            ssm["conv_b"].shape)
    return cfg, params, dataclasses.asdict(cfg)


def tokens_of(cfg, n, seed=1, rows=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, n), 0,
                              cfg.vocab_size)


def prefill(cfg, params, cache, seq, n_prompt, slot, chunked):
    """The prompt into ``slot`` as serving does it: chunks of CHUNK (the last
    one padded) or one bucket of 64. -> (logits at the last position,
    cache)."""
    if not chunked:
        padded = np.zeros((1, 64), np.int32)
        padded[0, :n_prompt] = seq[:n_prompt]
        return jax.jit(lambda c, t: tf.prefill_into_slot(
            params, cfg, t, jnp.int32(n_prompt), jnp.int32(slot), c))(
                cache, padded)
    chunk = jax.jit(lambda c, t, s: tf.prefill_chunk_into_slot(
        params, cfg, t, s, jnp.int32(n_prompt), jnp.int32(slot), c))
    for lo in range(0, n_prompt, CHUNK):
        piece = np.zeros((1, CHUNK), np.int32)
        hi = min(lo + CHUNK, n_prompt)
        piece[0, :hi - lo] = seq[lo:hi]
        logits, cache = chunk(cache, piece, jnp.int32(lo))
    return logits, cache


def decode(cfg, params, cache, token, length, slot, S=3):
    toks = np.zeros((S, 1), np.int32)
    toks[slot] = token
    lengths = np.zeros((S,), np.int32)
    lengths[slot] = length
    logits, cache = jax.jit(lambda c, t, l: tf.decode_slots(
        params, cfg, t, l, c, live=jnp.arange(S) == slot))(
            cache, toks, lengths)
    return logits[slot], cache


# -- (a) the configuration ---------------------------------------------------

def test_published_configuration_counts_to_the_digit():
    cfg = get_config("jamba2", "3B", target_context_length=None)
    assert cfg.num_params() == 3_029_337_472
    assert cfg.replace(n_layers=14).num_params() == 1_598_556_096
    assert cfg.layers_of("full") == (7, 21) and len(cfg.state_layers) == 26
    assert cfg.state_shapes("ssm") == ((3, 5120), (16, 5120))
    assert (cfg.n_heads, cfg.n_kv_groups, cfg.head_dim) == (20, 1, 128)
    tiny = debug_cfg()
    assert tiny.n_layers == 14 and not tiny.is_moe
    params = tf.init_params(tiny, jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree_util.tree_leaves(params)) == \
        tiny.num_params()
    # the mixers are stacked by kind: thirteen and one
    blocks = params["blocks"]
    assert blocks["ssm"]["w_in"].shape[0] == 13
    assert blocks["attn"]["wq"].shape[0] == 1
    assert blocks["mlp"]["up"].shape[0] == 14 and "head" not in params


def test_a_config_with_ssm_layers_says_what_they_are():
    with pytest.raises(ValueError, match="ssm_inner"):
        debug_cfg(ssm_inner=0)
    with pytest.raises(ValueError, match="'linear' or 'ssm'"):
        debug_cfg(layer_kinds=("ssm", "conv"))


# -- (b) the program against one pass of the reference -----------------------

def test_forward_matches_reference(model):
    cfg, params, m = model
    seq = tokens_of(cfg, 48, seed=2, rows=2)
    with jax.default_matmul_precision("highest"):
        got = tf.forward(params, cfg, seq)
        want = ref.logits_fn(params, m, seq)
        blocked = ref.logits_fn(params, m, seq, block_rows=8, piece_rows=16)
    assert float(jnp.abs(got - want).max()) < TOL
    # in pieces (the state and the tail go from piece to piece) and blocks
    assert float(jnp.abs(blocked - want).max()) < 1e-5


@pytest.mark.parametrize("chunked", [False, True],
                         ids=["bucketed", "three_chunks"])
def test_cached_path_matches_reference(model, chunked):
    """37 prompt tokens (three chunks of 16, the last with 11 pads, or one
    bucket of 64 with 27) into a slot that held another request's state,
    then 20 decode ticks: float32 against float32 agrees to rounding at
    every position."""
    cfg, params, m = model
    seq = np.asarray(tokens_of(cfg, 57)[0])
    policy = CHUNKED if chunked else KVCachePolicy()
    cache = tf.init_slot_cache(cfg, 3, cfg.context_length, policy=policy)
    dirty = lambda a: None if a is None else a + 1
    cache = dict(cache, state=[dirty(a) for a in cache["state"]],
                 conv=[dirty(a) for a in cache["conv"]])
    with jax.default_matmul_precision("highest"):
        logits, cache = prefill(cfg, params, cache, seq, 37, 1, chunked)
        got = [logits]
        for t in range(37, 57):
            logits, cache = decode(cfg, params, cache, seq[t], t, 1)
            got.append(logits)
        want = ref.logits_fn(params, m, seq[None])[0, 36:]
    assert float(jnp.abs(jnp.stack(got) - want).max()) < TOL
    # an 'ssm' layer holds no positions, the full one no state
    full = [l == 7 for l in range(14)]
    assert [a is not None for a in cache["k"]] == full
    assert [a is None for a in cache["state"]] == full
    assert cache["state"][0].shape == (3, 8, 64)
    assert cache["state"][0].dtype == jnp.float32


def test_lower_precision_fails_the_tolerance(model, monkeypatch):
    """What ``TOL`` has to catch: the reference with bfloat16 operands, and
    the program with its state rounded to bfloat16 after every token."""
    cfg, params, m = model
    seq = tokens_of(cfg, 57)
    with jax.default_matmul_precision("highest"):
        want = ref.logits_fn(params, m, seq)
        low = ref.logits_fn(params, m, seq, precision="bf16")
    assert float(jnp.abs(low - want).max()) > 50 * TOL
    real = ss.selective_step

    def rounded(*a):
        y, state = real(*a)
        return y, state.astype(jnp.bfloat16).astype(jnp.float32)

    monkeypatch.setattr(ss, "selective_step", rounded)
    with jax.default_matmul_precision("highest"):
        got = tf.forward(params, cfg, seq)
    assert float(jnp.abs(got - want).max()) > 5 * TOL


def test_generate_matches_reference_greedy(model):
    cfg, params, m = model
    prompt = np.asarray(tokens_of(cfg, 21, seed=5))
    out = generate(params, cfg, prompt, max_new_tokens=30, temperature=0.0,
                   eos_id=None, rng=jax.random.PRNGKey(0))
    seq = np.asarray(out[0])
    want = np.asarray(jnp.argmax(ref.logits_fn(params, m, seq[None, :-1])[0],
                                 -1))[20:]
    assert seq.shape == (51,) and (seq[21:] == want).all()


# -- (c) the three forms of the scan -----------------------------------------

def scan_inputs(B, T, I, N, seed=0):
    """From a non-zero state, with the stress a trained model brings: a
    channel that forgets everything in a token (delta * A = -30), one that
    forgets nothing (delta 0: padding), steps from 0.001 to 1."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    u = jax.random.normal(ks[0], (B, T, I))
    delta = jnp.exp(jax.random.uniform(ks[1], (B, T, I), minval=jnp.log(1e-3),
                                       maxval=0.0))
    delta = delta.at[:, :, 1].set(0.0)
    A = -jnp.broadcast_to(jnp.arange(1.0, N + 1)[:, None], (N, I))
    A = A.at[:, 0].set(-30.0)
    Bm = jax.random.normal(ks[2], (B, T, N))
    Cm = jax.random.normal(ks[3], (B, T, N))
    D = jax.random.normal(ks[4], (I,))
    state = jax.random.normal(ks[5], (B, N, I))
    return u, delta, A, Bm, Cm, D, state


@pytest.mark.parametrize("B,T,I,N", [(2, 24, 2048, 8), (1, 33, 1024, 16)])
def test_three_forms_of_the_scan_agree(B, T, I, N):
    args = scan_inputs(B, T, I, N, seed=T)
    u, delta, A, Bm, Cm, D, state = args
    y_scan, s_scan = ss.selective_scan(*args)
    # the kernel, in interpret mode
    assert ss.selective_scan_path(T, I, N, backend="tpu") == "kernel"
    y_k, s_k = ss.selective_scan_kernel(*args, interpret=True)
    assert float(jnp.abs(y_k - y_scan).max()) < 1e-5
    assert float(jnp.abs(s_k - s_scan).max()) < 1e-5
    # one step at a time
    s, ys = state, []
    for t in range(T):
        y, s = ss.selective_step(u[:, t], delta[:, t], A, Bm[:, t], Cm[:, t],
                                 D, s)
        ys.append(y)
    assert float(jnp.abs(jnp.stack(ys, 1) - y_scan).max()) < 1e-5
    assert float(jnp.abs(s - s_scan).max()) < 1e-5
    # a channel whose step is 0 kept its state bit for bit
    assert bool((s_k[:, :, 1] == state[:, :, 1]).all())
    assert bool(jnp.isfinite(y_k).all())


def test_path_names_the_form_by_what_the_call_is():
    assert ss.selective_scan_path(1, 5120, 16, backend="tpu") == "step"
    assert ss.selective_scan_path(512, 5120, 16, backend="tpu") == "kernel"
    assert ss.selective_scan_path(512, 5120, 16, backend="cpu") == "scan"
    # shapes the kernel does not take fall back to the sequential scan
    assert ss.selective_scan_path(512, 64, 8, backend="tpu") == "scan"
    assert ss.selective_scan_path(512, 5120, 64, backend="tpu") == "scan"
    assert ss.selective_scan_path(4096, 5120, 16, backend="tpu") == "scan"


def test_slot_pass_through_the_kernel_matches_the_scan(model, monkeypatch):
    """A chunk of the slot pass on the kernel's path (interpret mode, at the
    smallest width the kernel takes) serves the logits of the sequential
    scan."""
    cfg = debug_cfg(n_layers=2, layer_kinds=("ssm", "full"), ssm_inner=1024)
    params = tf.init_params(cfg, jax.random.PRNGKey(3))
    seq = np.asarray(tokens_of(cfg, 24)[0])
    fresh = lambda: tf.init_slot_cache(cfg, 2, cfg.context_length,
                                       policy=CHUNKED)
    want, _ = prefill(cfg, params, fresh(), seq, 24, 1, True)
    monkeypatch.setattr(
        tf, "selective_scan_path",
        lambda T, I, N: ss.selective_scan_path(T, I, N, backend="tpu"))
    monkeypatch.setattr(
        tf, "selective_scan_kernel",
        lambda *a: ss.selective_scan_kernel(*a, interpret=True))
    got, cache = prefill(cfg, params, fresh(), seq, 24, 1, True)
    assert float(jnp.abs(got - want).max()) < TOL
    assert float(jnp.abs(cache["state"][0][1]).max()) > 0


# -- (d) rows whose memory is no prefix --------------------------------------

def test_a_row_that_does_not_decode_keeps_state_and_tail_bit_for_bit(model):
    cfg, params, _ = model
    seq = np.asarray(tokens_of(cfg, 40)[0])
    cache = tf.init_slot_cache(cfg, 3, cfg.context_length, policy=CHUNKED)
    _, cache = prefill(cfg, params, cache, seq, 20, 0, True)
    _, cache = prefill(cfg, params, cache, seq[5:], 19, 2, True)
    before = jax.tree_util.tree_map(np.asarray, cache)
    _, after = decode(cfg, params, cache, seq[20], 20, 0)
    for name in ("state", "conv"):
        for l in cfg.state_layers:
            a, b = before[name][l], np.asarray(after[name][l])
            assert np.array_equal(a[1:], b[1:]), (name, l)
            assert not np.array_equal(a[0], b[0]), (name, l)


# -- (d2) the tick's step walks the decoding rows in place -------------------

def step_inputs(S, I, N, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    u = jax.random.normal(ks[0], (S, I))
    delta = jax.nn.softplus(jax.random.normal(ks[1], (S, I)))
    A = -jnp.broadcast_to(jnp.arange(1.0, N + 1)[:, None], (N, I))
    Bm = jax.random.normal(ks[2], (S, N))
    Cm = jax.random.normal(ks[3], (S, N))
    D = jax.random.normal(ks[4], (I,))
    return (u, delta, A, Bm, Cm, D), jax.random.normal(ks[5], (S, N, I))


@pytest.mark.parametrize("S,I,N,live", [
    (192, 5120, 16, range(0, 192, 3)),              # the cell's widths
    (8, 5120, 16, [6]),
    (8, 1024, 8, []),
    (8, 1024, 8, [0]),
    (8, 1024, 8, [7]),
    (8, 1024, 8, [0, 1, 2]),                         # live rows first
    (8, 1024, 8, [5, 6, 7]),                         # last
    (8, 2048, 16, [1, 4, 6]),                        # scattered
    (8, 1024, 8, range(8)),                          # all
    (192, 1024, 8, range(192)),
], ids=lambda v: str(len(v)) if not isinstance(v, int) else str(v))
def test_the_walk_is_the_step_on_the_live_rows_and_reads_no_other(S, I, N,
                                                                   live):
    """``selective_step_rows`` (interpret mode) against ``selective_step``
    and a select. Every state a tick may not read holds NaN and 3e38: those
    rows come back bit for bit, and nothing non-finite reaches ``y``."""
    assert ss.supports_step_rows(I, N)
    token, state = step_inputs(S, I, N, seed=S + I + len(live))
    mask = np.zeros((S,), bool)
    mask[list(live)] = True
    poison = jnp.where(jnp.arange(I) % 2 == 0, jnp.nan, 3e38)
    state = jnp.where(mask[:, None, None], state, poison)
    want_y, want_s = ss.selective_step(*token, state)
    mask = jnp.asarray(mask)
    table = ss.live_rows_table(mask)
    assert table[:-1].tolist() == sorted(live) + [S] * (S - len(live))
    assert int(table[-1]) == len(live)
    y, new = jax.jit(lambda *a: ss.selective_step_rows(
        *a, table, interpret=True))(*token, state)
    dead = ~np.asarray(mask)
    assert np.array_equal(np.asarray(new)[dead], np.asarray(state)[dead],
                          equal_nan=True)
    assert bool(jnp.isfinite(y).all()) and not np.asarray(y)[dead].any()
    on = np.asarray(mask)
    assert np.isfinite(np.asarray(new)[on]).all()
    np.testing.assert_allclose(np.asarray(y)[on], np.asarray(want_y)[on],
                               rtol=1e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(new)[on], np.asarray(want_s)[on],
                               rtol=1e-6, atol=2e-6)


def test_state_step_path_names_the_form_by_what_the_tick_is(model):
    cfg = debug_cfg(ssm_inner=1024)
    cache = tf.init_slot_cache(cfg, 4, cfg.context_length, policy=CHUNKED)
    l = cfg.state_layers[0]
    path = lambda **kw: tf.state_step_path(cache, "ssm", kw.pop("Tq", 1), **{
        "layer": l, "rows_named": True, "backend": "tpu", **kw})
    assert path() == "live_rows"
    assert path(backend="cpu") == "whole_buffer"
    assert path(backend=None) == "whole_buffer"          # this is a CPU
    assert path(rows_named=False) == "whole_buffer"
    assert path(Tq=3) == "whole_buffer"
    # a width that is not whole groups of 1024, states that are not whole
    # tiles or too many
    narrow = tf.init_slot_cache(model[0], 4, 64, policy=CHUNKED)
    assert tf.state_step_path(narrow, "ssm", 1, layer=l, rows_named=True,
                              backend="tpu") == "whole_buffer"
    assert ss.supports_step_rows(5120, 16)
    assert not ss.supports_step_rows(5120, 12)
    assert not ss.supports_step_rows(5120, 64)
    assert not ss.supports_step_rows(5000, 16)
    # the host's twin: decoding rows in a layer that walks, slots elsewhere
    assert ss.state_rows_walked(72, 192, 26, 0) == 72 * 26
    assert ss.state_rows_walked(72, 192, 0, 26) == 192 * 26
    assert ss.state_rows_walked(5, 48, 2, 1) == 5 * 2 + 48


def walking(monkeypatch):
    """The rule as a TPU answers it, in the program and in the engine."""
    from building_llm_from_scratch_tpu.serving import engine as engine_mod

    rule = tf.state_step_path
    forced = lambda *a, **kw: rule(*a, **dict(kw, backend="tpu"))
    monkeypatch.setattr(tf, "state_step_path", forced)
    monkeypatch.setattr(engine_mod, "state_step_path", forced)


def test_a_walked_tick_keeps_the_other_rows_bit_for_bit(monkeypatch):
    """The tick program on the walk's path: a row that does not decode keeps
    its state and tail bit for bit though it holds NaN, and the decoding
    row's logits are those of the whole-buffer form."""
    cfg = debug_cfg(n_layers=3, layer_kinds=("ssm", "full", "ssm"),
                    ssm_inner=1024)
    params = tf.init_params(cfg, jax.random.PRNGKey(3))
    seq = np.asarray(tokens_of(cfg, 40)[0])
    cache = tf.init_slot_cache(cfg, 3, cfg.context_length, policy=CHUNKED)
    _, cache = prefill(cfg, params, cache, seq, 20, 0, True)
    cache["state"] = [None if a is None else a.at[1:].set(jnp.nan)
                      for a in cache["state"]]
    before = jax.tree_util.tree_map(np.asarray, cache)
    want, _ = decode(cfg, params, cache, seq[20], 20, 0)
    walking(monkeypatch)
    got, after = decode(cfg, params, cache, seq[20], 20, 0)
    assert bool(jnp.isfinite(got).all())
    assert float(jnp.abs(got - want).max()) < TOL
    for name in ("state", "conv"):
        for l in cfg.state_layers:
            a, b = before[name][l], np.asarray(after[name][l])
            assert np.array_equal(a[1:], b[1:], equal_nan=True), (name, l)
            assert not np.array_equal(a[0], b[0]), (name, l)


def test_engine_tokens_identical_with_the_walk_on_and_off(monkeypatch):
    """Greedy requests through the engine, chunked prefill between decode
    ticks and slots used again, with the tick's step on either path: the same
    tokens; on the walk's path every tick's ``state_rows_touched`` is its
    ``state_rows``, a tick whose one decoding row stands beside a free slot
    and one between two of its chunks among them."""
    cfg = debug_cfg(n_layers=3, layer_kinds=("ssm", "full", "ssm"),
                    ssm_inner=1024)
    params = tf.init_params(cfg, jax.random.PRNGKey(3))
    prompts = [np.asarray(tokens_of(cfg, n, seed=n)[0])
               for n in (5, 40, 23, 9)]

    def serve():
        eng = DecodeEngine(cfg, params, None, n_slots=3,
                           max_len=cfg.context_length, kv_policy=CHUNKED,
                           max_queue=8)
        first = eng.submit(prompts[0], SamplingParams(max_new_tokens=14,
                                                      **GREEDY))
        while not first.output_ids:
            eng.step()
        rest = [eng.submit(p, SamplingParams(max_new_tokens=6, **GREEDY))
                for p in prompts[1:]]
        eng.run_until_idle()
        return eng, [list(r.output_ids) for r in [first] + rest]

    eng, want = serve()
    assert eng.state_step == "whole_buffer"
    assert eng.stats()["state_step"] == eng.healthz_payload()["state_step"] \
        == "whole_buffer"
    mark = get_metrics().recent("tick")[-1]["t1"]
    walking(monkeypatch)
    eng, got = serve()
    assert eng.state_step == eng.stats()["state_step"] == "live_rows"
    assert got == want
    ticks = [t for t in get_metrics().recent("tick")
             if t.get("state_rows") and t["t0"] >= mark]
    assert ticks and all(
        t["state_rows_touched"] == t["state_rows"] == 2 * t["rows"]
        for t in ticks)
    assert any(t["rows"] == 1 and t.get("chunk_tokens") == CHUNK
               for t in ticks)


def test_coresident_requests_match_the_reference(model):
    """Three requests of unlike lengths through the engine, chunked prefill
    between decode ticks, slots used again: each one's greedy tokens are the
    reference's."""
    cfg, params, m = model
    eng = DecodeEngine(cfg, params, None, n_slots=2,
                       max_len=cfg.context_length, kv_policy=CHUNKED,
                       max_queue=8)
    prompts = [np.asarray(tokens_of(cfg, n, seed=n)[0]) for n in (23, 5, 37)]
    reqs = [eng.submit(p, SamplingParams(max_new_tokens=9, **GREEDY))
            for p in prompts]
    eng.run_until_idle()
    with jax.default_matmul_precision("highest"):
        for p, r in zip(prompts, reqs):
            assert r.finish_reason == "length"
            seq = np.concatenate([p, r.output_ids])
            want = np.asarray(jnp.argmax(
                ref.logits_fn(params, m, seq[None, :-1])[0], -1))
            assert (seq[len(p):] == want[len(p) - 1:]).all()
    assert eng.selective_scan == {"tick": "step", "prefill": "scan"}
    assert eng.stats()["selective_scan"] == eng.selective_scan
    assert eng.healthz_payload()["selective_scan"] == eng.selective_scan
    assert eng.linear_attention is None


def test_state_bytes_the_ledger_and_the_tick_record(model):
    cfg, params, _ = model
    S = 3
    eng = DecodeEngine(cfg, params, None, n_slots=S,
                       max_len=cfg.context_length, kv_policy=CHUNKED,
                       max_queue=8)
    bps = eng.kv_policy.bytes_per_slot(cfg, cfg.context_length)
    I, N = cfg.ssm_inner, cfg.ssm_state
    assert bps["state_bytes"] == 13 * (N * I * 4 + 3 * I * 4)
    assert bps["kv_bytes"] == 2 * cfg.context_length * 16 * 4
    assert bps["total_bytes"] == bps["kv_bytes"] + bps["state_bytes"]
    held = sum(a.nbytes for k in ("state", "conv") for a in eng.cache[k]
               if a is not None)
    assert held == S * bps["state_bytes"]
    assert eng.layout()["state"] == {"layers": 13,
                                     "bytes_per_slot": bps["state_bytes"]}
    reqs = [eng.submit(np.arange(3, 3 + n) % cfg.vocab_size,
                       SamplingParams(max_new_tokens=4, **GREEDY))
            for n in (20, 7)]
    eng.run_until_idle()
    assert all(r.finish_reason == "length" for r in reqs)
    ticks = [t for t in get_metrics().recent("tick") if t.get("state_rows")]
    assert ticks and all(
        t["state_rows"] == 13 * t["rows"]
        and t["state_rows_touched"] == 13 * S for t in ticks[-3:])
    chunks = [t for t in get_metrics().recent("tick")
              if t.get("chunk_tokens")]
    # 20 tokens are a chunk of 16 and one of 4; 7 one of 7
    assert sorted(t["chunk_tokens"] for t in chunks[-3:]) == [4, 7, 16]


# -- (e) what is refused, by what the config is ------------------------------

STATE_ROWS = [(name, why) for name, needs, why in UNSUPPORTED
              if needs == "state"]


def state_only(kind):
    """A dense model whose one other kind of layer holds a state."""
    if kind == "ssm":
        return debug_cfg(name="another-name")
    return get_config("solar_open2", "250B", debug=True).replace(
        name="another-name", n_routed_experts=0, n_experts_per_tok=0,
        n_shared_experts=0)


@pytest.mark.parametrize("kind", ["linear", "ssm"])
@pytest.mark.parametrize("feature,why", STATE_ROWS,
                         ids=[name for name, _ in STATE_ROWS])
def test_a_recurrent_state_of_either_kind_refuses_every_row(kind, feature,
                                                            why):
    cfg = state_only(kind)
    with pytest.raises(ValueError) as e:
        refuse_unsupported(cfg, **{feature: True})
    label = {"linear": "linear-attention layers",
             "ssm": "state-space layers"}[kind]
    assert str(e.value) == f"another-name ({label}): {why}"
    refuse_unsupported(get_config("GPT2", "124M"), **{feature: True})


def test_the_list_holds_each_state_row_once():
    assert len(STATE_ROWS) == 8
    assert len({name for name, _ in STATE_ROWS}) == 8
    assert not [row for row in UNSUPPORTED if row[1] in ("linear", "ssm")]


@pytest.mark.parametrize("kw,match", [
    (dict(kv_policy=KVCachePolicy(prefill_chunk=4, paged=True,
                                   page_tokens=4)), "a page holds positions"),
    (dict(kv_policy=KVCachePolicy(prefill_chunk=4, prefix_cache=True)),
     "the state after them"),
    (dict(kv_policy=KVCachePolicy(kv_quant="int8")), "no int8 form"),
    (dict(spec_k=2), "already moved the recurrent state"),
    (dict(adapters=object()), "a layer that holds a state has others"),
])
def test_engine_refuses_what_a_state_does_not_support(model, kw, match):
    cfg, params, _ = model
    with pytest.raises(ValueError, match=match):
        DecodeEngine(cfg, params, None, n_slots=2, **kw)


@pytest.mark.parametrize("flags,match", [
    ((), "no tokenizer is registered"),
    (("--byte_tokenizer", "--load_weights"), "no checkpoint converter"),
    (("--byte_tokenizer", "--use_lora"), "LoRA"),
    (("--byte_tokenizer", "--run_type", "multi_chip", "--sp", "2"),
     "from shard to shard"),
])
def test_flags_refuse_what_the_config_does_not_support(tmp_path, flags,
                                                       match):
    from building_llm_from_scratch_tpu.args import get_args

    base = ["--data_dir", str(tmp_path), "--model", "jamba2",
            "--num_params", "3B", "--debug"]
    with pytest.raises(ValueError, match=match):
        get_args(base + list(flags))
    assert get_args(base + ["--byte_tokenizer"]).model == "jamba2"
