"""Serving subsystem tests (serving/): engine correctness — slot reuse,
engine-vs-generate() token parity, mid-stream admission isolation, queue
backpressure, eos/max-token retirement, per-request RNG reproducibility,
zero-recompile discipline — plus the generate() per-row eos satellite and
the ops-level slot primitives they sit on.
"""

import http.client
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from building_llm_from_scratch_tpu.configs import ModelConfig
from building_llm_from_scratch_tpu.generate import generate
from building_llm_from_scratch_tpu.models import init_params
from building_llm_from_scratch_tpu.serving import (
    DecodeEngine,
    QueueFullError,
    RequestQueue,
    SamplingParams,
    Scheduler,
)
from building_llm_from_scratch_tpu.serving.request import Request


def tiny_cfg(ctx=64, **kw):
    base = dict(name="serve-tiny", vocab_size=96, context_length=ctx,
                emb_dim=32, n_heads=2, n_layers=2, hidden_dim=64,
                n_kv_groups=2, norm="layernorm", positional="learned",
                activation="gelu", drop_rate=0.0, eos_id=1)
    base.update(kw)
    return ModelConfig(**base)


@pytest.fixture(scope="module")
def model():
    cfg = tiny_cfg()
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def solo_tokens(params, cfg, prompt, sp: SamplingParams):
    """The engine's expected output for one request: one-shot generate()
    with the matching seed/params (shared rng derivation + sampling)."""
    out, n = generate(params, cfg, np.asarray(prompt)[None],
                      max_new_tokens=sp.max_new_tokens,
                      temperature=sp.temperature, top_k=sp.top_k,
                      eos_id=(None if sp.ignore_eos
                              else (sp.eos_id if sp.eos_id is not None
                                    else cfg.eos_id)),
                      rng=jax.random.PRNGKey(sp.seed),
                      return_n_generated=True)
    Tp = len(prompt)
    return [int(t) for t in out[0, Tp: Tp + int(n[0])]]


# ---------------------------------------------------------------------------
# ops-level slot primitives
# ---------------------------------------------------------------------------

def test_slot_cache_append_per_row_offsets():
    from building_llm_from_scratch_tpu.ops.decode_step import (
        slot_cache_append,
    )

    S, H, T, D = 3, 2, 8, 4
    cache = np.zeros((S, H, T, D), np.float32)
    new = np.arange(S * H * D, dtype=np.float32).reshape(S, H, 1, D)
    lengths = np.array([0, 3, 7], np.int32)
    out = np.asarray(slot_cache_append(jnp.asarray(cache),
                                       jnp.asarray(new), lengths))
    for s, t in enumerate(lengths):
        np.testing.assert_array_equal(out[s, :, t], new[s, :, 0])
        mask = np.ones(T, bool)
        mask[t] = False
        assert (out[s][:, mask] == 0).all()
    # scalar length must equal the shared-offset DUS the decode path uses
    shared = np.asarray(slot_cache_append(jnp.asarray(cache),
                                          jnp.asarray(new),
                                          jnp.asarray(2, jnp.int32)))
    np.testing.assert_array_equal(shared[:, :, 2], new[:, :, 0])


_APPEND_T = 256


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("lengths", [
    [0, 0, 0, 0], [127] * 4, [128] * 4, [_APPEND_T - 1] * 4,
    [0, 127, 128, _APPEND_T - 1],
    # past the end: dynamic_update_slice clamps, so must the kernel
    [_APPEND_T, _APPEND_T + 44, 3, 5],
], ids=["first", "window_end", "window_start", "last", "mixed", "clamped"])
def test_slot_cache_append_per_row_offsets_lane_window(lengths, dtype):
    """The lane-window kernel (interpret mode) against the scatter, which
    stays the reference: bit for bit, every position of both buffers."""
    from building_llm_from_scratch_tpu.ops.decode_step import (
        lane_window_append,
        slot_cache_append,
        supports_lane_append,
    )

    S, H, T, D = 4, 2, _APPEND_T, 16
    assert supports_lane_append(1, T, D, Hkv=H, dtype=dtype)
    ks = jax.random.split(jax.random.PRNGKey(len(lengths) + sum(lengths)), 4)
    K, V = (jax.random.normal(k, (S, H, T, D)).astype(dtype) for k in ks[:2])
    kn, vn = (jax.random.normal(k, (S, H, 1, D)).astype(dtype)
              for k in ks[2:])
    lens = jnp.asarray(lengths, jnp.int32)
    K2, V2 = jax.jit(lambda *a: lane_window_append(*a, interpret=True))(
        K, V, kn, vn, lens)
    for got, pane, new in ((K2, K, kn), (V2, V, vn)):
        want = slot_cache_append(pane, new, lens)
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(
            np.asarray(got.astype(jnp.float32)),
            np.asarray(want.astype(jnp.float32)))
    # and the scatter wrote where the test says it did
    for s, t in enumerate(np.clip(lengths, 0, T - 1)):
        np.testing.assert_array_equal(
            np.asarray(K2[s, :, t].astype(jnp.float32)),
            np.asarray(kn[s, :, 0].astype(jnp.float32)))


def _append_cache(S=2, H=2, T=128, D=16, dtype=jnp.float32, quant=False):
    cache = {"k": [jnp.zeros((S, H, T, D), dtype)],
             "v": [jnp.zeros((S, H, T, D), dtype)]}
    if quant:
        cache["k_scale"] = [jnp.zeros((S, H, T, 1), jnp.float32)]
        cache["v_scale"] = [jnp.zeros((S, H, T, 1), jnp.float32)]
    return cache


@pytest.mark.parametrize("why,Tq,kw", [
    ("verify_tq", 3, {}),
    ("int8_cache", 1, dict(dtype=jnp.int8, quant=True)),
    ("head_dim_128", 1, dict(D=128)),
    ("tmax_not_lane_multiple", 1, dict(T=192 + 8)),
    ("over_vmem_budget", 1, dict(H=256, D=64)),
    ("not_a_tpu", 1, {}),
])
def test_kv_append_gate_refusals_take_the_scatter(why, Tq, kw):
    """Outside the gate the one append rule runs today's scatter: the
    name says so, and the traced write holds no kernel call."""
    from building_llm_from_scratch_tpu.models import transformer as tf

    cache = _append_cache(**kw)
    backend = None if why == "not_a_tpu" else "tpu"
    assert tf.kv_append_path(_append_cache(), 1, backend="tpu") \
        == "lane_window"
    assert tf.kv_append_path(cache, Tq, backend=backend) == "scatter"
    if why == "over_vmem_budget":
        return                                  # nothing small to trace
    S, H, _, D = cache["k"][0].shape
    k = jnp.ones((S, Tq, H, D), jnp.float32)
    lens = jnp.asarray([0, 5], jnp.int32)

    def append(cache, k, lens):
        kv = tf._RowsKV(tiny_cfg(ctx=128), cache, k.shape[1], lens)
        kv._append(0, k, k, lens)
        return kv.result()

    assert "pallas_call" not in str(jax.make_jaxpr(append)(cache, k, lens))


# -- the live-block attention kernel (interpret mode) against
# ``decode_attention``, which stays the reference ---------------------------

_ATTN_T, _ATTN_B = 384, 128


@pytest.fixture
def lane_blocks():
    """The kernel's module; three of its blocks make a test's buffer."""
    from building_llm_from_scratch_tpu.ops import decode_step as ds

    assert ds.LIVE_BLOCK == _ATTN_B
    return ds


def _attn_case(lengths, dtype, G, Hkv=2, T=_ATTN_T, D=16, seed=0):
    S = len(lengths)
    ks = jax.random.split(jax.random.PRNGKey(seed + sum(lengths)), 3)
    q = jax.random.normal(ks[0], (S, 1, Hkv * G, D)).astype(dtype)
    K, V = (jax.random.normal(k, (S, Hkv, T, D)).astype(dtype)
            for k in ks[1:])
    return q, K, V, jnp.asarray(lengths, jnp.int32)


def _attend_whole(q, K, V, lens):
    from building_llm_from_scratch_tpu.ops.attention import decode_attention

    return decode_attention(q, K, V, q_positions=(lens - 1)[:, None],
                            kv_length=lens)


def _close(got, want, dtype):
    assert got.dtype == want.dtype and got.shape == want.shape
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-6
    np.testing.assert_allclose(np.asarray(got.astype(jnp.float32)),
                               np.asarray(want.astype(jnp.float32)),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("G", [1, 4], ids=["mha", "gqa4"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("lengths", [
    [1] * 3, [_ATTN_B - 1] * 3, [_ATTN_B] * 3, [_ATTN_B + 1] * 3,
    [_ATTN_T] * 3, [0, 200, 0, 300, 1],
    # 32 rows are four grid cells of 8: a cell's first block is asked for
    # by the cell before it, whatever the parity of the blocks so far
    [int(n) for n in np.random.default_rng(0).integers(0, _ATTN_T + 1, 32)],
], ids=["one", "block_minus_1", "block", "block_plus_1", "tmax",
        "free_among_live", "mixed32_four_cells"])
def test_live_block_attention_matches_decode_attention(lane_blocks, lengths,
                                                       dtype, G):
    """Every row's output equals ``decode_attention``'s on the same panes,
    at each edge of a block. A free row (length 0) has nothing to attend:
    the reference averages the whole buffer there and the kernel reads one
    block; the engine ignores both, so only its being finite is held."""
    q, K, V, lens = _attn_case(lengths, dtype, G)
    got = jax.jit(lambda *a: lane_blocks.live_block_attention(
        *a, interpret=True))(q, K, V, lens)
    live = np.asarray(lens) > 0
    _close(got[live], _attend_whole(q, K, V, lens)[live], dtype)
    assert bool(jnp.isfinite(got.astype(jnp.float32)).all())


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("garbage", [np.nan, 3e38], ids=["nan", "huge"])
def test_live_block_attention_never_reads_past_a_rows_length(lane_blocks,
                                                             garbage, dtype):
    """NaN, or the largest finite number, in every position past each
    row's length (the rest of its last block and all the blocks beyond):
    nothing of it reaches the output. ``decode_attention`` itself is held
    to the clean panes: 0 * NaN is NaN in its value product."""
    lengths = [1, _ATTN_B - 1, _ATTN_B, _ATTN_B + 1, 300]
    q, K, V, lens = _attn_case(lengths, dtype, G=2, seed=1)
    past = jnp.arange(_ATTN_T)[None, None, :, None] >= lens[:, None, None,
                                                            None]
    dirty = [jnp.where(past, jnp.asarray(garbage, dtype), x) for x in (K, V)]
    got = jax.jit(lambda *a: lane_blocks.live_block_attention(
        *a, interpret=True))(q, *dirty, lens)
    _close(got, _attend_whole(q, K, V, lens), dtype)


# -- the same kernel in the layout ``head_dim`` 128 has (positions on the
# sublanes: blocks of rows, rings by position, rows that do not decode
# unread) ------------------------------------------------------------------

def _rows_case(lengths, dtype, Hkv, G, T=_ATTN_T, seed=0):
    return _attn_case(lengths, dtype, G, Hkv=Hkv, T=T, D=128, seed=seed)


def _attend_ring(q, K, V, lens, window):
    from building_llm_from_scratch_tpu.ops.attention import (
        decode_attention,
        ring_positions,
    )

    return decode_attention(
        q, K, V, q_positions=(lens - 1)[:, None], kv_length=lens,
        kv_positions=ring_positions(lens - 1, K.shape[2]), window=window)


def _live_rows(lane_blocks, q, K, V, lens, **kw):
    assert lane_blocks._rows_block(K.shape[2]) == _ATTN_B
    return jax.jit(lambda *a: lane_blocks.live_block_attention(
        *a, interpret=True, **kw))(q, K, V, lens)


@pytest.mark.parametrize("Hkv,G", [(8, 16), (8, 8), (1, 20), (2, 4)],
                         ids=["rag_8x16", "longdoc_8x8", "widechat_1x20",
                              "llama_gqa_2x4"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("lengths", [
    [0, 1, _ATTN_B - 1, _ATTN_B, _ATTN_B + 1, _ATTN_T],
    # 24 rows are three grid cells of 8: a cell's first block is asked for
    # by the cell before it, over rows that read nothing
    [int(n) * (i % 3 > 0) for i, n in enumerate(
        np.random.default_rng(0).integers(1, _ATTN_T + 1, 24))],
], ids=["edges", "mixed24_three_cells"])
def test_live_rows_attention_matches_decode_attention(lane_blocks, lengths,
                                                      dtype, Hkv, G):
    """The sublane form at the three cells' head groupings: every row's
    output equals ``decode_attention``'s on the same panes at each edge of
    a block; a row of length 0 reads nothing and writes zeros."""
    q, K, V, lens = _rows_case(lengths, dtype, Hkv, G)
    got = _live_rows(lane_blocks, q, K, V, lens)
    live = np.asarray(lens) > 0
    _close(got[live], _attend_whole(q, K, V, lens)[live], dtype)
    assert not np.asarray(got[~live].astype(jnp.float32)).any()


@pytest.mark.parametrize("window", [256, 200],
                         ids=["window_whole_blocks", "window_edge_in_block"])
@pytest.mark.parametrize("lengths", [
    [1, 100, 200, 256, 257, _ATTN_T],
    [_ATTN_T + 1, _ATTN_T + 100, 2 * _ATTN_T - 1, 2 * _ATTN_T],
    [2 * _ATTN_T + 130, 5000, 16384, 3, 0, 300],
], ids=["unwrapped", "wrapped_once", "wrapped_often_beside_short"])
def test_live_rows_attention_reads_a_ring_by_position(lane_blocks, lengths,
                                                      window):
    """A 'sliding' layer's buffer is a ring of 384 positions written at
    ``length mod 384``: the kernel attends the last ``window`` positions
    wherever they lie, as ``decode_attention`` does with ``ring_positions``;
    a row shorter than the ring reads its own prefix only."""
    q, K, V, lens = _rows_case(lengths, jnp.float32, 2, 4, seed=2)
    got = _live_rows(lane_blocks, q, K, V, lens, window=window)
    live = np.asarray(lens) > 0
    _close(got[live], _attend_ring(q, K, V, lens, window)[live], jnp.float32)


@pytest.mark.parametrize("window", [None, 200], ids=["full", "ring"])
@pytest.mark.parametrize("garbage", [np.nan, 3e38], ids=["nan", "huge"])
def test_live_rows_attention_reads_nothing_it_should_not(lane_blocks,
                                                         garbage, window):
    """NaN, or the largest finite number, in every position outside a
    decoding row's live ones (past its length, or behind its window) and in
    EVERY position of the rows that do not decode, one of them a slot
    between two prefill chunks at 16k: nothing of it reaches an output, and
    the rows that do not decode read zeros."""
    from building_llm_from_scratch_tpu.ops.attention import ring_positions

    lengths = [1, _ATTN_B + 1, 16000, 300, 700 if window else _ATTN_T, 40]
    live = jnp.asarray([True, True, False, True, True, False])
    q, K, V, lens = _rows_case(lengths, jnp.float32, 2, 4, seed=3)
    pos = (ring_positions(lens - 1, _ATTN_T) if window
           else jnp.broadcast_to(jnp.arange(_ATTN_T), (len(lengths),
                                                       _ATTN_T)))
    seen = (pos >= 0) & (pos < lens[:, None]) & live[:, None]
    if window:
        seen &= (lens - 1)[:, None] - pos < window
    dirty = [jnp.where(seen[:, None, :, None], x, jnp.float32(garbage))
             for x in (K, V)]
    got = _live_rows(lane_blocks, q, *dirty, lens, live=live, window=window)
    want = (_attend_ring(q, K, V, lens, window) if window
            else _attend_whole(q, K, V, lens))
    _close(got[np.asarray(live)], want[np.asarray(live)], jnp.float32)
    assert not np.asarray(got[~np.asarray(live)]).any()


@pytest.mark.parametrize("lengths,live,window,blocks", [
    ([130, 384, 0, 5], None, None, 2 + 3 + 0 + 1),
    ([130, 16000, 0, 5], [True, False, True, True], None, 2 + 0 + 0 + 1),
    # a ring of 384 and a window of 200: positions 450..649 lie at indices
    # 66..265, three blocks; a row of 200 reads its own prefix, two;
    # positions 800..999 lie at indices 32..231, two
    ([650, 200, 1000], None, 200, 3 + 2 + 2),
    # the window's newest positions wrap the end: 330..383 and 0..145
    ([384 + 146], None, 200, 3),
], ids=["full", "rows_that_do_not_decode", "ring", "ring_wrapping"])
def test_live_positions_read_counts_the_kernels_blocks(lane_blocks, lengths,
                                                       live, window, blocks):
    """The host's twin of the block arithmetic (the engine's
    ``kv_touched``), and the table the kernel is handed, agree."""
    n = np.asarray(lengths)
    live = None if live is None else np.asarray(live)
    assert lane_blocks.live_positions_read(
        n, _ATTN_T, 128, live=live, window=window) == blocks * _ATTN_B
    on_device = lane_blocks.live_block_span(
        jnp.asarray(np.where(live, n, 0) if live is not None else n),
        ring_len=_ATTN_T, block=_ATTN_B, window=window)
    assert int(on_device[1].sum()) == blocks
    # the lane layout reads every row, a free one one block, and no ring
    assert lane_blocks.live_positions_read(
        np.asarray([130, 384, 0, 5]), _ATTN_T, 64,
        live=np.asarray([True, False, True, True])) == 7 * _ATTN_B


@pytest.mark.parametrize("why,want,Tq,ring,backend,kw", [
    ("head_dim_128", "live_blocks", 1, False, "tpu", {}),
    ("head_dim_128_ring", "live_blocks", 1, True, "tpu", {}),
    ("llama_gqa_bf16", "live_blocks", 1, False, "tpu",
     dict(H=8, T=4096, dtype=jnp.bfloat16)),
    ("int8_cache", "whole_buffer", 1, False, "tpu",
     dict(dtype=jnp.int8, quant=True)),
    ("verify_tq", "whole_buffer", 3, False, "tpu", {}),
    ("not_whole_blocks", "whole_buffer", 1, False, "tpu", dict(T=192)),
    ("over_vmem_budget", "whole_buffer", 1, False, "tpu",
     dict(H=128, T=512)),
    ("not_a_tpu", "whole_buffer", 1, True, None, {}),
])
def test_decode_attention_rule_at_head_dim_128(why, want, Tq, ring, backend,
                                               kw):
    """One rule for both layouts: at ``head_dim`` 128 it admits a float
    cache of whole row blocks, ring or not, and refuses what it refuses
    under 128 (int8, verify, a cell over the VMEM budget, any other
    backend)."""
    from building_llm_from_scratch_tpu.models import transformer as tf

    cache = _append_cache(**{"D": 128, "T": 384, **kw})
    H = cache["k"][0].shape[1]
    assert tf.decode_attention_path(cache, Tq, 4 * H, ring=ring,
                                    backend=backend) == want


@pytest.mark.parametrize("why,Tq,kw", [
    ("verify_tq", 3, {}),
    ("int8_cache", 1, dict(dtype=jnp.int8, quant=True)),
    ("head_dim_256", 1, dict(D=256)),
    ("ring_arguments", 1, {}),
    ("tmax_not_lane_multiple", 1, dict(T=192 + 8)),
    ("over_vmem_budget", 1, dict(H=512, D=64)),
    ("not_a_tpu", 1, {}),
])
def test_decode_attention_gate_refusals_take_decode_attention(monkeypatch,
                                                              why, Tq, kw):
    """Outside the gate a tick attends with ``decode_attention``, today's
    two reductions: the name says so, and the traced attention holds no
    kernel call; inside it, it holds one."""
    import functools

    from building_llm_from_scratch_tpu.models import transformer as tf

    cfg = tiny_cfg(ctx=128)
    cache = _append_cache(**kw)
    ring = why == "ring_arguments"
    backend = None if why == "not_a_tpu" else "tpu"
    assert tf.decode_attention_path(_append_cache(), 1, 2, backend="tpu") \
        == "live_blocks"
    assert tf.decode_attention_path(cache, Tq, 2, ring=ring,
                                    backend=backend) == "whole_buffer"
    if why in ("over_vmem_budget", "verify_tq"):
        return          # nothing small to trace; verify never asks the rule
    if backend:
        monkeypatch.setattr(tf, "decode_attention_path", functools.partial(
            tf.decode_attention_path, backend=backend))
    S, H, T, D = cache["k"][0].shape
    q = jnp.ones((S, 1, H, D), jnp.float32)
    lens = jnp.asarray([0, 5], jnp.int32)
    ring_kw = ({"kv_positions": tf.ring_positions(lens, T), "window": 64}
               if ring else {})

    def attend(cache, q, lens):
        kv = tf._RowsKV(cfg, cache, 1, lens)
        kv.new = cache                     # as after the layer's append
        return kv._attend(0, q, cache["k"][0], cache["v"][0], ring_kw)

    assert "live_block_attention" not in str(
        jax.make_jaxpr(attend)(cache, q, lens))
    if backend and not ring:
        admitted = _append_cache()
        assert "live_block_attention" in str(jax.make_jaxpr(attend)(
            admitted, jnp.ones((2, 1, 2, 16), jnp.float32), lens))


def test_decode_attention_per_row_matches_scalar():
    from building_llm_from_scratch_tpu.ops.attention import decode_attention

    B, Hq, Hkv, D, T = 2, 4, 2, 8, 16
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, 1, Hq, D))
    K = jax.random.normal(ks[1], (B, Hkv, T, D))
    V = jax.random.normal(ks[2], (B, Hkv, T, D))
    # all rows at the same length: the per-row path must equal the scalar
    scalar = decode_attention(q, K, V, q_positions=jnp.asarray([5]),
                              kv_length=jnp.asarray(6))
    perrow = decode_attention(
        q, K, V, q_positions=jnp.full((B, 1), 5),
        kv_length=jnp.full((B,), 6))
    np.testing.assert_allclose(np.asarray(scalar), np.asarray(perrow),
                               rtol=1e-6)
    # different per-row lengths: each row must match its own scalar run
    lens = jnp.asarray([3, 9])
    mixed = decode_attention(q, K, V,
                             q_positions=(lens - 1)[:, None],
                             kv_length=lens)
    for b in range(B):
        ref = decode_attention(q[b:b + 1], K[b:b + 1], V[b:b + 1],
                               q_positions=(lens[b] - 1)[None],
                               kv_length=lens[b])
        np.testing.assert_allclose(np.asarray(mixed[b]),
                                   np.asarray(ref[0]), rtol=1e-6)


# ---------------------------------------------------------------------------
# generate(): per-row eos satellite
# ---------------------------------------------------------------------------

def test_generate_per_row_eos_stops_one_row_not_the_other(model):
    cfg, params = model
    r0 = np.asarray(jax.random.randint(jax.random.PRNGKey(5), (1, 4), 2,
                                       cfg.vocab_size), np.int32)
    r1 = np.asarray(jax.random.randint(jax.random.PRNGKey(6), (1, 4), 2,
                                       cfg.vocab_size), np.int32)
    prompt = np.concatenate([r0, r1], 0)
    probe = generate(params, cfg, prompt, max_new_tokens=1)
    first = np.asarray(probe)[:, -1]
    if first[0] == first[1]:
        pytest.skip("rows greedily agree on token 0; cannot split")
    eos = int(first[0])
    out, n = generate(params, cfg, prompt, max_new_tokens=4, eos_id=eos,
                      return_n_generated=True)
    # row 0 sampled its eos first — stopped, token dropped, padded w/ eos
    assert n[0] == 0
    assert n[1] >= 1
    assert out.shape[1] == prompt.shape[1] + int(n.max())
    if n[1] > 0:
        assert (out[0, prompt.shape[1]:] == eos).all()
    # escape hatch: the reference's batch-global quirk — row 0's eos
    # neither stops it nor is dropped
    ref = generate(params, cfg, prompt, max_new_tokens=4, eos_id=eos,
                   ref_eos_semantics=True)
    assert ref.shape[1] == prompt.shape[1] + 4
    assert ref[0, prompt.shape[1]] == eos


# ---------------------------------------------------------------------------
# engine correctness
# ---------------------------------------------------------------------------

def test_engine_matches_generate_greedy_and_sampled(model):
    """Token-level engine-vs-generate() parity for a greedy and a seeded
    sampling request decoded CONCURRENTLY in one slot batch."""
    cfg, params = model
    eng = DecodeEngine(cfg, params, n_slots=3, max_len=64)
    prompt = np.array([5, 6, 7, 8, 9], np.int32)
    cases = [
        SamplingParams(max_new_tokens=8, seed=3),
        SamplingParams(max_new_tokens=8, temperature=1.0, top_k=5, seed=3),
        SamplingParams(max_new_tokens=6, temperature=0.7, top_k=13,
                       seed=11),
    ]
    handles = [eng.submit(prompt, sp) for sp in cases]
    eng.run_until_idle()
    for h, sp in zip(handles, cases):
        assert h.done and h.finish_reason in ("eos", "length")
        assert h.output_ids == solo_tokens(params, cfg, prompt, sp), sp


@pytest.mark.parametrize("tp", [1, 2], ids=["one_device", "serve_tp2"])
def test_engine_tokens_identical_under_lane_window_append(monkeypatch, tp):
    """One engine run with the tick program built on the lane-window
    kernel (the rule told it is on a TPU; the kernel interprets on the
    CPU it really is on), one on the scatter: the same greedy and
    sampled tokens, and each engine names its append. Under
    ``--serve_tp`` each device's kernel appends its own head."""
    import functools

    from building_llm_from_scratch_tpu.models import transformer as tf
    from building_llm_from_scratch_tpu.parallel.sharding import (
        serve_mesh_plan,
    )
    from building_llm_from_scratch_tpu.serving import engine as engine_mod

    cfg = tiny_cfg(ctx=128)
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompts = [np.array([5, 6, 7, 8, 9], np.int32),
               np.arange(3, 40, dtype=np.int32)]
    cases = [SamplingParams(max_new_tokens=10, seed=3, ignore_eos=True),
             SamplingParams(max_new_tokens=7, temperature=0.9, top_k=5,
                            seed=3, ignore_eos=True)]

    def run():
        eng = DecodeEngine(cfg, params, n_slots=2, max_len=128,
                           mesh_plan=serve_mesh_plan(tp=tp) if tp > 1
                           else None)
        handles = [eng.submit(p, sp) for p, sp in zip(prompts, cases)]
        eng.run_until_idle()
        assert all(h.done and h.finish_reason == "length" for h in handles)
        assert eng.stats()["kv_append"] == eng.kv_append
        assert eng.healthz_payload()["kv_append"] == eng.kv_append
        return eng.kv_append, [h.output_ids for h in handles]

    scatter = run()
    on_tpu = functools.partial(tf.kv_append_path, backend="tpu")
    monkeypatch.setattr(tf, "kv_append_path", on_tpu)
    monkeypatch.setattr(engine_mod, "kv_append_path", on_tpu)
    lane = run()
    assert scatter[0] == "scatter" and lane[0] == "lane_window"
    assert lane[1] == scatter[1]
    assert lane[1][0] == solo_tokens(params, cfg, prompts[0], cases[0])


@pytest.mark.parametrize("tp", [1, 2], ids=["one_device", "serve_tp2"])
def test_engine_tokens_identical_under_live_block_attention(monkeypatch,
                                                            lane_blocks, tp):
    """One engine run with the tick program's attention on the live-block
    kernel (the rule told it is on a TPU; the kernel interprets on the CPU
    it really is on), one on ``decode_attention``: the same greedy and
    sampled tokens with rows in the first and in the second block of their
    buffers, each engine names its path, and the tick record counts what
    each read. Under ``--serve_tp`` each device's kernel attends its own
    head."""
    import functools

    from building_llm_from_scratch_tpu.models import transformer as tf
    from building_llm_from_scratch_tpu.obs.metrics import get_metrics
    from building_llm_from_scratch_tpu.parallel.sharding import (
        serve_mesh_plan,
    )
    from building_llm_from_scratch_tpu.serving import engine as engine_mod

    T, S = 256, 3
    cfg = tiny_cfg(ctx=T)
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompts = [np.array([5, 6, 7, 8, 9], np.int32),
               np.arange(3, 3 + _ATTN_B - 4, dtype=np.int32) % 90 + 2]
    cases = [SamplingParams(max_new_tokens=10, seed=3, ignore_eos=True),
             SamplingParams(max_new_tokens=9, temperature=0.9, top_k=5,
                            seed=3, ignore_eos=True)]

    def run():
        eng = DecodeEngine(cfg, params, n_slots=S, max_len=T,
                           mesh_plan=serve_mesh_plan(tp=tp) if tp > 1
                           else None)
        handles = [eng.submit(p, sp) for p, sp in zip(prompts, cases)]
        eng.run_until_idle()
        assert all(h.done and h.finish_reason == "length" for h in handles)
        assert eng.stats()["decode_attention"] == eng.decode_attention
        assert eng.healthz_payload()["decode_attention"] \
            == eng.decode_attention
        tick = [t for t in get_metrics().recent("tick")
                if t.get("rows") == 2][-1]
        return (eng.decode_attention, [h.output_ids for h in handles],
                tick["kv_positions"], tick["kv_touched"])

    whole = run()
    on_tpu = functools.partial(tf.decode_attention_path, backend="tpu")
    monkeypatch.setattr(tf, "decode_attention_path", on_tpu)
    monkeypatch.setattr(engine_mod, "decode_attention_path", on_tpu)
    live = run()
    assert whole[0] == "whole_buffer" and live[0] == "live_blocks"
    assert live[1] == whole[1]
    assert live[1][0] == solo_tokens(params, cfg, prompts[0], cases[0])
    # the last tick both rows decoded in (the long one's eighth): the short
    # row holds 5 + 7 positions and appends one, the long one 124 + 7 and
    # one, which is in its second block; two layers
    L = cfg.n_layers
    assert whole[2] == live[2] == L * (13 + 132)
    assert whole[3] == L * S * T                # three whole buffers
    assert live[3] == L * (1 + 2 + 1) * _ATTN_B     # the free slot: one


@pytest.mark.parametrize("family,size,kw", [
    ("command_a_plus", "218B", dict(n_layers=4, sliding_window=256)),
    ("solar_open2", "250B", dict(n_layers=4)),
    ("jamba2", "3B", {}),
], ids=["cohere2_rings", "solar_open2_state", "jamba_mqa"])
def test_engine_tokens_identical_under_live_rows_attention(monkeypatch,
                                                           lane_blocks,
                                                           family, size, kw):
    """The three ``head_dim``-128 families at their debug sizes, one engine
    run with the tick's attention on the kernel's sublane form (the rule
    told it is on a TPU), one on ``decode_attention``: the same greedy and
    sampled tokens with a slot free, a short request and one whose prompt
    has wrapped the rings, each engine names its path, and a tick in which
    the long request decodes alone touches its block-rounded live
    positions and nothing of the other slots."""
    import functools

    from building_llm_from_scratch_tpu.configs import get_config
    from building_llm_from_scratch_tpu.models import transformer as tf
    from building_llm_from_scratch_tpu.obs.metrics import get_metrics
    from building_llm_from_scratch_tpu.serving import KVCachePolicy
    from building_llm_from_scratch_tpu.serving import engine as engine_mod

    T, C, S, n_long = 640, 128, 3, 500
    cfg = get_config(family, size, debug=True, dtype="fp32").replace(
        attn_head_dim=128, context_length=T, **kw)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(2, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (n_long, 5)]
    cases = [SamplingParams(max_new_tokens=9, temperature=0.0, seed=3,
                            ignore_eos=True),
             SamplingParams(max_new_tokens=4, temperature=0.9, top_k=5,
                            seed=3, ignore_eos=True)]

    def run():
        eng = DecodeEngine(cfg, params, n_slots=S, max_len=T,
                           kv_policy=KVCachePolicy(prefill_chunk=C))
        handles = [eng.submit(p, sp) for p, sp in zip(prompts, cases)]
        eng.run_until_idle()
        assert all(h.done and h.finish_reason == "length" for h in handles)
        assert eng.stats()["decode_attention"] == eng.decode_attention \
            == eng.healthz_payload()["decode_attention"]
        assert eng.kv_append == "scatter"
        tick = [t for t in get_metrics().recent("tick")
                if t.get("rows") == 1 and not t.get("chunks")][-1]
        return (eng.decode_attention, [h.output_ids for h in handles],
                tick["kv_positions"], tick["kv_touched"],
                [k.shape[2] for k in eng.cache["k"] if k is not None])

    whole = run()
    on_tpu = functools.partial(tf.decode_attention_path, backend="tpu")
    monkeypatch.setattr(tf, "decode_attention_path", on_tpu)
    monkeypatch.setattr(engine_mod, "decode_attention_path", on_tpu)
    live = run()
    assert whole[0] == "whole_buffer" and live[0] == "live_blocks"
    assert live[1] == whole[1]
    # the last tick: the long request alone, 500 + 7 positions held and one
    # appended. Every buffer is whole blocks of 128 and of no more: a full
    # one of 640 reads four; a ring of 256 + 128 holds the newest 256
    # (indices 252..383 and 0..123: three blocks)
    buffers, n = live[4], n_long + 8
    rings = [b for b in buffers if b == 384]
    assert set(buffers) <= {384, T}
    assert whole[2] == live[2] == (len(buffers) - len(rings)) * n \
        + len(rings) * 256
    assert whole[3] == S * sum(buffers)
    assert live[3] == (len(buffers) - len(rings)) * 4 * _ATTN_B \
        + len(rings) * 3 * _ATTN_B


def test_slot_reuse_and_seed_reproducibility(model):
    """More requests than slots: retired slots are reused and every
    request still matches its solo run — including two identical
    (prompt, seed) requests submitted amid different co-batched traffic,
    which must produce identical tokens regardless of slot placement."""
    cfg, params = model
    eng = DecodeEngine(cfg, params, n_slots=2, max_len=64, max_queue=16)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(2, cfg.vocab_size, (3 + i,)).astype(np.int32)
               for i in range(5)]
    sps = [SamplingParams(max_new_tokens=4 + i, seed=i,
                          temperature=0.5 * (i % 2), top_k=7 if i % 2
                          else None)
           for i in range(5)]
    twin = (np.array([4, 4, 4], np.int32),
            SamplingParams(max_new_tokens=5, temperature=1.0, top_k=9,
                           seed=42))
    handles = [eng.submit(p, sp) for p, sp in zip(prompts, sps)]
    h_twin1 = eng.submit(twin[0], twin[1])
    eng.run_until_idle()
    # resubmit the twin amid fresh traffic: different slot history, same
    # tokens
    h_more = [eng.submit(p, sp) for p, sp in zip(prompts[:2], sps[:2])]
    h_twin2 = eng.submit(twin[0], twin[1])
    eng.run_until_idle()
    for h, p, sp in zip(handles + h_more, list(prompts) + prompts[:2],
                        sps + sps[:2]):
        assert h.output_ids == solo_tokens(params, cfg, p, sp)
    assert h_twin1.output_ids == h_twin2.output_ids
    assert h_twin1.output_ids == solo_tokens(params, cfg, *twin)
    assert eng.scheduler.n_active == 0 and len(eng.queue) == 0


def test_midstream_admission_does_not_perturb_inflight(model):
    """Admitting B while A is mid-decode must not change A's tokens."""
    cfg, params = model
    eng = DecodeEngine(cfg, params, n_slots=2, max_len=64)
    pa = np.array([9, 8, 7, 6], np.int32)
    pb = np.array([3, 4, 5], np.int32)
    sa = SamplingParams(max_new_tokens=10, seed=1, temperature=1.0,
                        top_k=11)
    ha = eng.submit(pa, sa)
    for _ in range(3):                       # A decodes alone for a while
        assert eng.step()
    assert not ha.done
    hb = eng.submit(pb, SamplingParams(max_new_tokens=6, seed=2))
    eng.run_until_idle()
    assert ha.output_ids == solo_tokens(params, cfg, pa, sa)
    assert hb.output_ids == solo_tokens(params, cfg, pb, hb.params)


def test_queue_backpressure_reject(model):
    cfg, params = model
    eng = DecodeEngine(cfg, params, n_slots=1, max_len=64, max_queue=2)
    sp = SamplingParams(max_new_tokens=2)
    p = np.array([2, 3], np.int32)
    h1, h2 = eng.submit(p, sp), eng.submit(p, sp)
    with pytest.raises(QueueFullError):
        eng.submit(p, sp)                      # bounded queue: reject
    assert eng.requests_rejected == 1
    eng.run_until_idle()
    assert h1.done and h2.done
    eng.submit(p, sp)                          # space again after drain
    eng.run_until_idle()


def test_eos_and_max_token_retirement(model):
    cfg, params = model
    prompt = np.array([7, 7, 8], np.int32)
    probe = generate(params, cfg, prompt[None], max_new_tokens=1)
    t0 = int(np.asarray(probe)[0, -1])         # the first greedy token
    eng = DecodeEngine(cfg, params, n_slots=2, max_len=64)
    # greedy request whose eos IS its first sampled token: finishes at
    # admission with zero output tokens, reason 'eos', slot freed
    h_eos = eng.submit(prompt, SamplingParams(max_new_tokens=5, eos_id=t0))
    h_len = eng.submit(prompt, SamplingParams(max_new_tokens=4,
                                              ignore_eos=True))
    eng.run_until_idle()
    assert h_eos.finish_reason == "eos" and h_eos.output_ids == []
    assert h_len.finish_reason == "length" and len(h_len.output_ids) == 4
    assert eng.scheduler.n_active == 0


def test_finish_during_admission_does_not_strand_queue(model):
    """Every request finishes DURING admission (eos is its first sampled
    token): step() must keep refilling the freed slot from the queue in
    the same tick instead of reporting idle with requests still queued."""
    cfg, params = model
    prompt = np.array([7, 7, 8], np.int32)
    probe = generate(params, cfg, prompt[None], max_new_tokens=1)
    t0 = int(np.asarray(probe)[0, -1])
    eng = DecodeEngine(cfg, params, n_slots=1, max_len=64, max_queue=8)
    handles = [eng.submit(prompt, SamplingParams(max_new_tokens=5,
                                                 eos_id=t0))
               for _ in range(3)]
    eng.run_until_idle()
    for h in handles:
        assert h.done and h.finish_reason == "eos" and h.output_ids == []
    assert eng.scheduler.n_active == 0 and len(eng.queue) == 0


def test_engine_loop_death_fails_requests_instead_of_hanging(model):
    """A BATCH-WIDE exception escaping step() on the background thread
    (here: the decode program itself dying) must fail the in-flight AND
    queued requests — result() raises, shutdown() returns — not strand
    them forever. (Per-REQUEST faults like a raising on_token callback no
    longer reach this path: they are isolated — see
    test_serving_resilience.py.)"""
    cfg, params = model
    eng = DecodeEngine(cfg, params, n_slots=1, max_len=64, max_queue=8)

    def bad_decode(*a, **kw):
        raise RuntimeError("decode program died")

    eng._decode = bad_decode
    sp = SamplingParams(max_new_tokens=4, ignore_eos=True)
    p = np.array([2, 3, 4], np.int32)
    h_bad = eng.submit(p, sp)
    h_queued = eng.submit(p, sp)
    eng.start()
    with pytest.raises(RuntimeError, match="engine loop error"):
        h_bad.result(timeout=30)
    with pytest.raises(RuntimeError, match="engine loop error"):
        h_queued.result(timeout=30)
    assert h_bad.finish_reason == "error" and h_bad.error
    # a dead engine rejects new submissions instead of silently
    # enqueueing them into a loop that will never run again
    with pytest.raises(RuntimeError, match="engine is dead"):
        eng.submit(p, sp)
    eng.shutdown()                             # must not spin forever
    assert eng.scheduler.n_active == 0 and len(eng.queue) == 0


def test_top_k_over_compiled_capacity_rejected(model):
    cfg, params = model
    eng = DecodeEngine(cfg, params, n_slots=1, max_len=64, max_top_k=8)
    with pytest.raises(ValueError, match="top_k"):
        eng.submit(np.array([2, 3], np.int32),
                   SamplingParams(max_new_tokens=2, top_k=9))


def test_terminal_bucket_warmed_when_max_len_not_multiple_of_64(model):
    """max_len=48: the clamped terminal bucket (48) must be in the warmup
    set, so a fully in-capacity prompt (40 tokens) never fires a
    bucket-miss recompile after freeze."""
    cfg, params = model
    eng = DecodeEngine(cfg, params, n_slots=1, max_len=48)
    assert 48 in eng.prompt_buckets()
    eng.warmup()
    h = eng.submit(np.full((40,), 5, np.int32),
                   SamplingParams(max_new_tokens=3, ignore_eos=True))
    eng.run_until_idle()
    assert len(h.output_ids) == 3
    assert eng.n_recompiles == 0


def test_streaming_and_callbacks():
    # byte-vocab config: ByteTokenizer ids run 0..256, so the module
    # fixture's vocab-96 model would make "abc" (bytes 97-99) an
    # out-of-vocab poison prompt — which submit now REJECTS (see
    # test_out_of_vocab_prompt_rejected in test_serving_resilience.py)
    from building_llm_from_scratch_tpu.data.tokenizers import ByteTokenizer

    tok = ByteTokenizer()
    cfg = tiny_cfg(vocab_size=tok.vocab_size)
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = DecodeEngine(cfg, params, tokenizer=tok, n_slots=1, max_len=64)
    seen = []
    h = eng.submit("abc", SamplingParams(max_new_tokens=5,
                                         ignore_eos=True),
                   on_token=lambda r, t, piece: seen.append((t, piece)))
    eng.run_until_idle()
    pieces = list(h.stream(timeout=1))
    assert len(h.output_ids) == 5
    assert len(seen) == 5
    assert [t for t, _ in seen] == h.output_ids
    assert "".join(pieces) == h.text
    assert h.text == tok.decode(h.output_ids)


def test_incremental_detok_holds_partial_multibyte(model):
    """A token that is the first byte of a multi-byte UTF-8 char must be
    held (empty piece), then emitted as ONE complete char when the
    continuation byte arrives — not committed as a mangled replacement
    char; final flush emits whatever is left."""
    cfg, params = model
    from building_llm_from_scratch_tpu.data.tokenizers import ByteTokenizer

    eng = DecodeEngine(cfg, params, tokenizer=ByteTokenizer(), n_slots=1,
                       max_len=64)
    req = Request(9001, np.array([1], np.int32), SamplingParams())
    req.output_ids.append(0xC3)                # first byte of 'é'
    assert eng._detok_piece(req) == "" and req.text == ""
    req.output_ids.append(0xA9)                # continuation byte
    assert eng._detok_piece(req) == "é" and req.text == "é"
    req.output_ids.append(ord("x"))
    assert eng._detok_piece(req) == "x"
    req.output_ids.append(0xC3)                # dangling partial at finish
    assert eng._detok_piece(req) == ""
    assert eng._detok_piece(req, final=True) == "�"
    assert req.text == "éx�"
    assert req.text == ByteTokenizer().decode(req.output_ids[:-1]) + "�"


def test_zero_recompiles_after_warmup_and_bucket_miss_surfaces(model,
                                                               tmp_path):
    """The compile discipline the smoke gate enforces: warmup compiles the
    bucket set, in-bucket traffic never recompiles, and an out-of-bucket
    prompt fires a ``recompile`` event (the bucket-miss detector)."""
    from building_llm_from_scratch_tpu.obs.metrics import configure_metrics

    cfg = tiny_cfg(ctx=192)
    params = init_params(cfg, jax.random.PRNGKey(0))
    mj = str(tmp_path / "serve_metrics.jsonl")
    sink = configure_metrics(mj)
    sink.write_header(test="recompile")
    try:
        eng = DecodeEngine(cfg, params, n_slots=2, max_len=192,
                           warmup_prompt_cap=64)
        eng.warmup()
        assert eng.warmed_up
        # in-bucket traffic (prompt bucket 64): silent steady state
        h = eng.submit(np.arange(2, 12, dtype=np.int32),
                       SamplingParams(max_new_tokens=3, ignore_eos=True))
        eng.run_until_idle()
        assert len(h.output_ids) == 3
        assert eng.n_recompiles == 0
        # a 70-token prompt needs the UNWARMED 128 bucket: recompile event
        h2 = eng.submit(np.full((70,), 5, np.int32),
                        SamplingParams(max_new_tokens=2, ignore_eos=True))
        eng.run_until_idle()
        assert len(h2.output_ids) == 2
        assert eng.n_recompiles == 1
    finally:
        sink.close()
        configure_metrics(None)
    rows = [json.loads(line) for line in open(mj)]
    recompiles = [r for r in rows if r.get("event") == "recompile"]
    assert len(recompiles) == 1
    assert recompiles[0]["label"] == "serve_prefill"
    assert [r for r in rows if r.get("event") == "request_done"]
    assert [r for r in rows if r.get("event") == "serve_warmup"]


def test_http_frontend_generate_and_healthz(model):
    cfg, params = model
    from building_llm_from_scratch_tpu.serving.frontend import (
        make_http_server,
    )

    eng = DecodeEngine(cfg, params, n_slots=1, max_len=64)
    eng.start()
    server = make_http_server(eng, 0, host="127.0.0.1")
    port = server.server_address[1]
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        conn.request("GET", "/healthz")
        health = json.loads(conn.getresponse().read())
        assert health["slots"] == 1 and health["queue_capacity"] >= 1
        body = json.dumps({"prompt_ids": [5, 6, 7], "max_new_tokens": 3,
                           "ignore_eos": True, "seed": 4})
        conn.request("POST", "/generate", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        out = json.loads(resp.read())
        assert resp.status == 200, out
        assert len(out["token_ids"]) == 3
        assert out["finish_reason"] == "length"
        conn.close()
    finally:
        server.shutdown()
        server.server_close()
        eng.shutdown()


# ---------------------------------------------------------------------------
# scheduler / queue units (no jax)
# ---------------------------------------------------------------------------

def _dummy_req(i):
    return Request(1000 + i, np.array([1], np.int32), SamplingParams())


def test_scheduler_fcfs_admission_and_slot_reuse():
    q = RequestQueue(8)
    sched = Scheduler(2)
    reqs = [_dummy_req(i) for i in range(4)]
    for r in reqs:
        q.put(r)
    admitted = sched.admit_from(q)
    assert [(s, r.id) for s, r in admitted] == [(0, 1000), (1, 1001)]
    assert sched.n_active == 2 and sched.admit_from(q) == []
    sched.retire(0)
    # freed slot refills FCFS from the queue head
    assert [(s, r.id) for s, r in sched.admit_from(q)] == [(0, 1002)]
    with pytest.raises(ValueError):
        sched.retire(1) or sched.retire(1)
    sched.retire(0)
    assert [(s, r.id) for s, r in sched.admit_from(q)] == [(0, 1003)]
    assert sched.occupancy() == 0.5            # 1003 alone; 1001 retired


def test_request_queue_block_timeout_and_capacity():
    q = RequestQueue(1)
    q.put(_dummy_req(0))
    with pytest.raises(QueueFullError):
        q.put(_dummy_req(1))
    with pytest.raises(QueueFullError):
        q.put(_dummy_req(1), block=True, timeout=0.05)
    assert q.get_nowait().id == 1000
    q.put(_dummy_req(2))                      # capacity restored
    assert len(q) == 1
