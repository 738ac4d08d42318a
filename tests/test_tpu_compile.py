"""Compile the pallas kernels for a DESCRIBED TPU v5e, without a chip.

The chip's compiler is installed here and compiles for a topology that is
described, not attached (``on-chip-measurement`` guide §2, third rehearsal).
Interpret-mode CPU tests cannot see what it refuses: an SMEM block that is
not a legal tile, more scoped VMEM than a kernel may use, a Mosaic call
handed to GSPMD without a shard_map. Every kernel here had passed its
interpret-mode tests while the compiler refused it. Nothing runs, so these
cases say nothing about results or times (chip_smoke.py does, on the chip).

ONE file, by design: only one process may hold the TPU library, so the
topology is described inside a module-scoped fixture — after a test of this
file has started, in the one xdist worker that was given the file — and
never at import or collection.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from building_llm_from_scratch_tpu.ops import decode_step as ds
from building_llm_from_scratch_tpu.ops import fused_attention as fa
from building_llm_from_scratch_tpu.ops import fused_dropout as fd
from building_llm_from_scratch_tpu.ops import xent_fwd_pallas as xp
from building_llm_from_scratch_tpu.parallel.collectives import (
    trace_under_mesh,
)

BF16, I32 = jnp.bfloat16, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without a chip: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *specs) -> str:
    """Lower + compile for the described chip; the optimized HLO text."""
    return jax.jit(fn).lower(*specs).compile().as_text()


def _spec(sharding):
    return lambda shape, dtype=BF16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding)


def _attention_grad(q, k, v, rng):
    """Forward + all three gradients, attention dropout on."""
    return jax.grad(lambda q, k, v: fa.fused_causal_attention(
        q, k, v, dropout_rate=0.1, dropout_rng=rng).astype(
            jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("B,T,Hq,Hkv,D", [
    (8, 1024, 12, 12, 64),       # GPT2-124M train step (bs8)
    (8, 1024, 32, 8, 64),        # LLaMA-3.2-1B GQA 32/8
])
def test_fused_attention_fwd_grad_dropout(one_chip, B, T, Hq, Hkv, D):
    assert fa.supports_shape(T, T, D)
    s = _spec(one_chip)
    hlo = _compile(_attention_grad, s((B, T, Hq, D)), s((B, T, Hkv, D)),
                   s((B, T, Hkv, D)), s((2,), jnp.uint32))
    assert hlo.count("tpu_custom_call") == 3     # fwd, dq, dkv


def test_fused_dropout_add_fwd_grad(one_chip):
    shape = (8, 1024, 768)
    assert fd.supports_shape(shape)
    s = _spec(one_chip)

    def fn(x, h, rng):
        return jax.value_and_grad(lambda h: fd.fused_dropout_add(
            x, h, 0.1, rng).astype(jnp.float32).sum())(h)

    hlo = _compile(fn, s(shape), s(shape), s((2,), jnp.uint32))
    assert hlo.count("tpu_custom_call") == 2     # forward, mask redraw


@pytest.mark.parametrize("size,S,dtype", [
    ("1.5B", 32, "bf16"),        # the serving cells: panes (32, 25, 1024, 64)
    ("124M", 8, "bf16"),         # (8, 12, 1024, 64)
    ("124M", 8, "fp32"),
])
def test_decode_slots_appends_in_place_at_full_depth(one_chip, monkeypatch,
                                                     size, S, dtype):
    """``decode_slots`` itself at the model's own depth (48 and 12 layers),
    as the engine jits it (cache donated): the append is ONE custom call a
    layer on the cache's own layout (positions on the lanes) and the
    attention ONE more on the same view, every pane aliased through, no
    ``while`` (the scatter's S-trip loops), no pane copied or relaid, and
    no reduction over all (S, H, Tmax) positions left (``decode_attention``'s
    two a layer). A kernel handed the logical shape gets a relayout copy
    of every pane in and out. (Full depth on purpose: a six-layer cut of the 1.5B program
    leaves the compiler VMEM to spare, and it then parks whole panes
    there, which the real program never does.)"""
    import dataclasses
    import re

    from building_llm_from_scratch_tpu.configs import get_config_gpt2
    from building_llm_from_scratch_tpu.models import transformer as tf

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(get_config_gpt2(size), dtype=dtype)
    L = cfg.n_layers
    shapes = lambda f, *a: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(f, *a))
    params = shapes(lambda: tf.init_params(cfg, jax.random.PRNGKey(0)))
    blocks = shapes(lambda p: tf.unstack_blocks(p, cfg), params)
    cache = shapes(lambda: tf.init_slot_cache(cfg, S, cfg.context_length))
    assert tf.kv_append_path(cache, 1) == "lane_window"
    assert tf.decode_attention_path(cache, 1, cfg.n_heads) == "live_blocks"
    row = jax.ShapeDtypeStruct((S,), I32, sharding=one_chip)

    def tick(cache, params, blocks, tokens, lengths):
        return tf.decode_slots(params, cfg, tokens[:, None], lengths, cache,
                               blocks)

    hlo = jax.jit(tick, donate_argnums=(0,)).lower(
        cache, params, blocks, row, row).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2 * L
    assert not re.search(r" while\(", hlo)
    H, T, hd = cfg.n_kv_groups, cfg.context_length, cfg.head_dim
    el = "bf16" if dtype == "bf16" else "f32"
    # scores or probabilities of every position of every row: the whole-
    # buffer attention's mark (a float32 [S,H,Tmax] or [S,H,1,1,Tmax])
    assert not re.search(rf"f32\[{S},{H},(?:1,)*{T}\]", hlo)
    # a pane, as the runtime keeps it and as the kernel views it (a bitcast)
    native = re.escape(f"{el}[{S},{H},{T},{hd}]") + r"\{2,3,1,0:"
    viewed = re.escape(f"{el}[{S},{H},{hd},{T}]") + r"\{3,2,1,0:"
    # the caches come in and go out in that layout ...
    head = hlo[:hlo.index("\n")]
    layouts = head[head.index("entry_computation_layout="):]
    assert len(re.findall(native, layouts)) == 4 * L     # k, v: in and out
    # ... aliased input to output, all 2L of them ...
    assert head.count("-alias)") == 2 * L
    # ... and are in no other layout anywhere between (no relayout) ...
    anywhere = re.findall(
        re.escape(f"{el}[{S},{H},") + rf"(?:{T},{hd}|{hd},{T})\]\{{[\d,]+:",
        hlo)
    assert anywhere and all(re.fullmatch(f"{native}|{viewed}", a)
                            for a in anywhere), set(anywhere)
    # ... and never copied: nothing at all at the cells' 105 MB a pane; a
    # 124M pane (12.6 MB) the compiler may prefetch into VMEM for the
    # attention that reads it, as it does for the scatter's program
    copied = [ln.strip()[:160] for ln in hlo.split("\n")
              if re.search(rf" = (?:{native}|{viewed})\S* copy", ln)]
    if size == "124M":
        copied = [ln for ln in copied
                  if not re.search(r"S\(1\)\} copy-done\(", ln)]
    assert not copied, copied[:3]


def test_sparse_window_decode_tick_fits_and_copies_no_expert(one_chip,
                                                            monkeypatch):
    """The decode tick of ``serve_command_a_plus_rag_mixed`` as the engine
    jits it, at the cell's own sizes (48 slots, rings of 4608 beside one
    full layer of 20480, 8 of 128 experts held, weights sliced inside the
    program): it fits one chip beside its 13 GB of arguments with under
    1.5 GB of temporaries, the caches are aliased through, every routed
    expert sits behind its own conditional (4 layers x 8 held), and no copy
    of a layer's experts is made on the way in: sliced outside the
    conditionals they are operands, which the compiler writes out whole
    (12 x 268 MB, 3.2 GB of temporaries, as first built)."""
    import re

    from building_llm_from_scratch_tpu.configs import get_config
    from building_llm_from_scratch_tpu.models import transformer as tf
    from building_llm_from_scratch_tpu.serving.kvcache import KVCachePolicy

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # the published preset cut to one chip's share, as the cell's
    # configuration file states it (benchmark/tests holds the two equal)
    cfg = get_config("command_a_plus", "218B", dtype="bf16",
                     target_context_length=None).replace(
        n_layers=4, vocab_size=32768, context_length=20480,
        experts_held=tuple(range(8)))
    S, policy = 48, KVCachePolicy(prefill_chunk=512)
    shapes = lambda f, *a: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(f, *a))
    params = shapes(lambda: tf.init_params(cfg, jax.random.PRNGKey(0)))
    cache = shapes(lambda: tf.init_slot_cache(cfg, S, cfg.context_length,
                                              policy=policy))
    assert [k.shape[2] for k in cache["k"]] == [4608, 4608, 4608, 20480]
    assert tf.kv_append_path(cache, 1) == "scatter"       # head_dim 128
    row = jax.ShapeDtypeStruct((S,), I32, sharding=one_chip)
    live = jax.ShapeDtypeStruct((S,), jnp.bool_, sharding=one_chip)

    def tick(cache, params, tokens, lengths, live):
        rows = []
        logits, cache = tf.decode_slots(params, cfg, tokens[:, None],
                                        lengths, cache, live=live,
                                        expert_rows=rows)
        return logits, jnp.stack(rows), cache

    compiled = jax.jit(tick, donate_argnums=(0,)).lower(
        cache, params, row, row, live).compile()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 1.5e9
    assert memory.alias_size_in_bytes == sum(
        2 * np.prod(k.shape) * 2 for k in cache["k"])
    hlo = compiled.as_text()
    assert len(re.findall(r" conditional\(", hlo)) == 32
    entry = hlo[hlo.index("ENTRY "):]
    assert not re.search(r"= bf16\[8,4096,4096\]", entry)
    # each layer's attention is the live-block kernel, inside scoped VMEM
    assert [tf.decode_attention_path(cache, 1, cfg.n_heads, layer=l,
                                     ring=l < 3) for l in range(4)] \
        == ["live_blocks"] * 4
    assert hlo.count('custom_call_target="tpu_custom_call"') == 4


def test_sparse_window_chunk_program_attends_in_one_kernel_a_layer(
        one_chip, monkeypatch):
    """The chunk program of ``serve_command_a_plus_rag_mixed`` as the engine
    jits it (``_chunk_impl``, cache donated) at the cell's own sizes (48
    slots, a chunk of 512, 128 query heads on 8 key-value heads of 128,
    rings of 4608 beside one full layer of 20480): each layer's attention
    is ONE custom call that reaches the slot's row in place (no pane
    copied, none sliced out) and each layer's held experts ONE more (the
    grouped product, no conditional left and no copy of a layer's experts
    made on the way in: PR 37), no ``while`` is left (the materialised form
    ran one key-value head at a time, float32 scores of 8192 query rows
    against the whole buffer written out: four loops), and the program's
    temporaries are under the 869,611,008 bytes it needed with them
    (``scripts/serving_hlo.py`` at the parent, PERF.md section 6, PR 34)."""
    import re

    from building_llm_from_scratch_tpu.configs import get_config
    from building_llm_from_scratch_tpu.models import transformer as tf
    from building_llm_from_scratch_tpu.serving import engine as eng
    from building_llm_from_scratch_tpu.serving.kvcache import KVCachePolicy

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get_config("command_a_plus", "218B", dtype="bf16",
                     target_context_length=None).replace(
        n_layers=4, vocab_size=32768, context_length=20480,
        experts_held=tuple(range(8)))
    S, C = 48, 512
    # an engine that holds nothing: only what its program builder reads
    e = object.__new__(eng.DecodeEngine)
    e.cfg, e.n_slots, e.max_top_k, e.spec_k, e._paged = cfg, S, 64, 0, False
    e._cache_shardings = e._sp_sharding = e.mesh_plan = None
    e.kv_policy = KVCachePolicy(prefill_chunk=C)
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    shapes = lambda f, *a: jax.tree_util.tree_map(
        lambda x: sds(x.shape, x.dtype), jax.eval_shape(f, *a))
    params = shapes(lambda: tf.init_params(cfg, jax.random.PRNGKey(0)))
    cache = shapes(lambda: tf.init_slot_cache(cfg, S, cfg.context_length,
                                              policy=e.kv_policy))
    assert [tf.chunk_attention_path(cache, C, cfg.n_heads, layer=l)
            for l in range(4)] == ["live_blocks"] * 4
    scalar = lambda dtype: sds((), dtype)
    compiled = jax.jit(e._chunk_impl, donate_argnums=(0,)).lower(
        cache, (params, None), sds((1, C), I32), scalar(I32), scalar(I32),
        scalar(I32), sds((2,), jnp.uint32), scalar(jnp.float32),
        scalar(I32)).compile()
    hlo = compiled.as_text()
    from building_llm_from_scratch_tpu.models import moe

    assert moe.expert_dispatch_path(cfg, C, BF16) == "grouped"
    assert hlo.count('custom_call_target="tpu_custom_call"') == 8
    assert not re.search(r" while\(| conditional\(", hlo)
    entry = hlo[hlo.index("ENTRY "):]
    assert not re.search(r"= bf16\[8,4096,4096\]", entry)
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 869_611_008
    assert memory.alias_size_in_bytes == sum(
        2 * np.prod(k.shape) * 2 for k in cache["k"])
    # a layer's buffers are arguments, updated in place and read by the
    # kernel where they lie: never copied, never sliced to a row
    panes = set(re.findall(
        r"= bf16\[48,8,(?:4608|20480),128\]\S* ([\w\-]+)\(", hlo))
    assert panes == {"parameter", "dynamic-update-slice"}, panes
    assert not re.search(r"= bf16\[1,8,(?:4608|20480),128\]", hlo)


def _holds_nothing(eng, cfg, S, C):
    """An engine that holds nothing: only what its program builders read."""
    from building_llm_from_scratch_tpu.serving.kvcache import KVCachePolicy

    e = object.__new__(eng.DecodeEngine)
    e.cfg, e.n_slots, e.max_top_k, e.spec_k, e._paged = cfg, S, 64, 0, False
    e._cache_shardings = e._sp_sharding = e.mesh_plan = None
    e.kv_policy = KVCachePolicy(prefill_chunk=C)
    return e


def test_state_beside_keys_and_values_programs_fit_and_copy_no_expert(
        one_chip, monkeypatch):
    """The decode tick and the chunk program of
    ``serve_solar_open2_longdoc_mixed`` as the engine jits them (cache
    donated) at the cell's own sizes: 48 slots, one full layer of 33,792
    positions beside three linear layers' float32 states (48 x 64 x 128 x
    128) and convolution tails, 20 of 320 experts held, chunks of 512. Each
    fits one chip beside its 11.4 GB of arguments with under 1 GB of
    temporaries, keys, values, states and tails are all aliased through,
    the tick's every routed expert sits behind its own conditional (4 layers
    x 20 held) where the chunk's 512 rows reach theirs in ONE grouped kernel
    a layer with no conditional left (PR 37), and no copy of a layer's
    experts is made on the way in; the tick steps each linear layer's state
    one decoding row a trip (``state_step_path``: one loop a linear layer,
    the state still aliased through: PR 45), the chunk runs the chunked form
    (one loop over sub-chunks a linear layer) and its full layer ONE
    attention kernel."""
    import re

    from building_llm_from_scratch_tpu.configs import get_config
    from building_llm_from_scratch_tpu.models import transformer as tf
    from building_llm_from_scratch_tpu.serving import engine as eng

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # the published preset cut to one chip's share, as the cell's
    # configuration file states it (benchmark/tests holds the two equal)
    cfg = get_config("solar_open2", "250B", dtype="bf16",
                     target_context_length=None).replace(
        n_layers=4, vocab_size=24576, context_length=33792,
        experts_held=tuple(range(20)))
    S, C = 48, 512
    e = _holds_nothing(eng, cfg, S, C)
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    shapes = lambda f, *a: jax.tree_util.tree_map(
        lambda x: sds(x.shape, x.dtype), jax.eval_shape(f, *a))
    params = shapes(lambda: tf.init_params(cfg, jax.random.PRNGKey(0)))
    cache = shapes(lambda: tf.init_slot_cache(cfg, S, cfg.context_length,
                                              policy=e.kv_policy))
    assert [None if k is None else k.shape[2] for k in cache["k"]] == [
        33792, None, None, None]
    assert [None if a is None else a.shape for a in cache["state"]] == [
        None] + [(S, 64, 128, 128)] * 3
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(cache))
    assert held == S * e.kv_policy.bytes_per_slot(cfg, 33792)["total_bytes"]
    assert tf.chunk_attention_path(cache, C, cfg.n_heads) == "live_blocks"
    scalar, row = (lambda dt: sds((), dt)), (lambda dt: sds((S,), dt))
    key = sds((2,), jnp.uint32)
    programs = {
        "tick": (e._decode_impl, (
            cache, (params, None), row(I32), row(I32),
            sds((S, 2), jnp.uint32), row(I32), row(jnp.float32), row(I32),
            None, None, None, row(jnp.bool_))),
        "chunk": (e._chunk_impl, (
            cache, (params, None), sds((1, C), I32), scalar(I32),
            scalar(I32), scalar(I32), key, scalar(jnp.float32),
            scalar(I32)))}
    for name, (fn, args) in programs.items():
        compiled = jax.jit(fn, donate_argnums=(0,)).lower(*args).compile()
        memory = compiled.memory_analysis()
        assert memory.temp_size_in_bytes < 1.0e9, name
        assert memory.alias_size_in_bytes == held, name
        hlo = compiled.as_text()
        assert len(re.findall(r" conditional\(", hlo)) == (
            80 if name == "tick" else 0), name
        entry = hlo[hlo.index("ENTRY "):]
        assert not re.search(r"= bf16\[20,(?:4096,1280|1280,4096)\]", entry)
        # the tick's: the head_dim-128 scatter append's two (keys, values:
        # ROADMAP S4) and one walk over the decoding rows a linear layer
        assert len(re.findall(r" while\(", hlo)) == (2 + 3 if name == "tick"
                                                     else 3), name
        # the tick's one is its full layer's live-block attention
        assert hlo.count('custom_call_target="tpu_custom_call"') == (
            1 if name == "tick" else 1 + 4), name


@pytest.mark.parametrize("family,size,S,cut,buffers,bodies,compiles", [
    ("command_a_plus", "218B", 48,
     dict(n_layers=4, vocab_size=32768, context_length=20480,
          experts_held=tuple(range(8))),
     [4608, 4608, 4608, 20480], 2, False),
    ("solar_open2", "250B", 48,
     dict(n_layers=4, vocab_size=24576, context_length=33792,
          experts_held=tuple(range(20))),
     [33792], 1, False),
    ("jamba2", "3B", 192, dict(context_length=3072), [3072, 3072], 1, True),
], ids=["rag", "longdoc", "widechat"])
def test_head_dim_128_decode_tick_lowers_one_kernel_a_buffer_shape(
        one_chip, monkeypatch, family, size, S, cut, buffers, bodies,
        compiles):
    """The set-up guard, as counts (PERF.md section 6, PR 43: every process
    lowers each distinct kernel to Mosaic in Python before it can ask the
    compile cache, and the rag cell's warm ``setup_s`` has 0.9 s to spend).
    The decode tick of each ``head_dim``-128 cell as the engine jits it, at
    the cell's widths: every attention layer is on the live-block kernel
    and the lowered text holds ONE body of it a buffer shape, called by
    every layer of that shape: the rag tick's three rings share one and its
    full layer has the other, widechat's two layers share one. A layer of a
    shape already lowered that adds another (a kernel no longer behind one
    ``jax.jit``, or a static argument that differs by layer) fails here.
    The same count for the state step's walk (PR 45): widechat's 26 'ssm'
    layers call ONE ``selective_step_rows`` body, longdoc's three 'linear'
    layers ONE ``recurrent_step_rows``. The widechat tick, which no other
    test compiles, is also compiled: 192 rows at one key-value head and the
    walk's four rows in flight fit scoped VMEM inside the whole program,
    every state is aliased through, and no copy of a (192, 16, 5120) float32
    buffer is made."""
    import re

    from building_llm_from_scratch_tpu.configs import get_config
    from building_llm_from_scratch_tpu.models import transformer as tf
    from building_llm_from_scratch_tpu.serving import engine as eng

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get_config(family, size, dtype="bf16",
                     target_context_length=None).replace(**cut)
    e = _holds_nothing(eng, cfg, S, 512)
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    shapes = lambda f, *a: jax.tree_util.tree_map(
        lambda x: sds(x.shape, x.dtype), jax.eval_shape(f, *a))
    params = shapes(lambda: tf.init_params(cfg, jax.random.PRNGKey(0)))
    blocks = (None if cfg.is_moe
              else shapes(lambda p: tf.unstack_blocks(p, cfg), params))
    cache = shapes(lambda: tf.init_slot_cache(cfg, S, cfg.context_length,
                                              policy=e.kv_policy))
    layers = [l for l, k in enumerate(cache["k"]) if k is not None]
    assert [cache["k"][l].shape[2] for l in layers] == buffers
    assert [tf.decode_attention_path(
        cache, 1, cfg.n_heads, layer=l,
        ring=cfg.layer_kind(l) == "sliding") for l in layers] \
        == ["live_blocks"] * len(layers)
    assert tf.kv_append_path(cache, 1) == "scatter"
    row = lambda dt: sds((S,), dt)
    lowered = jax.jit(e._decode_impl, donate_argnums=(0,)).lower(
        cache, (params, blocks), row(I32), row(I32), sds((S, 2), jnp.uint32),
        row(I32), row(jnp.float32), row(I32), None, None, None,
        row(jnp.bool_))
    text = lowered.as_text()
    # the state step's walk (PR 45): ONE body for every 'ssm' layer (a
    # kernel), one for every 'linear' layer (a loop, no kernel)
    kinds = [cfg.layer_kind(l) for l in cfg.state_layers]
    assert [tf.state_step_path(cache, kind, 1, layer=l, rows_named=True)
            for l, kind in zip(cfg.state_layers, kinds)] \
        == ["live_rows"] * len(kinds)
    for kind, body in (("ssm", "_step_rows_local"),
                       ("linear", "recurrent_step_rows")):
        n = kinds.count(kind)
        assert len(re.findall(rf"func\.func private @{body}\b", text)) \
            == min(n, 1), kind
        assert len(re.findall(rf"call @{body}\b", text)) == n, kind
    ssm = kinds.count("ssm")
    assert len(re.findall(r"func\.func private @_live_rows_local", text)) \
        == bodies
    assert len(re.findall(r"stablehlo\.custom_call @tpu_custom_call", text)) \
        == bodies + min(ssm, 1)
    assert len(re.findall(r"call @_live_rows_local", text)) == len(layers)
    if compiles:
        compiled = lowered.compile()
        hlo = compiled.as_text()
        assert hlo.count('custom_call_target="tpu_custom_call"') \
            == len(layers) + ssm
        # the aliasing took: every state goes through the walk in place,
        # and nothing in the program holds a second (192, 16, 5120) buffer
        held = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in jax.tree_util.tree_leaves(cache))
        memory = compiled.memory_analysis()
        assert memory.alias_size_in_bytes == held
        assert memory.temp_size_in_bytes < 0.3e9
        state = rf"f32\[{S},{cfg.ssm_state},{cfg.ssm_inner}\]"
        assert len(re.findall(state, hlo)) > ssm
        made = set(re.findall(rf"= {state}\S* ([\w\-]+)\(", hlo))
        assert made <= {"parameter", "get-tuple-element"}, made


@pytest.mark.parametrize("S,Hq,Hkv,Tmax,window", [
    (48, 128, 8, 4608, 4096),    # the rag cell's rings
    (48, 128, 8, 20480, None),   # and its full layer
    (48, 64, 8, 33792, None),    # longdoc's one full layer
    (192, 20, 1, 3072, None),    # widechat: 20 query heads on one
    (32, 32, 8, 8192, None),     # Llama-3-8B: four query heads a group
    (16, 64, 8, 4096, None),     # Llama-2-70B's grouping
])
def test_live_rows_attention_real_widths(one_chip, S, Hq, Hkv, Tmax, window):
    """The sublane form at every buffer shape the cells hold, and at the
    reference's Llama sizes, which the same rule admits."""
    assert ds.supports_live_attention(1, Tmax, 128, S=S, Hkv=Hkv, Hq=Hq,
                                      dtype=BF16, ring=window is not None)
    s = _spec(one_chip)
    pane = s((S, Hkv, Tmax, 128), BF16)
    hlo = _compile(
        lambda q, K, V, n, live: ds.live_block_attention(
            q, K, V, n, live=live, window=window),
        s((S, 1, Hq, 128), BF16), pane, pane, s((S,), I32),
        s((S,), jnp.bool_))
    assert hlo.count("tpu_custom_call") == 1
    # the buffers are read as they lie: no pane is copied or relaid
    assert not [ln for ln in hlo.split("\n")
                if f"bf16[{S},{Hkv},{Tmax},128]" in ln and " copy(" in ln]


def test_paged_decode_attention(one_chip):
    S, H, hd, page, n_pages, max_pages = 8, 12, 64, 16, 512, 64
    assert ds.supports_paged_shape(1, page, hd)
    s = _spec(one_chip)
    pool = s((n_pages, H, page, hd))
    hlo = _compile(ds.paged_decode_attention, s((S, 1, H, hd)), pool, pool,
                   s((S, max_pages), I32), s((S,), I32))
    assert hlo.count("tpu_custom_call") == 1


def test_lora_bgmv(one_chip):
    S, D, r, O, N = 8, 768, 16, 3072, 16
    assert ds.supports_lora_shape(D, r, O)
    s = _spec(one_chip)
    hlo = _compile(ds.lora_bgmv, s((S, D)), s((N, D, r)), s((N, r, O)),
                   s((S,), I32), s((N,), jnp.float32))
    assert hlo.count("tpu_custom_call") == 1


@pytest.mark.parametrize("H,F", [(8, 4096), (20, 1280)])
def test_grouped_experts_real_widths(one_chip, H, F):
    """The grouped expert product at both sparse cells' widths (a chunk's
    512 rows top-8: a buffer of 4096 rows; D 4096; 8 experts of width 4096
    or 20 of 1280, stacked over four layers): the gate admits the shape, the
    VMEM the kernel asks for is granted, the grid's length is a value (the
    visits that hold a row), and the stacked experts reach the kernel as
    they lie: no slice and no copy of them in the program."""
    import re

    from building_llm_from_scratch_tpu.ops import grouped_experts as ge

    L, D, M = 4, 4096, 4096
    assert ge.supports_grouped_experts(M, D, F, BF16)
    assert ge.buffer_rows(M) == M and ge._width_tile(D, F, 2) in (512, 256)
    s = _spec(one_chip)
    hlo = _compile(ge.grouped_gated_product, s((M, D)), s((L, H, D, F)),
                   s((L, H, D, F)), s((L, H, F, D)), s((), I32),
                   s((H,), I32))
    assert hlo.count("tpu_custom_call") == 1
    stacked = set(re.findall(
        rf"= bf16\[{L},{H},(?:{D},{F}|{F},{D})\]\S* ([\w\-]+)\(", hlo))
    assert stacked == {"parameter"}, stacked


@pytest.mark.parametrize("B,T", [(1, 512), (2, 1024)])
def test_selective_scan_real_widths(one_chip, B, T):
    """The selective-scan kernel at the state-space cell's layer (5120
    channels of 16 float32 states; a prefill chunk of 512 tokens, and the
    longest span the gate admits, two rows of it): the gate admits the shape,
    B and C fit scalar memory, the VMEM the kernel asks for is granted, and
    the program is ONE kernel call. The next span up is refused by the gate,
    not by the compiler mid-run."""
    from building_llm_from_scratch_tpu.ops import selective_scan as ss

    I, N = 5120, 16
    assert ss.supports_selective_scan_kernel(T, I, N)
    assert not ss.supports_selective_scan_kernel(2048, I, N)
    s = _spec(one_chip)
    f32 = jnp.float32
    hlo = _compile(ss.selective_scan_kernel, s((B, T, I), f32),
                   s((B, T, I), f32), s((N, I), f32), s((B, T, N), f32),
                   s((B, T, N), f32), s((I,), f32), s((B, N, I), f32))
    assert hlo.count("tpu_custom_call") == 1


@pytest.mark.parametrize("S", [192, 8])
def test_selective_step_rows_real_widths(one_chip, S):
    """The tick's walk over the decoding rows at the state-space cell's layer
    (5120 channels of 16 float32 states): the rule admits the shape, a row's
    (1, 5120) operands are copied as they lie (no slice off the tiling), the
    call is ONE kernel, and the state is written in place: the program holds
    no second (S, 16, 5120) buffer."""
    import re

    from building_llm_from_scratch_tpu.ops import selective_scan as ss

    I, N = 5120, 16
    assert ss.supports_step_rows(I, N)
    s = _spec(one_chip)
    f32 = jnp.float32

    def step(u, delta, A, Bm, Cm, D, state, live):
        return ss.selective_step_rows(u, delta, A, Bm, Cm, D, state,
                                      ss.live_rows_table(live))

    compiled = jax.jit(step, donate_argnums=(6,)).lower(
        s((S, I), f32), s((S, I), f32), s((N, I), f32), s((S, N), f32),
        s((S, N), f32), s((I,), f32), s((S, N, I), f32),
        s((S,), jnp.bool_)).compile()
    hlo = compiled.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1
    assert compiled.memory_analysis().alias_size_in_bytes == S * N * I * 4
    made = set(re.findall(rf"= f32\[{S},{N},{I}\]\S* ([\w\-]+)\(", hlo))
    assert made <= {"parameter", "get-tuple-element"}, made


def test_step_rows_under_serve_tp_on_four_devices(topo):
    """The walk under ``--serve_tp 4``: GSPMD refuses a bare Mosaic call, so
    the step shard_maps itself with every operand whole on every shard, as
    ``selective_scan_kernel`` does."""
    from building_llm_from_scratch_tpu.ops import selective_scan as ss

    mesh = Mesh(np.array(topo.devices).reshape(1, 1, 4),
                ("data", "seq", "model"))
    whole = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=NamedSharding(mesh, P()))
    S, I, N = 8, 5120, 16
    hlo = _compile(trace_under_mesh(
        lambda u, delta, A, Bm, Cm, D, state, live: ss.selective_step_rows(
            u, delta, A, Bm, Cm, D, state, ss.live_rows_table(live)),
        mesh), whole((S, I)), whole((S, I)), whole((N, I)), whole((S, N)),
        whole((S, N)), whole((I,)), whole((S, N, I)),
        whole((S,), jnp.bool_))
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1


def test_xent_fwd_largest_admitted_shape(one_chip):
    """The gate's VMEM budget, at its edge: the largest hidden width it
    admits for 2048 rows compiles, and the next lane multiple is refused
    by the gate (not by the compiler, mid-run). The vocabulary only sets
    the grid length, so a short one keeps the case quick."""
    N, D, V = 2048, 8704, 1024
    assert xp.supports_shape(N, D, V)
    assert not xp.supports_shape(N, D + 1024, V)
    assert not xp.supports_shape(16384, 768, 50257)   # refused at 100 MiB
    s = _spec(one_chip)
    hlo = _compile(xp.xent_fwd, s((N, D)), s((D, V)), s((N,), I32))
    assert hlo.count("tpu_custom_call") == 1


def test_sharded_attention_on_four_devices(topo):
    """GSPMD refuses a bare Mosaic call ("wrap the call in a shard_map"):
    under a 4-device data mesh the kernels shard_map themselves over the
    mesh the step builder made visible (parallel/collectives)."""
    mesh = Mesh(np.array(topo.devices), ("data",))
    s = _spec(NamedSharding(mesh, P("data")))
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32,
                               sharding=NamedSharding(mesh, P()))
    qkv = s((16, 1024, 12, 64))
    hlo = _compile(trace_under_mesh(_attention_grad, mesh),
                   qkv, qkv, qkv, rng)
    assert hlo.count("tpu_custom_call") == 3
    with pytest.raises(NotImplementedError, match="shard_map"):
        _compile(_attention_grad, qkv, qkv, qkv, rng)   # no mesh in scope


def test_sharded_lane_window_append_on_four_devices(topo):
    """The tick program's append under ``--serve_tp 4``: each device takes
    the windows of its own three heads, in place."""
    mesh = Mesh(np.array(topo.devices).reshape(1, 1, 4),
                ("data", "seq", "model"))
    panes = _spec(NamedSharding(mesh, P(None, "model")))
    new, pane = panes((8, 12, 1, 64)), panes((8, 12, 1024, 64))
    lens = jax.ShapeDtypeStruct((8,), I32, sharding=NamedSharding(mesh, P()))
    assert ds.supports_lane_append(1, 1024, 64, Hkv=12, dtype=BF16)
    hlo = jax.jit(trace_under_mesh(ds.lane_window_append, mesh),
                  donate_argnums=(0, 1)).lower(
        pane, pane, new, new, lens).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1
    assert "bf16[8,3,1024,64]" in hlo and " copy(" not in "".join(
        ln for ln in hlo.split("\n") if "bf16[8,3,1024,64]" in ln
        or "bf16[8,3,64,1024]" in ln)


def test_sharded_live_block_attention_on_four_devices(topo):
    """The tick program's attention under ``--serve_tp 4``: each device
    reads the live blocks of its own three heads, and no pane is copied."""
    mesh = Mesh(np.array(topo.devices).reshape(1, 1, 4),
                ("data", "seq", "model"))
    heads = _spec(NamedSharding(mesh, P(None, None, "model")))
    panes = _spec(NamedSharding(mesh, P(None, "model")))
    q, pane = heads((8, 1, 12, 64)), panes((8, 12, 1024, 64))
    lens = jax.ShapeDtypeStruct((8,), I32, sharding=NamedSharding(mesh, P()))
    assert ds.supports_live_attention(1, 1024, 64, S=8, Hkv=12, Hq=12,
                                      dtype=BF16)
    hlo = _compile(trace_under_mesh(ds.live_block_attention, mesh),
                   q, pane, pane, lens)
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1
    assert "bf16[8,3,64,1024]" in hlo and " copy(" not in "".join(
        ln for ln in hlo.split("\n") if "bf16[8,3,1024,64]" in ln
        or "bf16[8,3,64,1024]" in ln)


def test_sharded_live_rows_attention_on_four_devices(topo):
    """The same under ``--serve_tp 4`` at ``head_dim`` 128 (the sublane
    form, a ring): each device reads the live blocks of its own two
    key-value heads as they lie, and no pane is copied."""
    mesh = Mesh(np.array(topo.devices).reshape(1, 1, 4),
                ("data", "seq", "model"))
    heads = _spec(NamedSharding(mesh, P(None, None, "model")))
    panes = _spec(NamedSharding(mesh, P(None, "model")))
    q, pane = heads((8, 1, 32, 128)), panes((8, 8, 4608, 128))
    whole = lambda shape, dt: jax.ShapeDtypeStruct(
        shape, dt, sharding=NamedSharding(mesh, P()))
    hlo = _compile(trace_under_mesh(
        lambda q, K, V, n, live: ds.live_block_attention(
            q, K, V, n, live=live, window=4096), mesh),
        q, pane, pane, whole((8,), I32), whole((8,), jnp.bool_))
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1
    assert "bf16[8,2,4608,128]" in hlo and " copy(" not in "".join(
        ln for ln in hlo.split("\n") if "bf16[8,2,4608,128]" in ln)


def test_sharded_chunk_live_attention_on_four_devices(topo):
    """The chunk program's attention under ``--serve_tp 4``: each device
    attends the chunk's queries of its own two key-value heads (eight query
    heads) over its own share of the row, and no pane is copied."""
    from building_llm_from_scratch_tpu.ops import chunk_attention as ca

    mesh = Mesh(np.array(topo.devices).reshape(1, 1, 4),
                ("data", "seq", "model"))
    heads = _spec(NamedSharding(mesh, P(None, None, "model")))
    panes = _spec(NamedSharding(mesh, P(None, "model")))
    q, pane = heads((1, 512, 32, 128)), panes((8, 8, 4608, 128))
    at = jax.ShapeDtypeStruct((), I32, sharding=NamedSharding(mesh, P()))
    assert ca.supports_chunk_attention(512, 4608, 128, Hkv=8, Hq=32,
                                       dtype=BF16)
    hlo = _compile(trace_under_mesh(
        lambda *a: ca.chunk_live_attention(*a, window=4096),
        mesh), q, pane, pane, at, at, at)
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1
    assert "bf16[8,2,4608,128]" in hlo and " copy(" not in "".join(
        ln for ln in hlo.split("\n") if "bf16[8,2,4608,128]" in ln)


def test_grouped_experts_under_a_data_mesh_on_four_devices(topo):
    """A sparse model's forward pass under a four-device data mesh (the
    expert layer has no tensor-parallel split): GSPMD refuses a bare Mosaic
    call, so the grouped product shard_maps itself with every operand whole
    on every device."""
    from building_llm_from_scratch_tpu.ops import grouped_experts as ge

    mesh = Mesh(np.array(topo.devices), ("data",))
    s = _spec(NamedSharding(mesh, P()))
    hlo = _compile(trace_under_mesh(ge.grouped_gated_product, mesh),
                   s((1024, 1024)), s((2, 8, 1024, 512)),
                   s((2, 8, 1024, 512)), s((2, 8, 512, 1024)), s((), I32),
                   s((8,), I32))
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1


@pytest.mark.parametrize("S,Hq,Hkv,dtype", [
    (32, 25, 25, BF16),          # GPT2-1.5B, the serving cells
    (32, 25, 25, jnp.float32),
    (8, 32, 8, BF16),            # Llama-3.2-1B: four query heads a group
    (48, 12, 12, BF16),          # six grid cells of 8 rows
])
def test_live_block_attention_real_widths(one_chip, S, Hq, Hkv, dtype):
    assert ds.supports_live_attention(1, 1024, 64, S=S, Hkv=Hkv, Hq=Hq,
                                      dtype=dtype)
    s = _spec(one_chip)
    pane = s((S, Hkv, 1024, 64), dtype)
    hlo = _compile(ds.live_block_attention, s((S, 1, Hq, 64), dtype), pane,
                   pane, s((S,), I32))
    assert hlo.count("tpu_custom_call") == 1
