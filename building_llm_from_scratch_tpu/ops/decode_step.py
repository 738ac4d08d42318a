"""The decode tick's kernels on the slot cache (pallas, TPU), and the
cache's plain writers.

Why kernels at all: a decode tick appends ONE position a row and reads each
row's prefix. As XLA ops on the (S, Hkv, Tmax, hd) buffers the append is a
loop of per-row updates and the attention two reductions over every position
of every row, live or not. A pallas call with ``input_output_aliases``
DECLARES the in-place update, and a kernel handed the cache in the layout
the runtime keeps it in (positions on the lanes) neither copies nor relays
a pane.

  - ``lane_window_append``: a tick's keys and values into every row at its
    own length, one aliased call a layer (PR 26).
  - ``live_block_attention``: each row's one query against the key blocks
    its live positions reach, and no others: blocks of lanes where
    ``head_dim`` is under 128 (PR 29), of sublanes at 128, where a ring is
    read by position and a row that does not decode not at all (PR 43).
  - ``slot_cache_append`` / ``quantize_kv``: the per-row scatter and the
    int8 quantise-on-write that everything outside the two gates keeps
    (``supports_lane_append``, ``supports_live_attention``).
  - ``lora_bgmv``, ``paged_decode_attention``: per-row adapter deltas and
    page-table attention, both opt-in until measured on a chip.

Semantics are ``ops.attention.decode_attention``'s throughout: cache layout
(B, Hkv, Tmax, hd), a valid prefix per row, eval-only (no dropout, no grad
— generation never trains). ``models/transformer.py``'s access objects
(``_SlotKV``) are the only callers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from building_llm_from_scratch_tpu.parallel.collectives import mesh_kernel
from building_llm_from_scratch_tpu.parallel.mesh import MODEL_AXIS

_NEG_BIG = -1e30
# mosaic wants >= 8 sublanes; decode's G*Tq is often 1 — pad the query rows
_MIN_ROWS = 8
#: what one grid cell of a kernel here may hold: under the compiler's default
#: 16 MiB scoped-VMEM limit (asking for more does not help where it matters:
#: inside a whole decode program the v5e compiler then assigns whole cache
#: arrays to VMEM beside the kernel's own scope)
_VMEM_BUDGET = 14 * 2 ** 20

#: symmetric int8 KV quantization floor: an all-zero position (zeroed
#: pad, never-written cache row) quantizes to scale EPS and exact-zero
#: codes, so dequantization is exactly zero — byte-deterministic panes
KV_QUANT_EPS = 1e-8


def quantize_kv(x: jnp.ndarray) -> tuple:
    """Symmetric int8 quantization over the trailing head_dim axis:
    one fp32 scale per (..., position, head) written — computed at
    APPEND time, so every cache write is self-describing and appends at
    different times never re-scale each other's history.

    Returns (codes int8 (..., hd), scales fp32 (..., 1)) with
    ``codes * scales ~= x`` (max error scale/2 per element)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.maximum(amax / 127.0, KV_QUANT_EPS)
    codes = jnp.clip(jnp.round(xf / scale), -127.0, 127.0).astype(jnp.int8)
    return codes, scale


def dequantize_kv(codes: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    """Inverse of ``quantize_kv`` (fp32). The decode path never calls
    this on a whole cache — ``decode_attention`` folds the scales into
    its einsums instead — but parity tests and one-off consumers do."""
    return codes.astype(jnp.float32) * scale


def slot_cache_append(cache: jnp.ndarray, new: jnp.ndarray,
                      lengths: jnp.ndarray) -> jnp.ndarray:
    """Batched slot-indexed cache append: write ``new`` (B, Hkv, Tq, hd)
    into ``cache`` (B, Hkv, Tmax, hd) at PER-ROW time offsets ``lengths``
    (B,) — the continuous-batching primitive where every batch row is a
    different request at a different sequence length.

    Scalar ``lengths`` degrades to the shared-offset single
    ``dynamic_update_slice`` the one-shot decode path uses. The vmap'd
    per-row form becomes a ``scatter``, which the TPU compiler expands
    into a ``while`` of B trips, each a serial chain of about ten tiny
    operations (bound by their latency, not by bytes: 60% of the 1.5B
    decode program before PR 26). It is the REFERENCE write: whatever
    ``supports_lane_append`` refuses runs it, and ``lane_window_append``
    below (one in-place kernel call a layer, where the gate admits it)
    is tested against it bit for bit.
    """
    lengths = jnp.asarray(lengths)
    if lengths.ndim == 0:
        return jax.lax.dynamic_update_slice(
            cache, new.astype(cache.dtype), (0, 0, lengths, 0))

    def one(c, n, t):                      # c (Hkv, Tmax, hd), n (Hkv, Tq, hd)
        return jax.lax.dynamic_update_slice(c, n.astype(c.dtype), (0, t, 0))

    return jax.vmap(one)(cache, new, lengths.astype(jnp.int32))


#: positions of one lane tile: the window a lane-window append rewrites
_LANES = 128


def _lane_append_kernel(len_ref, kn_ref, vn_ref, K_ref, V_ref,
                        Ko_ref, Vo_ref):
    """One slot a grid cell. The index maps already chose the 128-position
    window that holds position ``t``; merge the new column at lane
    ``t % 128`` into it and store the window back over itself."""
    lane = len_ref[pl.program_id(0)] % _LANES
    hit = jax.lax.broadcasted_iota(jnp.int32, K_ref.shape, 3) == lane
    Ko_ref[...] = jnp.where(hit, kn_ref[...], K_ref[...])
    Vo_ref[...] = jnp.where(hit, vn_ref[...], V_ref[...])


def lane_window_append(k_cache, v_cache, k_new, v_new, lengths, *,
                       interpret=False):
    """Append ONE token a row to both buffers of a layer, in place, in one
    call: ``k_new``/``v_new`` (S, Hkv, 1, hd) go to position
    ``lengths[s]`` (>= 0) of ``k_cache``/``v_cache`` (S, Hkv, Tmax, hd).
    What it leaves is bit-identical to two ``slot_cache_append``s.

    Works on the cache's OWN device layout. For ``head_dim < 128`` the
    TPU runtime keeps (S, Hkv, Tmax, hd) with positions on the 128 lanes
    and ``head_dim`` on the sublanes (``{2,3,1,0:T(8,128)(2,1)}``: a
    minor dimension of 64 would waste half of every tile), so
    ``swapaxes(2, 3)`` is a bitcast there and a token is one lane column.
    The kernel sees (S, Hkv, hd, Tmax), takes the one (1, Hkv, hd, 128)
    window at lane block ``lengths[s] // 128`` (scalar-prefetched), and
    aliases each cache onto its output: windows never visited stay where
    they are, and no pane is copied or relaid. (A kernel handed the
    logical shape, or a hinted scatter, gets whole-pane relayout copies
    in and out: PERF.md section 7.)

    Under a mesh (``--serve_tp``) heads shard over the model axis like
    the slot cache; ``interpret=True`` runs on CPU for parity tests."""
    lengths = jnp.asarray(lengths, jnp.int32)
    panes = (None, MODEL_AXIS, None, None)
    return mesh_kernel(
        lambda _, *a: _lane_append_local(*a, interpret=interpret),
        (k_cache, v_cache, k_new, v_new, lengths),
        (panes, panes, panes, panes, (None,)),
        (panes, panes))


@functools.partial(jax.jit, static_argnames="interpret")
def _lane_append_local(k_cache, v_cache, k_new, v_new, lengths, *,
                       interpret):
    # jitted so that a program's layers trace and lower the kernel ONCE
    # (48 calls of one function): without it a warm set-up of the 1.5B
    # engine spends 3 s more on its decode program
    S, Hkv, Tmax, hd = k_cache.shape
    # dynamic_update_slice clamps an origin past the end; so does this
    lengths = jnp.minimum(lengths, Tmax - 1)
    window = pl.BlockSpec((1, Hkv, hd, _LANES),
                          lambda s, len_ref: (s, 0, 0, len_ref[s] // _LANES))
    column = pl.BlockSpec((1, Hkv, hd, 1), lambda s, len_ref: (s, 0, 0, 0))
    t = lambda x: jnp.swapaxes(x, 2, 3)        # a bitcast of the panes
    ko, vo = pl.pallas_call(
        _lane_append_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(S,),
            in_specs=[column, column, window, window],
            out_specs=[window, window],
        ),
        out_shape=[jax.ShapeDtypeStruct((S, Hkv, hd, Tmax), k_cache.dtype),
                   jax.ShapeDtypeStruct((S, Hkv, hd, Tmax), v_cache.dtype)],
        # K->Ko, V->Vo in place (operand indices count the prefetch arg)
        input_output_aliases={3: 0, 4: 1},
        interpret=interpret,
    )(lengths, t(k_new).astype(k_cache.dtype), t(v_new).astype(v_cache.dtype),
      t(k_cache), t(v_cache))
    return t(ko), t(vo)


def _live_block_kernel(len_ref, q_ref, k_hbm, v_hbm, o_ref,
                       kbuf, vbuf, sem, done_ref, m_ref, d_ref, acc_ref, *,
                       scale: float, block: int):
    """One grid cell = ``rows`` slots. For each row in turn, copy its live
    lane blocks (and no other) from HBM into one of two VMEM buffers while
    the block before is folded into the row's running softmax state
    (``_paged_kernel``'s max / denominator / accumulator, all heads at
    once); the first block of the NEXT row is asked for while this row's
    last one is computed, so the copies never stop between rows or between
    grid cells. In the cache's own orientation (hd, block) the score
    product contracts the sublanes and the value product the lanes: both
    are plain matrix products, and the heads' chains are independent, so
    the scheduler spreads them over the MXUs."""
    rows = q_ref.shape[0]
    n_rows = rows * pl.num_programs(0)
    first_row = pl.program_id(0) * rows

    def copies(row, b, slot):
        at = pl.ds(pl.multiple_of(b * block, block), block)
        return [pltpu.make_async_copy(hbm.at[row, :, :, at], buf.at[slot],
                                      sem.at[i, slot])
                for i, (hbm, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf)))]

    def start(row, b, slot):
        for copy in copies(row, b, slot):
            copy.start()

    @pl.when(pl.program_id(0) == 0)
    def _first_copy():
        done_ref[0] = 0
        start(0, 0, 0)

    def one_row(r, n_done):
        row = first_row + r
        n = len_ref[row]
        n_blocks = (jnp.maximum(n, 1) + block - 1) // block
        m_ref[...] = jnp.full_like(m_ref, _NEG_BIG)
        d_ref[...] = jnp.zeros_like(d_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        q = q_ref[r]                                      # (Hkv, Rp, hd)

        def one_block(b, n_done):
            slot = n_done % 2
            more = b + 1 < n_blocks

            @pl.when(more)
            def _next_block():
                start(row, b + 1, 1 - slot)

            @pl.when(jnp.logical_and(jnp.logical_not(more),
                                     row + 1 < n_rows))
            def _next_row():
                start(row + 1, 0, 1 - slot)

            for copy in copies(row, b, slot):
                copy.wait()
            k = kbuf[slot]                                # (Hkv, hd, block)
            live = b * block + jax.lax.broadcasted_iota(
                jnp.int32, (1, 1, block), 2) < n
            # a masked probability is exactly 0, but 0 * NaN is not: what
            # lies past a row's length never enters a product
            v = jnp.where(live, vbuf[slot], jnp.zeros((), vbuf.dtype))
            sc = jax.lax.dot_general(q, k, (((2,), (1,)), ((0,), (0,))),
                                     preferred_element_type=jnp.float32)
            sc = jnp.where(live, sc * scale, _NEG_BIG)    # (Hkv, Rp, block)
            m_new = jnp.maximum(m_ref[...],
                                jnp.max(sc, axis=-1, keepdims=True))
            alpha = jnp.exp(m_ref[...] - m_new)
            p = jnp.exp(sc - m_new)
            m_ref[...] = m_new
            d_ref[...] = d_ref[...] * alpha + jnp.sum(p, axis=-1,
                                                      keepdims=True)
            # probabilities in the cache's dtype before the value product,
            # as ``decode_attention`` casts them
            acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
            return n_done + 1

        n_done = jax.lax.fori_loop(0, n_blocks, one_block, n_done)
        o_ref[r] = (acc_ref[...] / d_ref[...]).astype(o_ref.dtype)
        return n_done

    # blocks folded so far, over all cells: its parity is the buffer that
    # the copy in flight (asked for by the cell before) lands in
    done_ref[0] = jax.lax.fori_loop(0, rows, one_row, done_ref[0])


def live_block_attention(q, k_cache, v_cache, kv_length, *, live=None,
                         window=None, interpret=False):
    """``decode_attention`` for ONE query token a row (``q`` (S, 1, Hq,
    hd), per-row ``kv_length`` (S,): the row attends positions
    ``[0, kv_length)``) that reads only the key blocks each row's live
    positions reach, where ``decode_attention`` reduces over the whole
    (S, Hkv, Tmax, hd) buffer with the lengths as a mask: a masked
    position contributed exactly 0 there, so leaving it unread is the
    same arithmetic.

    It works on the cache's own device layout, and the block axis follows
    it. ``head_dim < 128``: positions on the lanes (the ``swapaxes`` is a
    bitcast, no pane is copied or relaid), blocks of ``LIVE_BLOCK`` lanes;
    a free slot (length 0 or 1) reads one block. ``head_dim == 128``:
    positions on the sublanes, the buffer read as it lies in blocks of
    rows (``_rows_block``); there a 'sliding' layer's ring (``window``: the
    row attends ``(kv_length - window, kv_length)``, position p at index
    p mod Tmax, ``ring_positions``' arithmetic) is read by position, the
    blocks its window reaches and a row shorter than the ring its own
    prefix, and a row that does not decode (``live`` (S,) bool; None:
    every row does) starts no copy and reads zeros. Either way the buffers
    stay in HBM and the kernel copies blocks itself (the rows' scalars
    prefetched), so HBM traffic is the block-rounded live positions and
    nothing is paid for the blocks past them; ``Hq // Hkv`` query heads ride
    one key-value head as rows of its products.

    Under a mesh (``--serve_tp``) heads shard over the model axis like
    the slot cache; ``interpret=True`` runs on CPU for parity tests."""
    heads = (None, None, MODEL_AXIS, None)
    panes = (None, MODEL_AXIS, None, None)
    kv_length = jnp.asarray(kv_length, jnp.int32)
    if q.shape[-1] < _LANES:
        local = functools.partial(_live_attention_local, interpret=interpret)
    else:
        if live is not None:
            kv_length = jnp.where(live, kv_length, 0)
        local = functools.partial(_live_rows_local, window=window,
                                  interpret=interpret)
    return mesh_kernel(lambda _, *a: local(*a),
                       (q, k_cache, v_cache, kv_length),
                       (heads, panes, panes, (None,)), heads)


@functools.partial(jax.jit, static_argnames="interpret")
def _live_attention_local(q, k_cache, v_cache, kv_length, *, interpret):
    # jitted for the reason ``_lane_append_local`` is: one lowering a program
    S, Tq, Hq, hd = q.shape
    _, Hkv, Tmax, _ = k_cache.shape
    if Tq != 1:
        raise ValueError(f"live_block_attention is single-token only; "
                         f"Tq={Tq}")
    block = LIVE_BLOCK
    G = Hq // Hkv
    Rp = max(_MIN_ROWS, G)
    # (S, Hkv, G, hd) query rows, padded to the sublane minimum
    qr = q.reshape(S, Hkv, G, hd).astype(k_cache.dtype)
    if Rp != G:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, Rp - G), (0, 0)))
    rows = _live_attention_rows(S)
    some_rows = pl.BlockSpec((rows, Hkv, Rp, hd),
                             lambda i, len_ref: (i, 0, 0, 0))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    t = lambda x: jnp.swapaxes(x, 2, 3)        # a bitcast of the panes
    out = pl.pallas_call(
        functools.partial(_live_block_kernel, scale=1.0 / float(hd) ** 0.5,
                          block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(S // rows,),
            in_specs=[some_rows, in_hbm, in_hbm],
            out_specs=some_rows,
            scratch_shapes=[
                pltpu.VMEM((2, Hkv, hd, block), k_cache.dtype),
                pltpu.VMEM((2, Hkv, hd, block), v_cache.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),                  # blocks folded
                pltpu.VMEM((Hkv, Rp, 1), jnp.float32),        # running max
                pltpu.VMEM((Hkv, Rp, 1), jnp.float32),        # denominator
                pltpu.VMEM((Hkv, Rp, hd), jnp.float32),       # accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((S, Hkv, Rp, hd), q.dtype),
        name="live_block_attention",
        interpret=interpret,
    )(jnp.minimum(kv_length, Tmax), qr, t(k_cache), t(v_cache))
    return out[:, :, :G].reshape(S, 1, Hq, hd)


#: what a masked score reads: under the running maximum's start
#: (``_NEG_BIG``), so its probability is exp(-1e30) = 0 exactly, also for a
#: query that has seen no live key yet (an old block of a ring lies outside
#: the window of a chunk's later queries)
_MASKED = 2 * _NEG_BIG


def _ring_age(newest, index, ring_len: int):
    """How many positions behind the newest one the position at ``index``
    of a ring of ``ring_len`` lies, the newest at index ``newest`` (both in
    [0, ring_len)): 0 at ``newest``, 1 at the index before it, around the
    ring's end. A buffer as long as the sequence is the ring that never
    wraps. THE ring arithmetic of both kernels that read one by position
    (``ops/chunk_attention.py::_first_position``; below); no division, so
    it serves a vector of indices as well as a scalar."""
    age = newest - index
    return jnp.where(age < 0, age + ring_len, age)


#: key positions of one block of the sublane form: the largest of these
#: that divides the buffer. Measured on the chip at the cells' four buffer
#: lengths and their mixes of lengths, and at 192 short rows (PERF.md
#: section 6, PR 43; ms a call at 128 / 256 / 512): a ring of 4,608 with 7
#: rows of 48 decoding 0.249 / 0.217 / 0.225, a full layer of 20,480 the
#: same rows 0.238 / 0.215 / 0.223, of 33,792 with 8 rows 0.353 / 0.298 /
#: 0.290, two of 3,072 at one key-value head with 72 rows of 192 0.239 /
#: 0.198 / 0.160, 192 rows of 40-200 positions 0.231 / 0.191 / 0.206 at
#: one key-value head and 0.350 / 0.351 / 0.618 at eight. 256 is within 3%
#: of the best wherever rows are long; 512 copies too many dead positions
#: for short rows at eight heads, 128 pays a step's fixed cost twice
_ROW_BLOCKS = (256, 128)


def _rows_block(Tmax: int):
    return next((b for b in _ROW_BLOCKS if Tmax % b == 0), None)


def live_block_span(kv_length, *, ring_len: int, block: int, window, xp=jnp):
    """The blocks of a ``ring_len``-position buffer that hold the live
    positions of rows at ``kv_length`` (an int array; 0: a row that reads
    nothing): those under the length, in a ring (``window``) the newest
    ``window`` of them, which may wrap the buffer's end. -> (first block,
    blocks from it on around the end, the newest position's index, live
    positions), each like ``kv_length``. ``xp=np`` is the host's twin (the
    engine's ``kv_touched``)."""
    n = kv_length if window is not None else xp.minimum(kv_length, ring_len)
    live = xp.minimum(n, min(window or ring_len, ring_len))
    oldest = (n - live) % ring_len
    blocks = xp.minimum((oldest % block + live + block - 1) // block,
                        ring_len // block)
    return oldest // block, blocks, (n - 1) % ring_len, live


# rows of the scalar-prefetched (5, S + 1) table of ``_live_rows_kernel``
_FIRST, _BLOCKS, _NEWEST, _LIVE, _NEXT = range(5)


def _live_rows_kernel(at_ref, q_ref, k_hbm, v_hbm, o_ref,
                      kbuf, vbuf, sem, slot_ref, m_ref, d_ref, acc_ref, *,
                      scale: float, block: int):
    """``_live_block_kernel`` for the layout ``head_dim`` 128 has on the
    device (positions on the sublanes: a key block is (block, 128) rows as
    they lie). One grid cell = ``rows`` slots; for each in turn its live
    blocks (``live_block_span``, a row of ``at_ref`` each; a ring's may
    wrap) are copied from HBM into one of two VMEM buffers while the block
    before is folded into the running max / denominator / accumulator, all
    heads at once; the one copy in flight is the row's next block or, at its
    last, the first block of the next row that has any (``_NEXT``), across
    grid cells too. A row with no live position starts no copy and reads 0.
    Rows and blocks are rolled (``fori_loop``): one body, lowered once."""
    rows = q_ref.shape[0]
    n_rows = rows * pl.num_programs(0)
    ring_len = k_hbm.shape[2]
    ring_blocks = ring_len // block
    first_row = pl.program_id(0) * rows

    def copies(row, b, slot):
        at = pl.ds(pl.multiple_of(b * block, block), block)
        return [pltpu.make_async_copy(hbm.at[row, :, at, :], buf.at[slot],
                                      sem.at[i, slot])
                for i, (hbm, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf)))]

    def start(row, b, slot):
        for copy in copies(row, b, slot):
            copy.start()

    def around(b):
        return jnp.where(b >= ring_blocks, b - ring_blocks, b)

    @pl.when(pl.program_id(0) == 0)
    def _first_copy():
        slot_ref[0] = 0
        row = at_ref[_NEXT, 0]
        pl.when(row < n_rows)(lambda: start(row, at_ref[_FIRST, row], 0))

    def one_row(r, slot):
        row = first_row + r
        first, n_blocks = at_ref[_FIRST, row], at_ref[_BLOCKS, row]
        newest, n_live = at_ref[_NEWEST, row], at_ref[_LIVE, row]
        next_row = at_ref[_NEXT, row + 1]
        m_ref[...] = jnp.full_like(m_ref, _NEG_BIG)
        d_ref[...] = jnp.zeros_like(d_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        q = q_ref[r]                                      # (Hkv, Rp, hd)

        def one_block(j, slot):
            b = around(first + j)
            more = j + 1 < n_blocks
            pl.when(jnp.logical_or(more, next_row < n_rows))(
                lambda: start(jnp.where(more, row, next_row),
                              jnp.where(more, around(b + 1),
                                        at_ref[_FIRST, next_row]),
                              1 - slot))
            for copy in copies(row, b, slot):
                copy.wait()
            behind = newest - b * block     # of the block's first index

            def seen(shape, axis):      # the block's indices, by position
                return _ring_age(behind, jax.lax.broadcasted_iota(
                    jnp.int32, shape, axis), ring_len) < n_live

            # a masked probability is exactly 0, but 0 * NaN is not: what
            # lies outside a row's live positions never enters a product
            v = jnp.where(seen((1, block, 1), 1), vbuf[slot],
                          jnp.zeros((), vbuf.dtype))
            sc = jax.lax.dot_general(q, kbuf[slot],
                                     (((2,), (2,)), ((0,), (0,))),
                                     preferred_element_type=jnp.float32)
            sc = jnp.where(seen((1, 1, block), 2), sc * scale, _MASKED)
            m_new = jnp.maximum(m_ref[...],
                                jnp.max(sc, axis=-1, keepdims=True))
            alpha = jnp.exp(m_ref[...] - m_new)
            p = jnp.exp(sc - m_new)                   # (Hkv, Rp, block)
            m_ref[...] = m_new
            d_ref[...] = d_ref[...] * alpha + jnp.sum(p, axis=-1,
                                                      keepdims=True)
            # probabilities in the cache's dtype before the value product,
            # as ``decode_attention`` casts them
            acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
            return 1 - slot

        slot = jax.lax.fori_loop(0, n_blocks, one_block, slot)
        d = d_ref[...]
        o_ref[r] = (acc_ref[...] / jnp.where(d == 0.0, 1.0, d)
                    ).astype(o_ref.dtype)
        return slot

    # the buffer that the copy in flight (asked for by the block before,
    # in this cell or the one before it) lands in
    slot_ref[0] = jax.lax.fori_loop(0, rows, one_row, slot_ref[0])


def _query_rows(G: int, itemsize: int) -> int:
    """A group's query heads as rows of one product, padded to whole
    sublane tiles of the cache's dtype."""
    tile = 32 // itemsize
    return -(-G // tile) * tile


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def _live_rows_local(q, k_cache, v_cache, kv_length, *, window, interpret):
    # jitted for the reason ``_lane_append_local`` is: one lowering a
    # program for all its layers of one buffer shape (and window)
    S, Tq, Hq, hd = q.shape
    _, Hkv, Tmax, _ = k_cache.shape
    if Tq != 1:
        raise ValueError(f"live_block_attention is single-token only; "
                         f"Tq={Tq}")
    block = _rows_block(Tmax)
    G = Hq // Hkv
    Rp = _query_rows(G, k_cache.dtype.itemsize)
    qr = q.reshape(S, Hkv, G, hd).astype(k_cache.dtype)
    if Rp != G:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, Rp - G), (0, 0)))
    first, blocks, newest, live = live_block_span(
        kv_length, ring_len=Tmax, block=block, window=window)
    # the next row after each that has a block to read (S: none), and
    # before them all the first such row
    reads = jnp.where(blocks > 0, jnp.arange(S), S)
    next_row = jnp.append(jax.lax.cummin(reads, reverse=True), S)
    at = jnp.concatenate([
        jnp.pad(jnp.stack([first, blocks, newest, live]), ((0, 0), (0, 1))),
        next_row[None]]).astype(jnp.int32)
    rows = _live_attention_rows(S)
    some_rows = pl.BlockSpec((rows, Hkv, Rp, hd),
                             lambda i, at_ref: (i, 0, 0, 0))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        functools.partial(_live_rows_kernel, scale=1.0 / float(hd) ** 0.5,
                          block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(S // rows,),
            in_specs=[some_rows, in_hbm, in_hbm],
            out_specs=some_rows,
            scratch_shapes=[
                pltpu.VMEM((2, Hkv, block, hd), k_cache.dtype),
                pltpu.VMEM((2, Hkv, block, hd), v_cache.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),                  # buffer in use
                pltpu.VMEM((Hkv, Rp, 1), jnp.float32),        # running max
                pltpu.VMEM((Hkv, Rp, 1), jnp.float32),        # denominator
                pltpu.VMEM((Hkv, Rp, hd), jnp.float32),       # accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((S, Hkv, Rp, hd), q.dtype),
        name="live_rows_attention",
        interpret=interpret,
    )(at, qr, k_cache, v_cache)
    return out[:, :, :G].reshape(S, 1, Hq, hd)


def _bgmv_kernel(ids_ref, x_ref, a_ref, b_ref, scale_ref, o_ref, *,
                 n_pool: int):
    """One batch row per grid cell: the row's adapter id (scalar-prefetched
    SMEM) selected WHICH (D, r)/(r, O) pool panes the BlockSpec index maps
    DMA'd into VMEM; here we just multiply through and scale. id −1 rows
    fetch the clamped pane but scale by 0 — exact zero delta, no branch."""
    s = pl.program_id(0)
    i = ids_ref[s]
    sc = jnp.where(i >= 0, scale_ref[jnp.clip(i, 0, n_pool - 1)], 0.0)
    xa = jax.lax.dot(x_ref[0], a_ref[0],
                     preferred_element_type=jnp.float32)       # (rows, r)
    o_ref[0] = jax.lax.dot(xa.astype(b_ref.dtype), b_ref[0],
                           preferred_element_type=jnp.float32) * sc


def lora_bgmv(x, a_pool, b_pool, ids, scales, *, interpret=False):
    """Punica/S-LoRA-style BGMV: per-row gathered LoRA delta, fused.

    x:       (S, D)  one activation row per slot (single-token decode)
    a_pool:  (N, D, r)  stacked adapter A matrices (N = pool capacity)
    b_pool:  (N, r, O)
    ids:     (S,) int32 adapter id per row; −1 = base model (zero delta)
    scales:  (N,) fp32 alpha/rank per pool row

    Returns (S, O) fp32: ``scales[ids[s]] * (x[s] @ A[ids[s]]) @ B[ids[s]]``.

    Each grid cell DMAs exactly ONE adapter's panes from the pool (the
    scalar-prefetched ``ids`` drive the BlockSpec index maps), so HBM
    traffic is O(S · adapter_size), independent of pool capacity — the
    XLA gather-then-einsum fallback materializes the same gather but
    cannot skip fetching for id −1 rows. Adapter identity is DATA: any
    id mix compiles to this one program. TPU-gated via
    ``supports_lora_shape``; ``interpret=True`` runs the kernel on CPU
    for parity tests. Under a mesh every operand is whole on every
    device (the engine replicates the pool), so each runs the same call."""
    return mesh_kernel(
        lambda _, *a: _bgmv_local(*a, interpret=interpret),
        (x, a_pool, b_pool, ids, scales),
        ((None,) * 2, (None,) * 3, (None,) * 3, (None,), (None,)),
        (None,) * 2)


def _bgmv_local(x, a_pool, b_pool, ids, scales, *, interpret):
    S, D = x.shape
    N, _, r = a_pool.shape
    O = b_pool.shape[-1]
    # mosaic wants >= 8 sublanes; one activation row -> pad to 8 zero rows
    xp = jnp.zeros((S, _MIN_ROWS, D), x.dtype)
    xp = jax.lax.dynamic_update_slice(xp, x[:, None, :], (0, 0, 0))
    ids = ids.astype(jnp.int32)
    scales = scales.astype(jnp.float32)

    def pool_idx(s, ids_ref):
        return (jnp.clip(ids_ref[s], 0, N - 1), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(S,),
        in_specs=[
            pl.BlockSpec((1, _MIN_ROWS, D), lambda s, ids_ref: (s, 0, 0)),
            pl.BlockSpec((1, D, r), pool_idx),
            pl.BlockSpec((1, r, O), pool_idx),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, _MIN_ROWS, O),
                               lambda s, ids_ref: (s, 0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_bgmv_kernel, n_pool=N),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, _MIN_ROWS, O), jnp.float32),
        interpret=interpret,
    )(ids, xp, a_pool, b_pool, scales)
    return out[:, 0]


def supports_lora_shape(D: int, r: int, O: int) -> bool:
    """BGMV kernel eligibility for one (in=D, rank=r, out=O) projection:
    lane-aligned in/out dims and a sublane-aligned rank (the r-wide
    intermediate). Unsupported shapes keep the XLA gather+einsum path —
    same numbers, just without the per-row pool-pane DMA savings."""
    return D % 128 == 0 and O % 128 == 0 and r % 8 == 0 and 8 <= r <= 256


def _paged_kernel(tab_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  mx_ref, d_ref, acc_ref, *, scale: float,
                  page_tokens: int, max_pages: int):
    """Online-softmax attention over one row's page list: grid cell
    (s, m) DMAs physical page ``tab_ref[s * max_pages + m]`` — the
    scalar-prefetched flattened page table drives the K/V BlockSpec
    index maps, exactly the ``lora_bgmv`` gather discipline — and folds
    its ``page_tokens`` positions into the running (max, denom, acc)
    scratch. Initialized at m == 0, finalized into ``o_ref`` at the last
    page. Mask: global position  m*P + p  <=  lengths[s]  (the
    ``decode_attention`` Tq=1 causal rule); pages past the row's
    frontier are all-masked, contributing exp(_NEG_BIG - max) == 0."""
    s = pl.program_id(0)
    m = pl.program_id(1)

    @pl.when(m == 0)
    def _init():
        mx_ref[...] = jnp.full_like(mx_ref, _NEG_BIG)
        d_ref[...] = jnp.zeros_like(d_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                      # (Hkv, Rp, hd)
    k = k_ref[0]                                      # (Hkv, P, hd)
    v = v_ref[0]
    Hkv, Rp, _ = q.shape
    P = k.shape[1]
    sc = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32) * scale
    pos = (m * page_tokens
           + jax.lax.broadcasted_iota(jnp.int32, (Hkv, Rp, P), 2))
    sc = jnp.where(pos <= len_ref[s], sc, _NEG_BIG)
    m_new = jnp.maximum(mx_ref[...], jnp.max(sc, axis=-1, keepdims=True))
    alpha = jnp.exp(mx_ref[...] - m_new)
    p = jnp.exp(sc - m_new)
    mx_ref[...] = m_new
    d_ref[...] = d_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)

    @pl.when(m == max_pages - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / d_ref[...]).astype(o_ref.dtype)


def paged_decode_attention(q, k_pool, v_pool, page_table, lengths, *,
                           interpret=False):
    """Page-table attention for single-token decode: attend each slot's
    logical row WITHOUT materializing it — grid cell (s, m) streams only
    the physical page the row's table names, so HBM traffic is
    O(tokens in flight), identical to the contiguous kernel's, while the
    XLA reference path (``transformer._paged_view``) first gathers a
    (S, Hkv, Tmax, hd) copy per layer.

    q:          (S, 1, Hq, hd)  model layout, single token
    k_pool:     (N, Hkv, P, hd) shared page pool (unquantized)
    v_pool:     (N, Hkv, P, hd)
    page_table: (S, M) int32 physical page per logical page (0 = trash)
    lengths:    (S,) int32 valid prefix per row; attends kv_pos <=
                lengths[s] (the new token's position, appended by the
                caller BEFORE this kernel runs)

    Returns (S, 1, Hq, hd) attention output. Page identity is DATA
    (scalar-prefetched), so any table contents run through one compiled
    program. ``interpret=True`` runs on CPU for parity tests. Under a
    mesh heads shard over the model axis, like the page pool."""
    heads = (None, None, MODEL_AXIS, None)
    pool = (None, MODEL_AXIS, None, None)
    return mesh_kernel(
        lambda _, *a: _paged_attention_local(*a, interpret=interpret),
        (q, k_pool, v_pool, page_table, jnp.asarray(lengths, jnp.int32)),
        (heads, pool, pool, (None, None), (None,)), heads)


def _paged_attention_local(q, k_pool, v_pool, page_table, lengths, *,
                           interpret):
    S, Tq, Hq, hd = q.shape
    N, Hkv, P, _ = k_pool.shape
    M = page_table.shape[1]
    if Tq != 1:
        raise ValueError(f"paged_decode_attention is single-token only; "
                         f"Tq={Tq}")
    G = Hq // Hkv
    R = G * Tq
    Rp = max(_MIN_ROWS, R)
    qr = q.reshape(S, Tq, Hkv, G, hd).transpose(0, 2, 3, 1, 4)
    qr = qr.reshape(S, Hkv, R, hd)
    if Rp != R:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, Rp - R), (0, 0)))
    tab = page_table.astype(jnp.int32).reshape(-1)
    lens = lengths

    def kv_idx(s, m, tab_ref, len_ref):
        return (jnp.clip(tab_ref[s * M + m], 0, N - 1), 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, M),
        in_specs=[
            pl.BlockSpec((1, Hkv, Rp, hd),
                         lambda s, m, tab_ref, len_ref: (s, 0, 0, 0)),
            pl.BlockSpec((1, Hkv, P, hd), kv_idx),
            pl.BlockSpec((1, Hkv, P, hd), kv_idx),
        ],
        out_specs=pl.BlockSpec((1, Hkv, Rp, hd),
                               lambda s, m, tab_ref, len_ref: (s, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv, Rp, 1), jnp.float32),
            pltpu.VMEM((Hkv, Rp, 1), jnp.float32),
            pltpu.VMEM((Hkv, Rp, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=1.0 / float(hd) ** 0.5,
                          page_tokens=P, max_pages=M),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, Hkv, Rp, hd), q.dtype),
        interpret=interpret,
    )(tab, lens, qr, k_pool, v_pool)
    out = out[:, :, :R]
    out = out.reshape(S, Hkv, G, Tq, hd).transpose(0, 3, 1, 2, 4)
    return out.reshape(S, Tq, Hq, hd)


def supports_paged_shape(Tq: int, page_tokens: int, hd: int) -> bool:
    """Paged-attention kernel eligibility: single-token decode,
    lane-aligned head dim, sublane-aligned page length (each page is one
    VMEM pane). Ineligible shapes — and int8 pools, gated off by the
    caller — keep the XLA gather
    reference path."""
    return (Tq == 1 and hd % 64 == 0 and hd <= 256
            and page_tokens % 8 == 0)


def supports_lane_append(Tq: int, Tmax: int, hd: int, *, Hkv: int,
                         dtype) -> bool:
    """``lane_window_append`` eligibility: one token a row, a float
    cache whose ``head_dim`` is under a lane tile (so the runtime keeps
    positions on the lanes and the kernel's view is a bitcast; at 128
    it keeps the logical layout, positions on the sublanes, and the
    view would be a transpose of every pane), whole lane windows, and the
    pipeline's blocks (two windows in, two out, two columns padded to a
    window, each double-buffered) inside the VMEM budget. int8 caches
    and their (…, 1) scale sidecars have other native layouts. Whatever
    this refuses keeps ``slot_cache_append``'s scatter."""
    dtype = jnp.dtype(dtype)
    return (Tq == 1 and jnp.issubdtype(dtype, jnp.floating)
            and hd < _LANES and hd % 16 == 0 and Tmax % _LANES == 0
            and 12 * Hkv * hd * _LANES * dtype.itemsize <= _VMEM_BUDGET)


#: positions in one lane block of ``live_block_attention``: one lane tile,
#: which rounds a row's length up by the least. Measured on the 1.5B pane
#: (PERF.md section 6, PR 29): blocks of 256 are slower at every mix of
#: lengths, because the kernel's own copies pay nothing for a step but the
#: copy itself and a larger block only moves more dead positions
LIVE_BLOCK = _LANES
#: rows of queries and outputs a grid cell holds in VMEM (a divisor of S)
_LIVE_ROWS = 8


def _live_attention_rows(S: int) -> int:
    return max(d for d in range(1, min(S, _LIVE_ROWS) + 1) if S % d == 0)


def _live_attention_vmem_bytes(hd: int, S: int, Hkv: int, Hq: int,
                               itemsize: int) -> int:
    """One grid cell of ``live_block_attention``: two K and two V blocks,
    the query and output rows (double-buffered by the pipeline; ``hd``
    under a lane tile pads to one), the softmax state, and the float32
    scores and probabilities of one block."""
    Rp = max(_MIN_ROWS, Hq // Hkv)
    tile = 32 // itemsize                  # sublanes of a tile of this dtype
    return (4 * Hkv * hd * LIVE_BLOCK * itemsize
            + 4 * _live_attention_rows(S) * Hkv * -(-Rp // tile) * tile
            * _LANES * itemsize
            + 3 * Hkv * Rp * _LANES * 4
            + 3 * Hkv * Rp * LIVE_BLOCK * 4)


def _live_rows_vmem_bytes(Tmax: int, hd: int, S: int, Hkv: int, Hq: int,
                          itemsize: int) -> int:
    """The same for the sublane form: its blocks are ``_rows_block`` rows
    of whole lane tiles."""
    Rp, block = _query_rows(Hq // Hkv, itemsize), _rows_block(Tmax)
    return (4 * Hkv * block * hd * itemsize
            + 4 * _live_attention_rows(S) * Hkv * Rp * hd * itemsize
            + 3 * Hkv * Rp * _LANES * 4
            + 3 * Hkv * Rp * block * 4)


def supports_live_attention(Tq: int, Tmax: int, hd: int, *, S: int, Hkv: int,
                            Hq: int, dtype, ring: bool = False) -> bool:
    """``live_block_attention`` eligibility, ``supports_lane_append``'s
    twin: one query token a row, a float cache, whole groups of query
    heads, whole key blocks and a cell inside the VMEM budget, in one of
    the two layouts the runtime keeps a cache in: ``head_dim`` under a lane
    tile (positions on the lanes, so the kernel's view is a bitcast; no
    ring there), or one whole lane tile (positions on the sublanes, read
    as they lie; a ring by position). Whatever this refuses keeps
    ``decode_attention``."""
    dtype = jnp.dtype(dtype)
    if not (Tq == 1 and jnp.issubdtype(dtype, jnp.floating)
            and Hq % Hkv == 0):
        return False
    if hd == _LANES:
        return (_rows_block(Tmax) is not None
                and _live_rows_vmem_bytes(Tmax, hd, S, Hkv, Hq,
                                          dtype.itemsize) <= _VMEM_BUDGET)
    return (not ring and hd < _LANES and hd % 16 == 0
            and Tmax % LIVE_BLOCK == 0
            and _live_attention_vmem_bytes(hd, S, Hkv, Hq, dtype.itemsize)
            <= _VMEM_BUDGET)


def live_positions_read(kv_length, Tmax: int, hd: int, *, live=None,
                        window=None) -> int:
    """Key positions ``live_block_attention`` reads in one layer, its
    arguments as numpy arrays: the host's twin of the kernels' block
    arithmetic (the engine's ``kv_touched``). On the lanes every row reads
    its block-rounded length and a free one one block; on the sublanes
    ``live_block_span`` of the rows that decode."""
    import numpy as np

    if hd < _LANES:
        return int((-(-np.clip(kv_length, 1, Tmax) // LIVE_BLOCK)).sum()
                   ) * LIVE_BLOCK
    if live is not None:
        kv_length = np.where(live, kv_length, 0)
    block = _rows_block(Tmax)
    return int(live_block_span(kv_length, ring_len=Tmax, block=block,
                               window=window, xp=np)[1].sum()) * block
