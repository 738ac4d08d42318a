"""A prefill chunk's attention over its slot's cached keys (pallas, TPU).

A chunk of C prompt tokens is written into row ``slot`` of a layer's
(S, Hkv, Tmax, hd) buffer and then attends over THAT row, itself included
(``models/transformer.py::_ChunkKV``). As XLA ops (``decode_attention``)
that is the row sliced out, float32 scores of every query against every
position of the buffer written to HBM, masked, soft-maxed, cast and read
back, live positions or not: nine tenths of the rag cell's chunk program
(PERF.md section 5). ``chunk_live_attention`` is the same arithmetic as one
kernel: online softmax over the key blocks that hold live positions, no
score ever in HBM, no block past the prompt copied or computed.

It works on the cache's own device layout for ``head_dim`` 128: the runtime
keeps (S, Hkv, Tmax, hd) logical there (positions on the sublanes, ``hd`` on
the lanes), so a key block is a plain (block, 128) tile and the row is
reached by ``slot`` in the index maps. (Under 128 the runtime keeps
positions on the lanes: ``ops/decode_step.py``'s kernels; a chunk there
keeps ``decode_attention``.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from building_llm_from_scratch_tpu.ops.decode_step import (
    _LANES,
    _MASKED,
    _NEG_BIG,
    _VMEM_BUDGET,
    _ring_age,
)
from building_llm_from_scratch_tpu.parallel.collectives import mesh_kernel
from building_llm_from_scratch_tpu.parallel.mesh import MODEL_AXIS

#: key positions a grid step folds, largest first: the block has to divide
#: the chunk and the buffer (PERF.md section 7, PR 34: what was tried)
_KEY_BLOCKS = (512, 256, 128)
#: query rows (query heads of one group x chunk positions) a grid cell holds,
#: and of them the rows one pass of the kernel's body scores against a key
#: block. The passes are written out, and each is lowered in Python in every
#: process that builds a chunk program: 2048 rows in passes of 256 are 10%
#: faster on the chip and cost a warm start 2.4 s (PERF.md section 7, PR 34)
_QUERY_ROWS = 1024
_ROW_TILE = 512


def _key_block(C: int, Tmax: int):
    """The key block of a C-token chunk over a buffer of Tmax positions."""
    return next((b for b in _KEY_BLOCKS if C % b == 0 and Tmax % b == 0),
                None)


def _query_heads(C: int, G: int) -> int:
    """Query heads of a group that ride one grid cell."""
    return max(g for g in range(1, G + 1)
               if G % g == 0 and (g == 1 or g * C <= _QUERY_ROWS))


def _row_tile(C: int) -> int:
    """Query rows one pass scores: a whole part of one head's chunk, so a
    pass's rows are consecutive positions."""
    return _ROW_TILE if C % _ROW_TILE == 0 else C


def _vmem_bytes(C: int, Tmax: int, hd: int, G: int, itemsize: int) -> int:
    """One grid cell: query and output rows and a K and a V block, each
    double-buffered by the pipeline; the softmax state (a row's scalars
    replicated over a lane tile); one pass's float32 scores and
    probabilities and the probabilities cast for the value product."""
    rows, block = _query_heads(C, G) * C, _key_block(C, Tmax)
    return (4 * rows * hd * itemsize + 4 * block * hd * itemsize
            + 2 * rows * _LANES * 4 + rows * hd * 4
            + _row_tile(C) * block * (8 + itemsize))


def supports_chunk_attention(C: int, Tmax: int, hd: int, *, Hkv: int,
                             Hq: int, dtype) -> bool:
    """``chunk_live_attention`` eligibility: a float cache whose ``head_dim``
    is one whole lane tile (the layout above), chunk and buffer whole key
    blocks, whole groups of query heads, and a cell inside the VMEM budget.
    Whatever this refuses keeps ``decode_attention``."""
    dtype = jnp.dtype(dtype)
    return (jnp.issubdtype(dtype, jnp.floating) and hd == _LANES
            and _key_block(C, Tmax) is not None and Hq % Hkv == 0
            and _vmem_bytes(C, Tmax, hd, Hq // Hkv, dtype.itemsize)
            <= _VMEM_BUDGET)


def chunk_positions_read(lo: int, hi: int, C: int, Tmax: int) -> int:
    """Key positions the kernel reads in one layer for the chunk [lo, lo + C)
    of a prompt whose live part ends at ``hi``: what the chunk has written
    so far (a ring: at most its length) less the chunk's own blocks past
    ``hi``, blocks rounded up. The host's twin of ``_first_position`` (the
    engine's ``chunk_kv_touched``)."""
    block = _key_block(C, Tmax)
    return min(lo + C, Tmax) - (lo + C - -(-hi // block) * block)


def _first_position(j, last, *, block: int, ring_len):
    """The absolute position index ``j * block`` of the buffer holds once
    position ``last`` (the chunk's last) is written. ``ring_len`` None: the
    identity. A ring (``ring_positions``' arithmetic): the newest position
    congruent to the index; an index the ring has not reached reads as its
    own number, which is past ``last`` and so past every live key. Chunks
    start at whole chunks and a block divides the chunk and the ring, so a
    block's positions are consecutive from this one."""
    at = j * block
    if ring_len is None:
        return at
    pos = last - _ring_age(jax.lax.rem(last, ring_len), at, ring_len)
    return jnp.where(pos < 0, at, pos)


def _block_index(j, at_ref, *, C: int, block: int, ring_len):
    """Where grid step ``j`` reads: its own block while that holds a live
    key, else the block of the last live key (``kv_len - 1``), which is the
    step before's: the pipeline copies a block only when the index changes,
    so a dead block costs no copy."""
    chunk_start, kv_len = at_ref[1], at_ref[2]
    first = _first_position(j, chunk_start + C - 1, block=block,
                            ring_len=ring_len)
    newest = jnp.maximum(kv_len - 1, 0)
    if ring_len is not None:
        newest = jax.lax.rem(newest, ring_len)
    return jnp.where(first < kv_len, j, newest // block)


def _chunk_kernel(at_ref, q_ref, k_ref, v_ref, o_ref, m_ref, d_ref, acc_ref,
                  *, scale: float, C: int, block: int, ring_len, window):
    """Grid cell (key-value head, group of query heads, key block): fold
    one key block into the running max / denominator / accumulator of the
    cell's query rows (``_paged_kernel``'s state, a (rows, block) product
    each way on the MXU). Masks are by absolute position and only where a
    block needs one: a block wholly before the chunk and inside every
    query's window is folded bare."""
    j = pl.program_id(2)
    chunk_start, kv_len = at_ref[1], at_ref[2]
    last = chunk_start + C - 1
    first = _first_position(j, last, block=block, ring_len=ring_len)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_BIG)
        d_ref[...] = jnp.zeros_like(d_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def fold(masked: bool):
        k, v = k_ref[...], v_ref[...]
        tile = _row_tile(C)
        if masked:
            q_pos = chunk_start + jax.lax.broadcasted_iota(
                jnp.int32, (C, block), 0)
            k_pos = first + jax.lax.broadcasted_iota(
                jnp.int32, (C, block), 1)
            seen = (q_pos >= k_pos) & (k_pos < kv_len)
            if window is not None:
                seen &= q_pos - k_pos < window
            # a masked probability is exactly 0, but 0 * NaN is not
            v = jnp.where(first + jax.lax.broadcasted_iota(
                jnp.int32, (block, 1), 0) < kv_len, v,
                jnp.zeros((), v.dtype))
        for r in range(0, q_ref.shape[0], tile):
            at = pl.ds(r, tile)
            sc = jax.lax.dot_general(
                q_ref[at, :], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if masked:
                # the cell's query heads share the chunk's positions
                sc = jnp.where(seen[r % C:r % C + tile], sc, _MASKED)
            # the running max and denominator are kept replicated over a
            # lane tile (``hd`` is one, so the accumulator takes them as
            # they are): one broadcast a reduction, none a use
            m_prev = m_ref[at, :]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(sc - pltpu.repeat(m_new, block // _LANES, 1))
            m_ref[at, :] = m_new
            d_ref[at, :] = d_ref[at, :] * alpha + jnp.sum(p, axis=-1,
                                                         keepdims=True)
            # probabilities in the cache's dtype before the value product,
            # as ``decode_attention`` casts them
            acc_ref[at, :] = acc_ref[at, :] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    live = first < kv_len
    bare = first + block <= chunk_start
    if window is not None:
        bare &= last - first < window
    pl.when(live & bare)(functools.partial(fold, False))
    pl.when(live & jnp.logical_not(bare))(functools.partial(fold, True))

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        d = d_ref[...]
        # a query with no live key (none today: a pad query sees the
        # prompt's last key) reads 0, not 0 / 0
        o_ref[...] = (acc_ref[...] / jnp.where(d == 0.0, 1.0, d)
                      ).astype(o_ref.dtype)


def chunk_live_attention(q, k_cache, v_cache, slot, chunk_start, kv_len, *,
                         window=None, interpret=False):
    """``decode_attention`` for one chunk against ONE row of a layer's
    buffers: ``q`` (1, C, Hq, hd) at positions ``chunk_start + arange(C)``,
    ``k_cache`` / ``v_cache`` (S, Hkv, Tmax, hd) with the chunk already
    written into row ``slot``; a query attends the positions up to its own
    that lie under ``kv_len`` (the prompt's end inside the chunk: pad keys
    are never attended, pad queries compute garbage in their own rows).
    -> (1, C, Hq, hd).

    ``window`` (a 'sliding' layer): a query at p attends (p - window, p],
    and the buffer is a ring (position p at index p mod Tmax) that the
    chunk, starting at a whole chunk, was written into before this read;
    indices it has not reached read as never written whatever a longer
    request left there.

    Key blocks that hold no live position (past ``kv_len``, or never
    written) are neither copied nor computed. The ``Hq // Hkv`` query heads
    of a group ride one product as rows; key-value heads are never
    repeated. bf16 operands, float32 accumulation and softmax state,
    probabilities cast to the cache's dtype before the value product: the
    materialised form's precision.

    Under a mesh heads shard over the model axis like the slot cache;
    ``interpret=True`` runs on CPU for parity tests."""
    heads = (None, None, MODEL_AXIS, None)
    panes = (None, MODEL_AXIS, None, None)
    at = jnp.stack([jnp.asarray(x, jnp.int32).reshape(())
                    for x in (slot, chunk_start, kv_len)])
    return mesh_kernel(
        lambda _, *a: _chunk_attention_local(*a, window=window,
                                             interpret=interpret),
        (q, k_cache, v_cache, at), (heads, panes, panes, (None,)), heads)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def _chunk_attention_local(q, k_cache, v_cache, at, *, window, interpret):
    # jitted for the reason ``_lane_append_local`` is: one lowering a program
    _, C, Hq, hd = q.shape
    _, Hkv, Tmax, _ = k_cache.shape
    G = Hq // Hkv
    block, gq = _key_block(C, Tmax), _query_heads(C, G)
    rows = gq * C
    ring_len = None if window is None else Tmax
    # (Hkv, G * C, hd): a group's query heads as rows of one product
    qr = q.reshape(C, Hkv, G, hd).transpose(1, 2, 0, 3).reshape(
        Hkv, G * C, hd).astype(k_cache.dtype)
    some_rows = pl.BlockSpec((None, rows, hd),
                             lambda h, i, j, at_ref: (h, i, 0))
    key_block = pl.BlockSpec(
        (None, None, block, hd),
        lambda h, i, j, at_ref: (at_ref[0], h, _block_index(
            j, at_ref, C=C, block=block, ring_len=ring_len), 0))
    out = pl.pallas_call(
        functools.partial(_chunk_kernel, scale=1.0 / float(hd) ** 0.5, C=C,
                          block=block, ring_len=ring_len, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(Hkv, G // gq, Tmax // block),
            in_specs=[some_rows, key_block, key_block],
            out_specs=some_rows,
            scratch_shapes=[
                pltpu.VMEM((rows, _LANES), jnp.float32),  # running max
                pltpu.VMEM((rows, _LANES), jnp.float32),  # denominator
                pltpu.VMEM((rows, hd), jnp.float32),      # accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((Hkv, G * C, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="chunk_live_attention",
        interpret=interpret,
    )(at, qr, k_cache, v_cache)
    return out.reshape(Hkv, G, C, hd).transpose(2, 0, 1, 3).reshape(
        1, C, Hq, hd)
