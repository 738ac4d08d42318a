"""The held experts' gated products over rows sorted by expert (pallas, TPU).

``models/moe.py`` sorts a call's (row, held expert) assignments by expert and
gathers the rows into one buffer; ``grouped_gated_product`` then runs

    ys[r] = (silu(xs[r] @ Wg[e]) * (xs[r] @ Wu[e])) @ Wd[e]     e = r's group

as ONE kernel whose grid walks the (expert, row tile) pairs that hold a row:
an expert's three matrices are read once for each row tile its group
touches (one, at a prefill chunk's 13-32 rows an expert), an expert with no
row is never visited, and the grid ends at the last live row, so the static
worst case of the buffer costs nothing. As XLA ops this was a Python loop
over the held experts, each with its own sorts, gathers and four
conditionals (PERF.md section 6, PR 37).

Groups are not aligned to row tiles: a tile that holds the end of one group
and the start of the next is visited once for each, and each visit stores
its own rows only (the grouped matmul of ``jax.experimental.pallas.ops.tpu.
megablox``, whose visit order this follows). The expert's width F is walked
in tiles inside a visit: gate and up give a (rows, tile) piece of the hidden
activation, which the down tile folds into a float32 (rows, D) accumulator,
so no intermediate leaves VMEM. bf16 operands, float32 accumulation; the
SiLU and the product with ``up`` in float32 before the one cast.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from building_llm_from_scratch_tpu.ops.activations import silu
from building_llm_from_scratch_tpu.ops.decode_step import _LANES
from building_llm_from_scratch_tpu.parallel.collectives import mesh_kernel

#: rows of one visit. A chunk's 512 rows top-8 of which a sixteenth of the
#: experts is held make some 256 assignments: one tile or two, so few groups
#: lie across a tile's edge and read their weights twice; at 256 rows a
#: tile's product (3 x 2 x 256 x D x F) takes about as long as its weights
#: take to arrive (PERF.md section 6, PR 37: 128 reads 8% slower at F 4096)
ROW_TILE = 256
#: bytes the three weight tiles of one grid step may hold, each
#: double-buffered by the pipeline; the width tile is the largest whole part
#: of F, in lane tiles, that fits (PERF.md section 6, PR 37: what was tried)
_WEIGHT_TILES_BYTES = 24 * 2 ** 20
#: what a kernel may ask of a v5e's 128 MiB of VMEM, and what it asks for
#: over the buffers counted below (the compiler's own temporaries)
_VMEM_CEILING = 100 * 2 ** 20
_VMEM_MARGIN = 8 * 2 ** 20
#: (row, expert) pairs one call may hold: its two gathers are written out at
#: the buffer's static length (8 KB a pair at D 4096)
MAX_PAIRS = 32768


def _width_tile(D: int, F: int, itemsize: int):
    """Columns of gate / up (rows of down) one grid step holds, or None."""
    fits = [t for t in range(_LANES, F + 1, _LANES)
            if F % t == 0 and 6 * D * t * itemsize <= _WEIGHT_TILES_BYTES]
    return max(fits, default=None)


def _vmem_bytes(D: int, tf: int, itemsize: int) -> int:
    """What one grid step asks for: the weight tiles, the row tile in and
    out (all double-buffered), the float32 accumulator, the hidden piece with
    its two float32 sources, and the margin."""
    return (6 * D * tf * itemsize + 4 * ROW_TILE * D * itemsize
            + ROW_TILE * D * 4 + ROW_TILE * tf * (8 + itemsize)
            + _VMEM_MARGIN)


def supports_grouped_experts(n_pairs: int, D: int, F: int, dtype) -> bool:
    """``grouped_gated_product`` eligibility: float weights whose widths are
    whole lane tiles, a width tile inside the VMEM budget, and a buffer of
    at most ``MAX_PAIRS`` rows. Whatever this refuses keeps the loop over
    the held experts."""
    dtype = jnp.dtype(dtype)
    if not (jnp.issubdtype(dtype, jnp.floating) and D % _LANES == 0
            and F % _LANES == 0 and 0 < n_pairs <= MAX_PAIRS):
        return False
    tf = _width_tile(D, F, dtype.itemsize)
    return (tf is not None
            and _vmem_bytes(D, tf, dtype.itemsize) <= _VMEM_CEILING)


def buffer_rows(n_pairs: int) -> int:
    """Rows of the sorted buffer for ``n_pairs`` assignments: whole tiles."""
    return -(-n_pairs // ROW_TILE) * ROW_TILE


def _visits(group_sizes: jnp.ndarray, M: int):
    """The grid's walk over a buffer of M rows sorted by group: for each
    visit its group and its row tile, and how many visits there are. A group
    visits every tile that holds one of its rows, in order; a group with no
    row visits none. -> (offsets (H + 1,), group of a visit (V,), tile of a
    visit (V,), visits ()), V = M / tile + H - 1 the most there can be."""
    H = group_sizes.shape[0]
    tiles = M // ROW_TILE
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    n_tiles = jnp.where(group_sizes == 0, 0,
                        -(-ends // ROW_TILE) - starts // ROW_TILE)
    last = jnp.cumsum(n_tiles)                    # visits up to each group
    v = jnp.arange(tiles + H - 1, dtype=jnp.int32)
    group = jnp.minimum(jnp.sum(v[:, None] >= last[None, :], axis=1,
                                dtype=jnp.int32), H - 1)
    tile = starts[group] // ROW_TILE + v - (last - n_tiles)[group]
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return (offsets.astype(jnp.int32), group,
            jnp.clip(tile, 0, tiles - 1).astype(jnp.int32),
            last[-1].astype(jnp.int32))


def _grouped_kernel(offsets_ref, group_ref, tile_ref, layer_ref, x_ref,
                    gate_ref, up_ref, down_ref, o_ref, acc_ref, *,
                    n_width_tiles: int):
    """Grid cell (visit, width tile): fold one width tile of the visit's
    expert into the accumulator of the visit's row tile; after the last,
    store the rows that are the expert's own."""
    del layer_ref                                  # the index maps' alone
    v, f = pl.program_id(0), pl.program_id(1)

    @pl.when(f == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    g = jnp.dot(x, gate_ref[...], preferred_element_type=jnp.float32)
    u = jnp.dot(x, up_ref[...], preferred_element_type=jnp.float32)
    h = (silu(g) * u).astype(x.dtype)
    acc_ref[...] += jnp.dot(h, down_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(f == n_width_tiles - 1)
    def _store():
        e, tile = group_ref[v], tile_ref[v]
        row = tile * ROW_TILE + jax.lax.broadcasted_iota(
            jnp.int32, (ROW_TILE, 1), 0)
        own = (row >= offsets_ref[e]) & (row < offsets_ref[e + 1])
        out = acc_ref[...].astype(o_ref.dtype)
        # a tile is revisited only by consecutive visits, so it is still
        # here; what no group owns (past the last live row) reads zero
        seen = (v > 0) & (tile_ref[jnp.maximum(v - 1, 0)] == tile)

        @pl.when(seen)
        def _():
            o_ref[...] = jnp.where(own, out, o_ref[...])

        @pl.when(jnp.logical_not(seen))
        def _():
            o_ref[...] = jnp.where(own, out, jnp.zeros_like(out))


def grouped_gated_product(xs, gate, up, down, layer, group_sizes, *,
                          interpret=False):
    """``xs`` (M, D): rows sorted by group, M whole row tiles, group h's rows
    at ``[sum(group_sizes[:h]), sum(group_sizes[:h + 1]))``. ``gate`` / ``up``
    (L, H, D, F) and ``down`` (L, H, F, D): the stacked experts, read at
    ``layer`` (an int32 scalar, traced or not) inside the index maps, so no
    slice of them is ever made. ``group_sizes`` (H,) int32. -> (M, D) in xs's
    type: row r's gated product with its group's expert; rows past the last
    group's end are zero in a tile some group reached and UNWRITTEN in the
    tiles after it (the caller gathers live rows only).

    Cost follows the rows: the grid has one step for each (group, row tile)
    pair that holds a row and width tile, and ends there.

    Under a mesh every operand is whole on every shard; ``interpret=True``
    runs on CPU for parity tests."""
    whole = lambda a: (None,) * a.ndim
    scalars = (jnp.asarray(layer, jnp.int32).reshape(1),
               group_sizes.astype(jnp.int32))
    args = (xs, gate, up, down) + scalars
    return mesh_kernel(
        lambda _, *a: _grouped_local(*a, interpret=interpret),
        args, tuple(whole(a) for a in args), whole(xs))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _grouped_local(xs, gate, up, down, layer, group_sizes, *, interpret):
    # jitted for the reason ``_lane_append_local`` is: one lowering a
    # program, the layer a value and not a constant of the text
    M, D = xs.shape
    F = gate.shape[-1]
    itemsize = jnp.dtype(gate.dtype).itemsize
    tf = _width_tile(D, F, itemsize)
    offsets, group, tile, n_visits = _visits(group_sizes, M)
    rows = pl.BlockSpec((ROW_TILE, D), lambda v, f, o, g, t, l: (t[v], 0))
    wide = pl.BlockSpec((None, None, D, tf),
                        lambda v, f, o, g, t, l: (l[0], g[v], 0, f))
    tall = pl.BlockSpec((None, None, tf, D),
                        lambda v, f, o, g, t, l: (l[0], g[v], f, 0))
    return pl.pallas_call(
        functools.partial(_grouped_kernel, n_width_tiles=F // tf),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n_visits, F // tf),
            in_specs=[rows, wide, wide, tall],
            out_specs=rows,
            scratch_shapes=[pltpu.VMEM((ROW_TILE, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((M, D), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_bytes(D, tf, itemsize)),
        name="grouped_experts",
        interpret=interpret,
    )(offsets, group, tile, layer, xs.astype(gate.dtype), gate, up, down)
