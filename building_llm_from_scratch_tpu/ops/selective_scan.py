"""The selective state space of an 'ssm' layer (``cfg.layer_kinds``; Mamba-1):
a diagonal recurrence a channel and state, with an input-dependent step, B
and C, in three forms that agree.

One row keeps a state ``s`` (N, I), float32, zero before a sequence: N states
for each of I channels, the channels LAST (on a TPU the last axis lies on the
128 lanes: (I, N) with N = 16 would be stored eight times its size). For the
token at ``t``, with the channel's input ``u_t`` (I,), its step ``delta_t``
(I,) > 0, the token's ``B_t`` and ``C_t`` (N,), the decay rates ``A`` (N, I)
< 0 and the skip ``D`` (I,):

    s_t = exp(delta_t * A) * s_(t-1) + B_t (x) (delta_t * u_t)
    y_t = C_t . s_t + D * u_t

``selective_step`` is that, literally, for one token a row: what a decode
tick runs. ``selective_scan`` is the step under a ``lax.scan`` over T tokens:
the plain form, what the CPU runs and what the kernel's gate falls back to.
``selective_scan_kernel`` (pallas, TPU) runs a span with the state of 1024
channels held in sixteen vector registers across all T steps: a grid step
takes one row and 1024 channels laid out as one (8, 128) tile a state index,
reads ``B_t[n]`` and ``C_t[n]`` as scalars (SMEM), and does one exponential
and six multiply-adds an element and step, with no reduction across lanes.
No matrix product anywhere: XLA's nearest forms are an associative scan that
writes (T, N, I) float32 tensors out some ten times, or T dependent steps of
a few microseconds each (PERF.md section 6, PR 39).

A token with ``delta = 0`` leaves the state as it was (``exp(0) = 1``,
``delta * u = 0``): padding and rows that do not decode are masked there,
by the caller. Everything is float32.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SUBLANES, _LANES = 8, 128
#: channels of one grid step: one float32 register tile a state index
GROUP = _SUBLANES * _LANES
#: B and C of one row ride in scalar memory, T x N words each
_MAX_SCALARS = 16384
#: what one grid step holds, double-buffered: delta, u and y, T x GROUP
#: float32 each
_VMEM_LIMIT = 64 * 2 ** 20


def supports_selective_scan_kernel(T: int, inner: int, n_state: int) -> bool:
    """``selective_scan_kernel`` eligibility: whole groups of 1024 channels,
    at most 32 states (their tiles and A's fill the register file past
    that), and a span whose B and C fit scalar memory and whose three
    (T, 1024) float32 blocks fit VMEM twice."""
    return (inner % GROUP == 0 and 1 <= n_state <= 32
            and 1 < T and T * n_state <= _MAX_SCALARS
            and 6 * T * GROUP * 4 <= _VMEM_LIMIT // 2)


def selective_scan_path(T: int, inner: int, n_state: int, *,
                        backend: Optional[str] = None) -> str:
    """THE rule for the form a program's 'ssm' layers run, made once, at
    trace time, on what the code can observe: ``"step"`` for one token a row
    (a decode tick); for a span (a prefill chunk, a prompt, a training
    sequence) ``"kernel"`` on a TPU at shapes the kernel takes, else
    ``"scan"``. The engine reports the names (``stats()["selective_scan"]``);
    tests force the kernel (interpret mode) with ``backend="tpu"``."""
    if T == 1:
        return "step"
    if ((backend or jax.default_backend()) == "tpu"
            and supports_selective_scan_kernel(T, inner, n_state)):
        return "kernel"
    return "scan"


def selective_step(u, delta, A, Bm, Cm, D, state):
    """One token a row. u, delta (..., I), Bm, Cm (..., N), A (N, I), D (I,),
    state (..., N, I), all float32 -> (y (..., I), the new state)."""
    state = (jnp.exp(delta[..., None, :] * A) * state
             + Bm[..., :, None] * (delta * u)[..., None, :])
    return jnp.sum(state * Cm[..., :, None], axis=-2) + D * u, state


def selective_scan(u, delta, A, Bm, Cm, D, state):
    """``selective_step`` over T tokens: u, delta (B, T, I), Bm, Cm (B, T,
    N), state (B, N, I) -> (y (B, T, I), the state after the last)."""
    def one(state, xs):
        y, state = selective_step(*xs[:2], A, *xs[2:], D, state)
        return state, y

    time_first = lambda a: jnp.moveaxis(a, 1, 0)
    state, y = jax.lax.scan(one, state,
                            tuple(map(time_first, (u, delta, Bm, Cm))))
    return jnp.moveaxis(y, 0, 1), state


def _scan_kernel(b_ref, c_ref, u_ref, dt_ref, a_ref, d_ref, s_ref,
                 y_ref, s_out_ref, *, T: int, N: int):
    """One row, one group of channels, all T steps. b_ref, c_ref (1, T * N)
    SMEM; u_ref, dt_ref, y_ref (1, T, 1, 8, 128); a_ref, s_ref, s_out_ref
    (.., N, 1, 8, 128); d_ref (1, 8, 128)."""
    A = [a_ref[n, 0] for n in range(N)]
    D = d_ref[0]

    def step(t, s):
        u, dt = u_ref[0, t, 0], dt_ref[0, t, 0]
        du = dt * u
        y = D * u
        out = []
        for n in range(N):
            s_n = jnp.exp(dt * A[n]) * s[n] + b_ref[0, t * N + n] * du
            y = y + c_ref[0, t * N + n] * s_n
            out.append(s_n)
        y_ref[0, t, 0] = y
        return tuple(out)

    s = jax.lax.fori_loop(0, T, step,
                          tuple(s_ref[0, n, 0] for n in range(N)))
    for n in range(N):
        s_out_ref[0, n, 0] = s[n]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _kernel_local(u, delta, A, Bm, Cm, D, state, interpret: bool = False):
    B, T, I = u.shape
    N, G = A.shape[0], I // GROUP
    tile = (_SUBLANES, _LANES)
    # channels (I,) -> (G, 8, 128): a group's channels fill one register tile
    span = lambda a: a.reshape((B, T, G) + tile)
    # a row's B and C as one (1, T * N) block of scalar memory (a block's
    # last two sizes have to be the array's own there)
    flat = lambda a: a.reshape(B, 1, T * N)
    time_block = pl.BlockSpec((1, T, 1) + tile, lambda b, g: (b, 0, g, 0, 0))
    state_block = pl.BlockSpec((1, N, 1) + tile, lambda b, g: (b, 0, g, 0, 0))
    scalars = pl.BlockSpec((None, 1, T * N), lambda b, g: (b, 0, 0),
                           memory_space=pltpu.SMEM)
    y, state = pl.pallas_call(
        functools.partial(_scan_kernel, T=T, N=N),
        grid=(B, G),
        in_specs=[scalars, scalars, time_block, time_block,
                  pl.BlockSpec((N, 1) + tile, lambda b, g: (0, g, 0, 0)),
                  pl.BlockSpec((1,) + tile, lambda b, g: (g, 0, 0)),
                  state_block],
        out_specs=[time_block, state_block],
        out_shape=[jax.ShapeDtypeStruct((B, T, G) + tile, jnp.float32),
                   jax.ShapeDtypeStruct((B, N, G) + tile, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="selective_scan",
        interpret=interpret,
    )(flat(Bm), flat(Cm), span(u), span(delta), A.reshape((N, G) + tile),
      D.reshape((G,) + tile), state.reshape((B, N, G) + tile))
    return y.reshape(B, T, I), state.reshape(B, N, I)


def selective_scan_kernel(u, delta, A, Bm, Cm, D, state, *,
                          interpret: bool = False
                          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``selective_scan`` as one pallas call (the same arguments and
    results); shapes as ``supports_selective_scan_kernel`` says."""
    # here, not at the top: ``parallel`` imports the model, which imports this
    from building_llm_from_scratch_tpu.parallel.collectives import mesh_kernel

    args = tuple(a.astype(jnp.float32)
                 for a in (u, delta, A, Bm, Cm, D, state))
    whole = lambda a: (None,) * a.ndim
    # under a mesh every operand is whole on every shard (ROADMAP R4: a
    # split over channels)
    return mesh_kernel(
        lambda _, *a: _kernel_local(*a, interpret=interpret),
        args, tuple(whole(a) for a in args), (whole(u), whole(state)))


# -- the tick's step over the rows that decode --------------------------------

#: a row's state, u and delta each have this many VMEM buffers: while one row
#: is stepped the two after it are on their way in and the one before it is
#: on its way out (a row is 327,680 B each way, 0.8 us of bandwidth, and is
#: stepped in some 440 cycles: a walk that waited for each row would be
#: bound by the copies' latency). The kernel alone on a v5e (PERF.md section
#: 6, PR 45; ms a call with 0 / 1 / 72 / 192 of 192 rows named, one program
#: stepping 26 buffers of (192, 16, 5120) in turn, some 0.075 of each call
#: being that program's own relayouts of u and delta): 0.078 / 0.081 / 0.096
#: / 0.221, where the step over every row and its select take 0.216 at any
#: count; in the widechat tick 0.031 a call at 62 decoding rows
_STEP_BUFFERS = 4


def supports_step_rows(inner: int, n_state: int) -> bool:
    """``selective_step_rows`` eligibility: whole groups of 1024 channels and
    whole sublane tiles of at most 32 states (the state is walked as it lies
    in the cache: eight states by 128 channels a register)."""
    return (inner % GROUP == 0 and n_state % _SUBLANES == 0
            and 1 <= n_state <= 32)


def live_rows_table(live: jnp.ndarray) -> jnp.ndarray:
    """``live`` (S,) bool -> (S + 1,) int32: the rows that decode, in order,
    in front (what follows them is S), and their count last. Made once a
    tick; every layer's walk reads it from scalar memory."""
    S = live.shape[0]
    rows = jnp.sort(jnp.where(live, jnp.arange(S, dtype=jnp.int32), S))
    return jnp.append(rows, jnp.sum(live, dtype=jnp.int32))


def state_rows_walked(n_decoding: int, n_slots: int, walked_layers: int,
                      other_layers: int) -> int:
    """The host's twin of the walk (the engine's ``state_rows_touched``):
    the states a tick's steps read and write. A layer on the walk's path
    (``selective_step_rows``, ``recurrent_step_rows``: the table names the
    decoding rows and nothing else is copied) touches its decoding rows',
    any other layer every slot's."""
    return n_decoding * walked_layers + n_slots * other_layers


def _step_rows_kernel(rows_ref, b_ref, c_ref, u_hbm, dt_hbm, a_ref, d_ref,
                      s_hbm, y_ref, s_out_hbm, sbuf, ubuf, dtbuf, sem,
                      *, N: int, G: int):
    """``selective_step`` for the rows ``rows_ref`` names ((S + 1,), the
    count last), in place in ``s_hbm`` / ``s_out_hbm`` (one buffer, (S, N,
    I)). b_ref, c_ref (S * N,) scalar memory; u_hbm, dt_hbm (S, 1, I) left in
    HBM (a row of them is copied as it lies; as (S, I) it would be one
    sublane of each tile, which no copy may slice); a_ref (N, I), d_ref (1,
    I) and the output y_ref (S, I) whole in VMEM. A row's state comes in as
    it lies (registers of eight states by 128 channels), is stepped where it
    landed and goes back from there, u and delta beside it; its y is stored
    into its row of y_ref, which starts as zeros and leaves as one block in
    the layout its consumer reads. No state but a named row's is read or
    written."""
    S = s_hbm.shape[0]
    n = rows_ref[S]
    y_ref[...] = jnp.zeros_like(y_ref)

    def copies_in(j, k):
        r = rows_ref[j]
        return [pltpu.make_async_copy(s_hbm.at[r], sbuf.at[k], sem.at[0, k]),
                pltpu.make_async_copy(u_hbm.at[r], ubuf.at[k], sem.at[1, k]),
                pltpu.make_async_copy(dt_hbm.at[r], dtbuf.at[k],
                                      sem.at[2, k])]

    def copies_out(j, k):
        return [pltpu.make_async_copy(sbuf.at[k], s_out_hbm.at[rows_ref[j]],
                                      sem.at[3, k])]

    def start(copies):
        for copy in copies:
            copy.start()

    def wait(copies):
        for copy in copies:
            copy.wait()

    def step(j, k):
        r = rows_ref[j]
        states = jax.lax.broadcasted_iota(jnp.int32, (N, 1), 0)

        def column(n, cols):    # the row's N scalars down the sublanes
            return tuple(jnp.where(states == n, ref[r * N + n], col)
                         for ref, col in zip((b_ref, c_ref), cols))

        zeros = jnp.zeros((N, 1), jnp.float32)
        Bc, Cc = jax.lax.fori_loop(0, N, column, (zeros, zeros))

        def group(g, carry):
            # a group's 1024 channels at once, as arrays: every read of the
            # buffers before any write (a write would hold back the reads
            # behind it), and a few operations to lower
            at = pl.ds(pl.multiple_of(g * GROUP, GROUP), GROUP)
            u, dt = ubuf[k, :, at], dtbuf[k, :, at]
            s = (jnp.exp(dt * a_ref[:, at]) * sbuf[k, :, at]
                 + Bc * (dt * u))
            sbuf[k, :, at] = s
            y_ref[pl.ds(r, 1), at] = (jnp.sum(s * Cc, axis=0, keepdims=True)
                                      + d_ref[:, at] * u)
            return carry

        jax.lax.fori_loop(0, G, group, 0)

    for j in range(_STEP_BUFFERS - 2):
        pl.when(j < n)(lambda j=j: start(copies_in(j, j)))

    def one_row(j, carry):
        k = j % _STEP_BUFFERS
        ahead = j + _STEP_BUFFERS - 2
        k_ahead = ahead % _STEP_BUFFERS
        # the buffer the row two ahead lands in is the one the row two
        # behind left from
        pl.when(j >= 2)(lambda: wait(copies_out(j - 2, k_ahead)))
        pl.when(ahead < n)(lambda: start(copies_in(ahead, k_ahead)))
        wait(copies_in(j, k))
        step(j, k)
        start(copies_out(j, k))
        return carry

    jax.lax.fori_loop(0, n, one_row, 0)
    for back in (2, 1):
        pl.when(n >= back)(lambda back=back: wait(
            copies_out(n - back, (n - back) % _STEP_BUFFERS)))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step_rows_local(table, u, delta, A, Bm, Cm, D, state,
                     interpret: bool = False):
    # jitted so that a program's layers of one shape lower ONE body
    S, N, I = state.shape
    # a row of u or delta as its own (1, I) array: see the kernel
    rows = lambda a: a.reshape(S, 1, I)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    f32 = jnp.float32
    small = pltpu.VMEM((_STEP_BUFFERS, 1, I), f32)
    return pl.pallas_call(
        functools.partial(_step_rows_kernel, N=N, G=I // GROUP),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[in_hbm, in_hbm, in_vmem, in_vmem, in_hbm],
            out_specs=[in_vmem, in_hbm],
            scratch_shapes=[
                pltpu.VMEM((_STEP_BUFFERS, N, I), f32), small, small,
                pltpu.SemaphoreType.DMA((4, _STEP_BUFFERS)),
            ],
        ),
        out_shape=(jax.ShapeDtypeStruct((S, I), f32),
                   jax.ShapeDtypeStruct((S, N, I), f32)),
        # operand 7 (the three tables count): the state, in place
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        name="selective_step_rows",
        interpret=interpret,
    )(table, Bm.reshape(S * N), Cm.reshape(S * N), rows(u), rows(delta), A,
      D.reshape(1, I), state)


def selective_step_rows(u, delta, A, Bm, Cm, D, state, table, *,
                        interpret: bool = False
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``selective_step`` for the rows ``table`` names alone
    (``live_rows_table``), in place: u, delta (S, I), Bm, Cm (S, N), state
    (S, N, I) -> (y (S, I), the state). The state of a row that is not named
    is neither read nor written: it keeps it bit for bit, and its ``y``
    reads 0. Shapes as ``supports_step_rows`` says."""
    from building_llm_from_scratch_tpu.parallel.collectives import mesh_kernel

    f32 = lambda a: a.astype(jnp.float32)
    args = (table,) + tuple(map(f32, (u, delta, A, Bm, Cm, D, state)))
    whole = lambda a: (None,) * a.ndim
    return mesh_kernel(
        lambda _, *a: _step_rows_local(*a, interpret=interpret),
        args, tuple(whole(a) for a in args), (whole(u), whole(state)))
