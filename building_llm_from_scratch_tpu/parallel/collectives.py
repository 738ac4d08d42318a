"""Collective / multi-host helpers.

Maps the reference's explicit torch.distributed calls to their TPU-native
equivalents (SURVEY.md §2.2 "Communication backend"):

  torch.distributed.barrier()        -> sync_global_devices()
  rank == 0 gating                   -> is_coordinator()
  dist.all_reduce (DDP grads)        -> implicit: GSPMD psum under jit
  FSDP all-gather / reduce-scatter   -> implicit: GSPMD from sharding specs
  FSDP FULL_STATE_DICT gather        -> gather_full(tree)

Explicit collectives (psum/all_gather/ppermute) are provided for
``shard_map`` kernels (ring attention) that hand-schedule communication;
``trace_under_mesh`` + ``mesh_kernel`` put pallas kernels, which GSPMD
cannot partition, under a shard_map of the step's mesh.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec


def is_coordinator() -> bool:
    """Process-0 check (the reference's ``rank == 0`` pattern)."""
    return jax.process_index() == 0


def sync_global_devices(name: str = "barrier") -> None:
    """Cross-host barrier (reference dist.barrier, main.py:178 etc.)."""
    if jax.process_count() == 1:
        return
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices(name)


def gather_full(tree: Any) -> Any:
    """Gather a (possibly sharded) pytree to full host values — the analog
    of FSDP's FULL_STATE_DICT rank-0 gather (reference train.py:244-249).

    Single-process: device_get reassembles local shards. Multi-process:
    arrays span non-addressable devices, so each leaf goes through a
    process_allgather collective first (every host ends with the full
    value, matching the reference's CPU-offload gather)."""
    import numpy as np

    multi = jax.process_count() > 1

    def gather(x):
        if not isinstance(x, jax.Array):
            return x
        if multi and not x.is_fully_addressable:
            from jax.experimental import multihost_utils

            return np.asarray(
                multihost_utils.process_allgather(x, tiled=True))
        return np.asarray(jax.device_get(x))

    return jax.tree_util.tree_map(gather, tree)


# shard_map building blocks -------------------------------------------------

def trace_under_mesh(fn, mesh):
    """Make ``mesh`` the ambient (abstract) mesh while ``fn`` is traced.

    GSPMD cannot partition a Mosaic kernel ("wrap the call in a
    shard_map"), and a jitted step only learns its mesh from its
    operands' shardings, which tracing does not show. Step builders that
    know their plan's mesh wrap the function they jit with this, so
    ``mesh_kernel`` below can read the mesh where the kernel is called.
    No mesh, or one device: ``fn`` unchanged."""
    if mesh is None or mesh.size == 1:
        return fn

    @functools.wraps(fn)
    def traced(*args, **kwargs):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return fn(*args, **kwargs)

    return traced


def mesh_kernel(fn, args, in_axes, out_axes):
    """Call ``fn(shard, *args)`` — a pallas kernel — once per shard of
    the ambient mesh (``trace_under_mesh``).

    ``in_axes`` / ``out_axes`` name, per operand / result and per array
    dim, the mesh axis that may shard that dim (None: never). The call
    is manual over every auto-partitioned axis (Mosaic refuses one left
    auto, even of size one); an axis larger than one shards the dims
    tagged with it where it divides them all, and operands stay whole
    over the others (GSPMD gathers them there — correct, and visible in
    the HLO). ``shard`` is the linear index of this shard over the mapped
    axes (int32 0 without a mesh) for kernels that seed a PRNG per shard.
    Inside an enclosing shard_map every axis is already manual, so the
    kernel is called bare."""
    mesh = jax.sharding.get_abstract_mesh()
    big = [] if mesh.empty else [a for a in mesh.auto_axes
                                 if mesh.shape[a] > 1]
    if not big:
        return fn(jnp.int32(0), *args)
    live = []
    for name in big:
        dims = [x.shape[d] for x, axes in zip(args, in_axes)
                for d, a in enumerate(axes) if a == name]
        if dims and all(d % mesh.shape[name] == 0 for d in dims):
            live.append(name)

    def spec(axes):
        return PartitionSpec(*(a if a in live else None for a in axes))

    def body(*shard_args):
        shard = jnp.int32(0)
        for name in live:
            shard = shard * mesh.shape[name] + jax.lax.axis_index(name)
        return fn(shard, *shard_args)

    return jax.shard_map(
        body, mesh=mesh, in_specs=tuple(spec(a) for a in in_axes),
        out_specs=jax.tree_util.tree_map(
            spec, out_axes, is_leaf=lambda a: isinstance(a, tuple) and all(
                e is None or isinstance(e, str) for e in a)),
        axis_names=frozenset(mesh.auto_axes), check_vma=False)(*args)


def psum(x, axis_name: str):
    return jax.lax.psum(x, axis_name)


def all_gather(x, axis_name: str, axis: int = 0, tiled: bool = True):
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def ppermute_next(x, axis_name: str, axis_size: int):
    """Rotate shards one step around the ring (ring attention's primitive)."""
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    return jax.lax.ppermute(x, axis_name, perm)


def axis_index(axis_name: str):
    return jax.lax.axis_index(axis_name)
