"""Weights from ``--seed``: one jitted call, on the device, in the type asked.

The benchmark makes the weights, not the program and not the reference: the
program is handed them in the type it serves or trains in, the reference is
handed the same draws in float32 once the program's state is freed. The tree's
layout is the one the configuration's reference names, so a new family brings
its own with its reference file.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmark import spec

INIT_STD = 0.02


def make_params(config: Dict[str, Any], seed: int, dtype) -> Dict[str, Any]:
    """The parameter tree of a configuration file: the shapes its reference
    names (``param_shapes``), matrices drawn normal(0, 0.02), biases zero,
    norm scales one."""
    shapes = spec.load_module("reference", config["reference"]).param_shapes(
        config["model"])
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    paths = [jax.tree_util.keystr(p) for p, _ in flat]
    leaves = [shape for _, shape in flat]

    def build(key):
        out = []
        for i, (path, shape) in enumerate(zip(paths, leaves)):
            if path.endswith("['scale']"):
                out.append(jnp.ones(shape, dtype))
            elif len(shape) - (1 if "blocks" in path else 0) == 1:
                out.append(jnp.zeros(shape, dtype))      # biases
            else:
                out.append((jax.random.normal(jax.random.fold_in(key, i),
                                              shape, jnp.float32)
                            * INIT_STD).astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    # the seed rides as data (a uint32 pair), so one compiled program serves
    # every seed, and seeds past 2**31 stay distinct
    key = jax.random.wrap_key_data(jnp.asarray(
        [(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], jnp.uint32))
    return jax.jit(build)(key)
