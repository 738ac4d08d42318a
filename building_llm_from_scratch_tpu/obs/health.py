"""In-graph per-layer-group training-health metrics.

The global pre-clip ``grad_norm`` in the step metrics says *that* something
went wrong, never *where*: a loss spike caused by one block's exploding
gradients, a clipped update silently capping progress, or a single layer
going non-finite all look identical from one scalar. This module computes
the localized view INSIDE the jitted train step — per-layer-group gradient
norms, parameter norms, update norms (post-clip: ``optax.clip_by_global_norm``
sits first in the optimizer chain, so the update already reflects it),
update-to-param ratios, and first-non-finite-group localization — as
compact ``(n_groups,)`` arrays in the metrics pytree. The host only ever
*appends* the device arrays and fetches them at the logging cadence, so the
no-per-step-host-sync invariant from the obs/ round holds unchanged.

Grouping: the trainable pytree's top-level keys become groups, except
``"blocks"`` — whose leaves are stacked per-layer ``(L, ...)`` tensors
(models/transformer.py scans layers) — which expands into one group per
transformer block. The same rule applied to a LoRA adapter tree (also
rooted at ``blocks``/``head``) or a pipeline-stage tree (stacked leading
stage axis) yields per-block / per-stage groups with no special cases.
Keys are sorted so the group order is identical across the
``grad_accum=1``, scan-accumulated, shard_map and pipeline step builders —
the arrays must line up with ``group_names`` computed host-side.

Everything here is pure ``jax.numpy`` on already-materialized trees: no
host callbacks, no new collectives (under GSPMD the reductions shard like
any other compute), and the whole bundle is O(n_groups) scalars of
device->host traffic per fetch.
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp

#: Top-level pytree key whose leaves carry a stacked leading layer axis.
STACKED_KEY = "blocks"

#: Metric names emitted per group (each a (n_groups,) float32 array).
HEALTH_ARRAYS = ("grad_norm", "param_norm", "update_norm", "update_ratio")


def _stacked_groups(tree: Dict[str, Any]) -> List[tuple]:
    """The stacked subtree's leaves by what they are stacked over:
    ``[(label, rows, leaves)]``. First ``("block", L, ...)``: the leaves
    stacked over all L layers (every leaf, for most models). A model whose
    layers are of unlike kinds stacks each kind's mixer over its own layers
    (models/transformer.py): such a subgroup (fewer rows than L) is a group
    of its own, ``("attn", La, ...)``, ``("linear", Ll, ...)``, its i-th row
    the i-th layer OF THAT KIND."""
    sub = tree.get(STACKED_KEY)
    if not isinstance(sub, dict):
        return []
    leaves = jax.tree_util.tree_leaves(sub)
    if not leaves:
        return []
    L = max(int(leaf.shape[0]) for leaf in leaves)
    groups = [("block", L, [x for x in leaves if x.shape[0] == L])]
    for name in sorted(sub):
        own = [x for x in jax.tree_util.tree_leaves(sub[name])
               if x.shape[0] != L]
        if own:
            groups.append((str(name), int(own[0].shape[0]), own))
    return groups


def group_names(tree: Dict[str, Any]) -> List[str]:
    """Ordered group labels for ``tree`` (host-side; pairs with the arrays
    ``group_health`` returns). Sorted top-level keys, with the stacked
    ``blocks`` subtree expanded to ``block_00..block_{L-1}`` (and, for a
    model that stacks its mixers by kind, that kind's ``attn_00..``,
    ``linear_00..``: ``_stacked_groups``)."""
    names: List[str] = []
    for key in sorted(tree):
        if key == STACKED_KEY:
            names.extend(f"{label}_{i:02d}"
                         for label, rows, _ in _stacked_groups(tree)
                         for i in range(rows))
        else:
            names.append(str(key))
    return names


def _group_sumsq(tree: Dict[str, Any]) -> jnp.ndarray:
    """(n_groups,) fp32 sum-of-squares per group, in ``group_names``
    order. Per-layer values come from one vectorized reduction over each
    stacked leaf's trailing axes — no per-layer slicing, so the compiled
    program stays O(n_leaves) reductions regardless of depth."""
    parts: List[jnp.ndarray] = []
    for key in sorted(tree):
        leaves = jax.tree_util.tree_leaves(tree[key])
        if key == STACKED_KEY:
            for _, rows, stacked in _stacked_groups(tree):
                acc = jnp.zeros((rows,), jnp.float32)
                for leaf in stacked:
                    x = leaf.astype(jnp.float32)
                    acc = acc + jnp.sum(jnp.square(x),
                                        axis=tuple(range(1, x.ndim)))
                parts.append(acc)
        else:
            acc0 = jnp.zeros((), jnp.float32)
            for leaf in leaves:
                x = leaf.astype(jnp.float32)
                acc0 = acc0 + jnp.sum(jnp.square(x))
            parts.append(acc0[None])
    if not parts:
        return jnp.zeros((0,), jnp.float32)
    return jnp.concatenate(parts)


def _group_nonfinite(tree: Dict[str, Any]) -> jnp.ndarray:
    """(n_groups,) bool: any non-finite element in the group. Computed
    directly on the leaves — a sum-of-squares can overflow to inf on its
    own, which would mislabel a merely-large group as broken."""
    parts: List[jnp.ndarray] = []
    for key in sorted(tree):
        leaves = jax.tree_util.tree_leaves(tree[key])
        if key == STACKED_KEY:
            for _, rows, stacked in _stacked_groups(tree):
                acc = jnp.zeros((rows,), bool)
                for leaf in stacked:
                    acc = acc | jnp.any(
                        ~jnp.isfinite(leaf.astype(jnp.float32)),
                        axis=tuple(range(1, leaf.ndim)))
                parts.append(acc)
        else:
            acc0 = jnp.zeros((), bool)
            for leaf in leaves:
                acc0 = acc0 | jnp.any(~jnp.isfinite(leaf.astype(jnp.float32)))
            parts.append(acc0[None])
    if not parts:
        return jnp.zeros((0,), bool)
    return jnp.concatenate(parts)


def group_norms(tree: Dict[str, Any]) -> jnp.ndarray:
    """(n_groups,) fp32 L2 norms per group, in ``group_names`` order —
    the public pre-clip view of ``_group_sumsq`` (the fused multi-LoRA
    step clips each job's gradient by ITS group norm, so it needs the
    norms before it can build the updates ``group_health`` wants)."""
    return jnp.sqrt(_group_sumsq(tree))


def first_nonfinite_group(tree: Dict[str, Any]) -> jnp.ndarray:
    """Index (int32 scalar) of the first group containing a non-finite
    value, or -1 when all groups are finite. Index into ``group_names``."""
    bad = _group_nonfinite(tree)
    if bad.shape[0] == 0:
        return jnp.asarray(-1, jnp.int32)
    return jnp.where(jnp.any(bad),
                     jnp.argmax(bad).astype(jnp.int32),
                     jnp.asarray(-1, jnp.int32))


def group_health(grads: Dict[str, Any], params: Dict[str, Any],
                 updates: Dict[str, Any]) -> Dict[str, jnp.ndarray]:
    """The health bundle for one optimizer step.

    ``grads`` are pre-clip (matching the step's global ``grad_norm``);
    ``updates`` are what ``optax.apply_updates`` adds — post-clip,
    post-adam, post-LR, so clipping and any optimizer pathology are
    visible; ``params`` are the post-update trainable leaves.

    Returns (all fp32 unless noted):
      - ``grad_norm`` / ``param_norm`` / ``update_norm``: (G,) L2 norms;
      - ``update_ratio``: (G,) update_norm / param_norm (the classic
        should-be-~1e-3 training-health signal; 0-param groups report 0);
      - ``first_nonfinite``: int32 scalar group index, -1 when healthy.
    """
    g = jnp.sqrt(_group_sumsq(grads))
    p = jnp.sqrt(_group_sumsq(params))
    u = jnp.sqrt(_group_sumsq(updates))
    ratio = u / jnp.maximum(p, 1e-12)
    return {
        "grad_norm": g,
        "param_norm": p,
        "update_norm": u,
        "update_ratio": ratio,
        "first_nonfinite": first_nonfinite_group(grads),
    }


def nonfinite_group_name(names: List[str], fetched: Dict[str, Any]):
    """Resolve a fetched bundle's ``first_nonfinite`` index to its group
    name (None when healthy/out of range) — the ONE place the sentinel
    convention lives, shared by the JSONL health row and the watchdog
    context so they can never disagree."""
    import numpy as np

    idx = int(np.asarray(fetched.get("first_nonfinite", -1)))
    return names[idx] if 0 <= idx < len(names) else None


def describe_health(names: List[str], fetched: Dict[str, Any],
                    top_k: int = 3) -> Dict[str, Any]:
    """Host-side digest of one fetched health bundle for event attachment
    (the watchdog_halt path): names the first non-finite group (if any)
    and the ``top_k`` groups by gradient norm, so a halt diagnostic says
    *which layer* instead of just *diverged*."""
    import numpy as np

    out: Dict[str, Any] = {}
    out["first_nonfinite_group"] = nonfinite_group_name(names, fetched)
    gn = np.asarray(fetched.get("grad_norm", []), dtype=np.float64)
    if gn.size and len(names) == gn.size:
        order = np.argsort(gn)[::-1][:top_k]
        out["top_grad_norm_groups"] = [
            {"group": names[int(i)], "grad_norm": round(float(gn[int(i)]), 6)}
            for i in order]
    return out


def health_summary_line(names: List[str], fetched: Dict[str, Any]) -> str:
    """One log line: 'health: max grad block_07 1.2e+01, max ratio head
    3.1e-03' — for humans tailing the log while the JSONL carries the
    full arrays (the trainer emits it at eval cadence)."""
    import numpy as np

    gn = np.asarray(fetched.get("grad_norm", []), dtype=np.float64)
    ur = np.asarray(fetched.get("update_ratio", []), dtype=np.float64)
    if not gn.size or len(names) != gn.size:
        return "health: n/a"
    gi = int(np.argmax(gn))
    line = f"health: max grad {names[gi]} {gn[gi]:.2e}"
    if ur.size == gn.size:
        ri = int(np.argmax(ur))
        line += f", max ratio {names[ri]} {ur[ri]:.2e}"
    return line
