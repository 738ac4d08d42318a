"""Checkpoint save/restore.

The reference saves model weights only, with no optimizer state and NO
resume path anywhere (train.py:231-257, SURVEY.md §5). This module provides
the full design the reference lacks while keeping its export semantics:

  - ``save_checkpoint`` / ``load_checkpoint``: the COMPLETE train state
    (trainable + frozen params, optax state, step, rng), SHARDED: every
    process writes only its addressable shards (one ``.npy`` per unique
    shard, deduplicated across replicas) plus a JSON manifest — an
    Orbax-style resumable checkpoint (SURVEY.md §5 target). Peak host
    memory is ONE SHARD on both save and restore; nothing is gathered.
    Restore streams shard files (mmap) straight onto a target sharding —
    which may differ from the save-time sharding (any slice of the global
    array is assembled from the files that cover it), so an fsdp-8 run can
    restore into a dp-4 run. Requires the checkpoint dir to be on storage
    every process can reach (the norm for pod slices).
  - ``load_checkpoint`` also still reads the round-3 gathered format
    (one full .npy per leaf) for backward compatibility.
  - ``export_params`` / ``load_exported_params``: a single ``.npz`` of just
    the model params, gathered to process 0 — the analog of the reference's
    final ``model_pg_final.pth`` full-state-dict export (main.py:171-172).

Manifest integrity fields (fault-tolerance round): each shard entry in
``manifest["leaves"][i]["shards"]`` additionally records ``bytes`` (file
size) and ``sha256`` (content hash), computed by process 0 after the
all-shards barrier and before the manifest is committed. They are what
``training/resilience.validate_checkpoint`` checks so ``--resume auto``
can reject truncated/bit-rotted checkpoints and fall back to the previous
valid one. Manifests written before this round (no checksum fields) still
load and validate on shard existence alone. ``manifest["metadata"]`` may
also carry a ``cursor`` dict (epoch, file_index, batch_index) written by
the Trainer so resume fast-forwards the deterministic shuffled loader to
the exact mid-epoch position.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, Optional

import jax
import numpy as np

from building_llm_from_scratch_tpu.obs.metrics import emit_event
from building_llm_from_scratch_tpu.utils.logging import setup_logger

logger = setup_logger(__name__)

Params = Dict[str, Any]

_SHARDED_FORMAT = "sharded-v1"


def _restore_dtype(arr: np.ndarray, dtype_name: str) -> np.ndarray:
    """Recover the recorded dtype. np.load returns bf16 (and other
    ml_dtypes) arrays as raw void bytes; a view restores them losslessly."""
    target = np.dtype(dtype_name)        # ml_dtypes names resolve (jax loads it)
    if arr.dtype == target:
        return arr
    if arr.dtype.kind == "V" and arr.dtype.itemsize == target.itemsize:
        return arr.view(target)
    return arr.astype(target)


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _norm_index(index, shape):
    """Serialize a devices_indices_map index (tuple of slices) as
    [[start, stop], ...] with Nones resolved against the global shape."""
    out = []
    for sl, dim in zip(index, shape):
        start = 0 if sl.start is None else int(sl.start)
        stop = dim if sl.stop is None else int(sl.stop)
        out.append([start, stop])
    return out


def _unique_shards(leaf):
    """(owner_device, index) per UNIQUE shard of a jax.Array: replicas are
    deduplicated; the device with the lowest id in each replica group owns
    the write."""
    shape = leaf.shape
    index_map = leaf.sharding.devices_indices_map(shape)
    groups: Dict[tuple, list] = {}
    for dev, index in index_map.items():
        key = tuple(tuple(b) for b in _norm_index(index, shape))
        groups.setdefault(key, []).append(dev)
    out = []
    for key in sorted(groups):
        devs = groups[key]
        owner = min(devs, key=lambda d: d.id)
        out.append((owner, key))
    return out


def sha256_file(path: str, chunk: int = 1 << 20) -> str:
    """Chunked file hash — the single implementation shared by the save
    path (recording) and resilience.validate_checkpoint (verifying)."""
    import hashlib

    h = hashlib.sha256()
    with open(path, "rb") as f:
        for block in iter(lambda: f.read(chunk), b""):
            h.update(block)
    return h.hexdigest()


class _HashingWriter:
    """File-object tee: forwards writes while folding the exact bytes into
    a sha256. No ``fileno`` on purpose — numpy then streams the array
    through ``write()`` in chunks, so hashing adds NO extra array copy and
    the save path keeps its peak-host-memory-is-one-shard contract."""

    def __init__(self, f):
        import hashlib

        self._f = f
        self._h = hashlib.sha256()
        self.nbytes = 0

    def write(self, data):
        self._h.update(data)
        self.nbytes += len(data)
        return self._f.write(data)

    def hexdigest(self) -> str:
        return self._h.hexdigest()


def _write_shard_hashed(path: str, arr: np.ndarray):
    """np.save through a hashing tee — locally-written shards get their
    integrity record for free instead of a full read-back at manifest
    time. Returns (nbytes, sha256hex)."""
    with open(path, "wb") as f:
        w = _HashingWriter(f)
        np.save(w, arr)
    return w.nbytes, w.hexdigest()


def _barrier(name: str) -> None:
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(name)


def _plan_leaf_shards(index: int, leaf):
    """Shard plan for one (``jnp_asarray``'d) leaf: the manifest shard
    entries plus ``owned`` — the ``[(fname, device_buffer)]`` THIS process
    is responsible for writing. One implementation shared by the
    synchronous save loop and the async snapshot (``snapshot_for_save``),
    so the two paths can never disagree about file layout or ownership."""
    n_procs = jax.process_count()
    local_ids = {d.id for d in jax.local_devices()}
    shards_meta, owned = [], []
    if n_procs > 1 and leaf.sharding.is_fully_addressable:
        # host-local leaf (e.g. jnp.asarray of a python scalar before any
        # jitted step): every process sees only its OWN devices in
        # devices_indices_map, so each would elect a local owner for the
        # same index and race np.save on the same file (round-4 ADVICE
        # low #2). Route through process 0 alone.
        fname = f"leaf_{index:05d}.shard_000.npy"
        shards_meta.append({"file": fname,
                            "index": [[0, d] for d in leaf.shape]})
        if jax.process_index() == 0:
            owned.append((fname, leaf))
    else:
        by_device = {s.device.id: s for s in leaf.addressable_shards}
        for k, (owner, index_key) in enumerate(_unique_shards(leaf)):
            fname = f"leaf_{index:05d}.shard_{k:03d}.npy"
            shards_meta.append({"file": fname,
                                "index": [list(se) for se in index_key]})
            if owner.id in local_ids:
                owned.append((fname, by_device[owner.id].data))
    return shards_meta, owned


def _plan_state_shards(state: Params):
    """Flatten ``state`` into per-leaf shard plans and post every owned
    shard's device->host copy asynchronously: ``np.asarray`` on each shard
    otherwise serializes one blocking transfer per leaf. Only OWNER shards
    are
    prefetched — replicas would multiply the transferred bytes by the
    local device count for nothing. Returns
    ``[(path, leaf, shards_meta, owned)]``."""
    leaves = jax.tree_util.tree_flatten_with_path(state)[0]
    planned = []
    for i, (path, leaf) in enumerate(leaves):
        leaf = jnp_asarray(leaf)
        shards_meta, owned = _plan_leaf_shards(i, leaf)
        planned.append((path, leaf, shards_meta, owned))
    for _, _, _, owned in planned:
        for _, buf in owned:
            try:
                buf.copy_to_host_async()
            except (AttributeError, RuntimeError):
                pass
    return planned


def save_checkpoint(ckpt_dir: str, state: Params,
                    extra_metadata: Optional[dict] = None) -> str:
    """Write ``state`` as a SHARDED checkpoint. Returns the dir.

    Every process writes the unique shards it owns (lowest-device-id
    replica wins, so replicated leaves are written exactly once across the
    job); process 0 writes the manifest. Nothing is gathered — peak host
    memory is one shard. All processes must see the same filesystem.

    Atomic commit (round-4 ADVICE medium #1): all shards land in a
    ``<dir>.tmp`` staging dir; after a cross-process barrier confirms every
    shard write finished, process 0 writes the manifest (still into the
    staging dir) and renames it over the target. A reader therefore never
    sees a manifest without all its shards. The commit is two renames
    (previous -> ``.old``, staging -> final); a preemption in the window
    between them leaves no dir at the tag itself, but BOTH neighbours are
    complete (``.tmp`` holds the new checkpoint incl. manifest, ``.old``
    the previous one) and ``load_checkpoint``/``checkpoint_metadata``
    transparently fall back to them (``_resolve_ckpt_dir``), so no commit
    ordering loses a restorable checkpoint.
    """
    t_save = time.perf_counter()
    is_proc0 = jax.process_index() == 0
    tmp_dir = ckpt_dir.rstrip("/") + ".tmp"
    if is_proc0:
        # a crashed earlier save may have left a stale staging dir
        if os.path.isdir(tmp_dir):
            import shutil

            shutil.rmtree(tmp_dir)
        os.makedirs(tmp_dir, exist_ok=True)
    _barrier(f"ckpt_stage:{ckpt_dir}")
    os.makedirs(tmp_dir, exist_ok=True)
    manifest = {"format": _SHARDED_FORMAT, "leaves": [],
                "metadata": extra_metadata or {}}
    planned = _plan_state_shards(state)
    local_hashes: Dict[str, tuple] = {}      # fname -> (bytes, sha256)
    for i, (path, leaf, shards_meta, owned) in enumerate(planned):
        for fname, buf in owned:
            nb, hx = _write_shard_hashed(os.path.join(tmp_dir, fname),
                                         np.asarray(buf))
            if is_proc0:
                local_hashes[fname] = (nb, hx)
        manifest["leaves"].append({
            "index": i,
            "path": _path_str(path),
            "shape": list(leaf.shape),
            "dtype": str(leaf.dtype),
            "shards": shards_meta,
        })
    # every shard file is on disk before the manifest exists anywhere
    _barrier(f"ckpt_shards:{ckpt_dir}")
    if is_proc0:
        import shutil

        # integrity records for resilience.validate_checkpoint: every shard
        # gets its size + sha256 into the manifest BEFORE the commit
        # rename, so a truncated or bit-flipped file is detectable at
        # resume time. Shards this process wrote were hashed at write time;
        # only shards OTHER hosts wrote (on the shared filesystem, complete
        # per the barrier above) need a read-back — zero extra I/O on
        # single-host runs.
        for leaf_meta in manifest["leaves"]:
            for sh in leaf_meta["shards"]:
                if sh["file"] in local_hashes:
                    sh["bytes"], sh["sha256"] = local_hashes[sh["file"]]
                else:
                    spath = os.path.join(tmp_dir, sh["file"])
                    sh["bytes"] = os.path.getsize(spath)
                    sh["sha256"] = sha256_file(spath)
        with open(os.path.join(tmp_dir, "manifest.json"), "w") as f:
            json.dump(manifest, f, indent=1)
        old_dir = None
        if os.path.isdir(ckpt_dir):
            old_dir = ckpt_dir.rstrip("/") + ".old"
            if os.path.isdir(old_dir):
                shutil.rmtree(old_dir)
            os.rename(ckpt_dir, old_dir)
        os.rename(tmp_dir, ckpt_dir)
        if old_dir is not None:
            shutil.rmtree(old_dir)
    # no process returns (and e.g. immediately resaves the same tag or
    # resumes from it) before the commit rename is visible
    _barrier(f"ckpt_commit:{ckpt_dir}")
    # structured telemetry: the coordinator's manifest carries every
    # shard's size, so total bytes come for free (other hosts report None
    # rather than a partial local sum)
    total_bytes = (sum(int(sh.get("bytes", 0)) for leaf in manifest["leaves"]
                       for sh in leaf["shards"]) if is_proc0 else None)
    emit_event("checkpoint_save", path=ckpt_dir,
               step=(extra_metadata or {}).get("global_step"),
               seconds=round(time.perf_counter() - t_save, 4),
               bytes=total_bytes, leaves=len(manifest["leaves"]))
    return ckpt_dir


def snapshot_for_save(state: Params,
                      extra_metadata: Optional[dict] = None) -> dict:
    """Materialize everything ``write_snapshot`` needs to write a sharded
    checkpoint WITHOUT touching device state again: the manifest skeleton
    plus host copies of every owned shard.

    This is the synchronous half of an async save (training/
    async_checkpoint.py): it MUST run on the main thread — ``np.asarray``
    below blocks until the in-flight donated steps that produce ``state``
    have finished and the posted D2H DMAs land, which is device work the
    background writer thread must never touch. Cost vs the streaming
    synchronous save: the whole state is host-resident at once (that IS
    the async tradeoff — the write, hash and commit I/O move off the
    critical path in exchange for one state-sized host buffer).
    """
    planned = _plan_state_shards(state)
    manifest = {"format": _SHARDED_FORMAT, "leaves": [],
                "metadata": extra_metadata or {}}
    arrays: Dict[str, np.ndarray] = {}
    for i, (path, leaf, shards_meta, owned) in enumerate(planned):
        for fname, buf in owned:
            arrays[fname] = np.asarray(buf)
        manifest["leaves"].append({
            "index": i,
            "path": _path_str(path),
            "shape": list(leaf.shape),
            "dtype": str(leaf.dtype),
            "shards": shards_meta,
        })
    return {"manifest": manifest, "arrays": arrays}


def write_snapshot(ckpt_dir: str, snapshot: dict) -> str:
    """Write a ``snapshot_for_save`` snapshot as a committed checkpoint.

    Pure host I/O over host arrays — safe on a background thread; the
    same ``.tmp`` staging, sha256-manifest and two-rename commit sequence
    as ``save_checkpoint``, so readers (``load_checkpoint``,
    ``validate_checkpoint``, ``_resolve_ckpt_dir`` recovery) cannot tell
    the two writers apart. Single-process writes only: the async path
    falls back to the synchronous (barrier-using) save on multi-host runs
    — ``AsyncCheckpointer`` enforces that, this function just refuses.
    """
    import shutil

    if jax.process_count() > 1:
        raise RuntimeError(
            "write_snapshot is single-process only (its commit sequence "
            "has no cross-host barriers); use save_checkpoint.")
    t_save = time.perf_counter()
    manifest, arrays = snapshot["manifest"], snapshot["arrays"]
    tmp_dir = ckpt_dir.rstrip("/") + ".tmp"
    if os.path.isdir(tmp_dir):
        shutil.rmtree(tmp_dir)
    os.makedirs(tmp_dir, exist_ok=True)
    for leaf_meta in manifest["leaves"]:
        for sh in leaf_meta["shards"]:
            nb, hx = _write_shard_hashed(os.path.join(tmp_dir, sh["file"]),
                                         arrays[sh["file"]])
            sh["bytes"], sh["sha256"] = nb, hx
    with open(os.path.join(tmp_dir, "manifest.json"), "w") as f:
        json.dump(manifest, f, indent=1)
    old_dir = None
    if os.path.isdir(ckpt_dir):
        old_dir = ckpt_dir.rstrip("/") + ".old"
        if os.path.isdir(old_dir):
            shutil.rmtree(old_dir)
        os.rename(ckpt_dir, old_dir)
    os.rename(tmp_dir, ckpt_dir)
    if old_dir is not None:
        shutil.rmtree(old_dir)
    total_bytes = sum(int(sh.get("bytes", 0)) for leaf in manifest["leaves"]
                      for sh in leaf["shards"])
    emit_event("checkpoint_save", path=ckpt_dir,
               step=manifest["metadata"].get("global_step"),
               seconds=round(time.perf_counter() - t_save, 4),
               bytes=total_bytes, leaves=len(manifest["leaves"]),
               writer="async")
    return ckpt_dir


def save_checkpoint_gathered(ckpt_dir: str, state: Params,
                             extra_metadata: Optional[dict] = None) -> str:
    """The round-3 format: every leaf gathered full and written by process
    0 (the reference's FULL_STATE_DICT rank-0 gather, train.py:244-249).
    Kept for interop with round-3 checkpoints and as the compat-path test
    fixture; ``save_checkpoint`` (sharded) is the default."""
    from building_llm_from_scratch_tpu.parallel.collectives import gather_full

    is_writer = jax.process_index() == 0
    leaves = jax.tree_util.tree_flatten_with_path(state)[0]
    if is_writer:
        os.makedirs(ckpt_dir, exist_ok=True)
    manifest = {"leaves": [], "metadata": extra_metadata or {}}
    for i, (path, leaf) in enumerate(leaves):
        arr = np.asarray(gather_full(leaf))
        manifest["leaves"].append({
            "index": i,
            "path": _path_str(path),
            "shape": list(arr.shape),
            "dtype": str(arr.dtype),
        })
        if is_writer:
            np.save(os.path.join(ckpt_dir, f"leaf_{i:05d}.npy"), arr)
    if is_writer:
        with open(os.path.join(ckpt_dir, "manifest.json"), "w") as f:
            json.dump(manifest, f, indent=1)
    return ckpt_dir


def jnp_asarray(leaf):
    """Leaves like python ints (step counters built outside jit) become
    committed jax arrays so sharding introspection works uniformly."""
    if isinstance(leaf, jax.Array):
        return leaf
    import jax.numpy as jnp

    return jnp.asarray(leaf)


def _read_leaf_slice(ckpt_dir: str, meta: dict, index) -> np.ndarray:
    """Assemble an arbitrary slice of a leaf from its shard files (mmap —
    only the bytes covering the request are read)."""
    shape = tuple(meta["shape"])
    bounds = _norm_index(index, shape)
    target_shape = tuple(b[1] - b[0] for b in bounds)
    dtype = np.dtype(meta["dtype"])
    # fast path: a single shard exactly matches the request
    for sh in meta["shards"]:
        if [list(map(int, b)) for b in sh["index"]] == bounds:
            arr = np.load(os.path.join(ckpt_dir, sh["file"]))
            return _restore_dtype(arr, meta["dtype"])
    out = np.empty(target_shape, dtype)
    filled = 0
    for sh in meta["shards"]:
        s_bounds = sh["index"]
        # overlap of shard box and requested box, per dim
        lo = [max(a[0], b[0]) for a, b in zip(s_bounds, bounds)]
        hi = [min(a[1], b[1]) for a, b in zip(s_bounds, bounds)]
        if any(l >= h for l, h in zip(lo, hi)):
            continue
        src = np.load(os.path.join(ckpt_dir, sh["file"]), mmap_mode="r")
        src = _restore_dtype(np.asarray(src[tuple(
            slice(l - sb[0], h - sb[0])
            for l, h, sb in zip(lo, hi, s_bounds))]), meta["dtype"])
        out[tuple(slice(l - b[0], h - b[0])
                  for l, h, b in zip(lo, hi, bounds))] = src
        filled += src.size
    if filled < int(np.prod(target_shape)):
        raise ValueError(
            f"Checkpoint shards for leaf '{meta['path']}' do not cover the "
            f"requested slice {bounds} — incomplete checkpoint?")
    return out


def _resolve_ckpt_dir(ckpt_dir: str) -> str:
    """Resolve a checkpoint tag to a readable dir, recovering from a save
    preempted inside the two-rename commit window: prefer the tag itself,
    then the completed staging dir (``.tmp`` — manifest is written there
    last, so its presence means every shard is on disk), then the
    displaced previous checkpoint (``.old``)."""
    if os.path.exists(os.path.join(ckpt_dir, "manifest.json")):
        return ckpt_dir
    for suffix in (".tmp", ".old"):
        cand = ckpt_dir.rstrip("/") + suffix
        if os.path.exists(os.path.join(cand, "manifest.json")):
            logger.warning(
                "Checkpoint %s has no manifest (save preempted mid-commit?)"
                "; recovering from %s", ckpt_dir, cand)
            return cand
    return ckpt_dir


def _cleanup_stale_siblings(ckpt_dir: str) -> None:
    """Remove ``.tmp``/``.old`` staging dirs orphaned by a crashed save.

    Only called once the tag itself resolved (its manifest exists), so the
    siblings are by definition leftovers, not the recovery copy. Process 0
    only — peers resolve the committed tag and never read the orphans."""
    import jax as _jax

    if _jax.process_index() != 0:
        return
    import shutil

    for suffix in (".tmp", ".old"):
        cand = ckpt_dir.rstrip("/") + suffix
        if os.path.isdir(cand):
            logger.warning(
                "Removing orphaned checkpoint staging dir %s (left by a "
                "crashed save).", cand)
            shutil.rmtree(cand, ignore_errors=True)


def _read_manifest(ckpt_dir: str) -> dict:
    """Read + structurally check a checkpoint manifest, raising ONE clear
    ``ValueError`` naming the dir and what is missing/malformed instead of
    a raw ``FileNotFoundError``/``KeyError``/``JSONDecodeError``."""
    manifest_path = os.path.join(ckpt_dir, "manifest.json")
    if not os.path.isfile(manifest_path):
        raise ValueError(
            f"'{ckpt_dir}' is not a readable checkpoint: manifest.json is "
            "missing (not a checkpoint directory, or the save died before "
            "its commit and left no recoverable .tmp/.old staging dir).")
    try:
        with open(manifest_path) as f:
            manifest = json.load(f)
    except (json.JSONDecodeError, UnicodeDecodeError, OSError) as e:
        raise ValueError(
            f"Checkpoint manifest {manifest_path} is malformed "
            f"({type(e).__name__}: {e}); the checkpoint cannot be "
            "restored.") from e
    if not isinstance(manifest, dict) or not isinstance(
            manifest.get("leaves"), list):
        raise ValueError(
            f"Checkpoint manifest {manifest_path} is malformed: expected a "
            "JSON object with a 'leaves' list.")
    return manifest


def load_checkpoint(ckpt_dir: str, template_state: Params,
                    shardings: Optional[Params] = None) -> Params:
    """Restore a checkpoint into the structure of ``template_state``.

    ``template_state`` (e.g. a freshly initialized state) supplies the
    pytree structure; leaf paths are cross-checked against the manifest.
    If ``shardings`` (a matching pytree of jax.sharding.Sharding) is given,
    each leaf lands directly on its target placement — for sharded-v1
    checkpoints each process reads ONLY the bytes its devices need
    (restore-time sharding may differ from save-time sharding).

    Handles both the sharded-v1 format and the round-3 gathered format
    (full ``leaf_NNNNN.npy`` files).
    """
    t_load = time.perf_counter()
    resolved = _resolve_ckpt_dir(ckpt_dir)
    if resolved == ckpt_dir:
        _cleanup_stale_siblings(ckpt_dir)
    ckpt_dir = resolved
    manifest = _read_manifest(ckpt_dir)
    sharded = manifest.get("format") == _SHARDED_FORMAT
    flat, treedef = jax.tree_util.tree_flatten_with_path(template_state)
    if len(flat) != len(manifest["leaves"]):
        raise ValueError(
            f"Checkpoint has {len(manifest['leaves'])} leaves but template "
            f"state has {len(flat)} — structure mismatch.")
    shard_leaves = (jax.tree_util.tree_leaves(shardings)
                    if shardings is not None else [None] * len(flat))
    loaded = []
    for (path, tmpl), meta, shard in zip(flat, manifest["leaves"],
                                         shard_leaves):
        if _path_str(path) != meta["path"]:
            raise ValueError(
                f"Leaf path mismatch: template {_path_str(path)} vs "
                f"checkpoint {meta['path']}")
        tmpl_shape = tuple(getattr(tmpl, "shape", ()))
        tmpl_dtype = str(getattr(tmpl, "dtype", ""))
        if tuple(meta["shape"]) != tmpl_shape:
            # exactly the train-state PRNG leaf (state["rng"]) — an
            # endswith match would also catch unrelated leaves whose name
            # merely ends in "rng" and silently skip their structure check
            if meta["path"] == "rng":
                # PRNG keys are impl-specific (threefry (2,) vs rbg (4,)
                # uint32); a checkpoint written under a different default
                # impl cannot restore its dropout stream — keep the
                # template's fresh key instead of bricking the resume
                logger.warning(
                    "Checkpoint rng leaf has shape %s but the current PRNG "
                    "impl uses %s; keeping a fresh rng (dropout stream "
                    "restarts).", tuple(meta["shape"]), tmpl_shape)
                # same placement contract as every other restored leaf
                loaded.append(jax.device_put(tmpl, shard)
                              if shard is not None else tmpl)
                continue
            raise ValueError(
                f"Checkpoint leaf '{meta['path']}' has shape "
                f"{tuple(meta['shape'])} but the model expects {tmpl_shape} "
                "— wrong model size/config for this checkpoint.")
        if tmpl_dtype and meta["dtype"] != tmpl_dtype:
            raise ValueError(
                f"Checkpoint leaf '{meta['path']}' has dtype "
                f"{meta['dtype']} but the model expects {tmpl_dtype} "
                "— was the checkpoint written with a different --data_type?")
        if sharded and shard is not None:
            # stream shard files straight onto the target sharding: the
            # callback is invoked once per addressable shard index
            arr = jax.make_array_from_callback(
                tuple(meta["shape"]), shard,
                lambda idx, meta=meta: _read_leaf_slice(ckpt_dir, meta, idx))
            loaded.append(arr)
            continue
        if sharded:
            full_idx = tuple(slice(0, d) for d in meta["shape"])
            arr = _read_leaf_slice(ckpt_dir, meta, full_idx)
        else:
            arr = np.load(os.path.join(ckpt_dir,
                                       f"leaf_{meta['index']:05d}.npy"))
            arr = _restore_dtype(arr, meta["dtype"])
        if shard is not None:
            loaded.append(jax.device_put(arr, shard))
        else:
            loaded.append(jax.device_put(arr))
    emit_event("checkpoint_restore", path=ckpt_dir,
               step=manifest.get("metadata", {}).get("global_step"),
               seconds=round(time.perf_counter() - t_load, 4),
               leaves=len(manifest["leaves"]))
    return jax.tree_util.tree_unflatten(treedef, loaded)


def checkpoint_metadata(ckpt_dir: str) -> dict:
    return _read_manifest(_resolve_ckpt_dir(ckpt_dir)).get("metadata", {})


def export_params(path: str, params: Params) -> str:
    """Single-file params export (reference final .pth, main.py:171-172).

    Like ``save_checkpoint``, each leaf passes through ``gather_full``
    (leaf-at-a-time — all processes iterate in the same order) so
    mesh-sharded params on multi-host runs reassemble before process 0
    writes. Dtypes are recorded per array (``__dtype__.<key>`` entries)
    because np.savez stores ml_dtypes arrays as raw void bytes."""
    from building_llm_from_scratch_tpu.parallel.collectives import gather_full

    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    arrays = {}
    for p, leaf in flat:
        key = _path_str(p)
        arr = np.asarray(gather_full(leaf))
        arrays[key] = arr
        arrays[f"__dtype__.{key}"] = np.asarray(str(arr.dtype))
    if jax.process_index() == 0:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        np.savez(path, **arrays)
    return path


def load_exported_params(path: str, template_params: Params) -> Params:
    """Load an ``export_params`` file into the template's structure."""
    data = np.load(path)
    flat, treedef = jax.tree_util.tree_flatten_with_path(template_params)
    leaves = []
    for p, tmpl in flat:
        key = _path_str(p)
        if key not in data:
            raise KeyError(f"Export missing parameter {key}")
        dtype_key = f"__dtype__.{key}"
        # restore through the RECORDED dtype (falling back to the template
        # for exports written before dtypes were recorded), then cast to the
        # template — never reinterpret bits across same-width dtypes
        recorded = (str(data[dtype_key]) if dtype_key in data
                    else str(tmpl.dtype))
        arr = _restore_dtype(data[key], recorded).astype(tmpl.dtype)
        leaves.append(jax.device_put(arr))
    return jax.tree_util.tree_unflatten(treedef, leaves)
