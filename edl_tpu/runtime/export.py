"""Inference-model export: the ``save_inference_model`` equivalent.

The reference's serving story is Fluid's ``save_inference_model``: trainer 0
periodically writes a pruned inference program + params that a separate
process loads to predict (`example/ctr/ctr/train.py:169-180` every 1000
batches and each pass; `example/fit_a_line/fluid/fit_a_line.py:40-44,95-117`
save/load; `recognize_digits.py:147-173` infer mode). There the "program" is
a serialized graph; here the graph is a pure function already in the
package, so the artifact is **(model reference + config + params)** — the
loader rebuilds the jitted predict function from the zoo and places the
weights on whatever mesh serves them.

Artifact layout (one directory):

- ``manifest.json`` — format version, model module ref + config kwargs,
  step, the weights filename, and the flattened leaf index (tree paths +
  logical dtypes);
- ``params-<step>.npz`` — leaves keyed ``leaf_00000...``, in manifest
  order. bfloat16 travels as uint16 bit patterns with the logical dtype
  recorded in the manifest.

Concurrent-reader safety (the reference's pattern is infer-while-train):
weights files are step-unique and published before the manifest, and the
manifest is renamed into place atomically — a poller that reads a manifest
always finds exactly the weights it names (the previous artifact's weights
are kept one generation as grace for a reader holding an older manifest).

In multi-process jobs params can be sharded across hosts, so gathering is
a COLLECTIVE: every process must call ``save_inference_model`` (or invoke
the ``PeriodicExporter``) at the same step — the lockstep multihost loop
guarantees this for ``step_callback`` — and only the writer rank touches
the filesystem.
"""

from __future__ import annotations

import json
import logging
import os
import shutil
import tempfile
import threading
import time
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding

from edl_tpu.obs.metrics import get_registry

__all__ = ["save_inference_model", "load_inference_model", "InferenceModel",
           "PeriodicExporter", "artifact_version", "resolve_artifact_dir",
           "LATEST"]

log = logging.getLogger("edl_tpu.runtime.export")

MANIFEST = "manifest.json"
#: atomic pointer file in a versioned export root naming the newest
#: complete version directory — the serving tier's swap watcher reads this
LATEST = "LATEST"
_VERSION_PREFIX = "v"
_FORMAT = 1

#: same family train_loop counts hot-loop retraces into (get-or-create by
#: name shares the instrument without importing the trainer): a predict
#: retrace past the first shape is the same performance bug on the serving
#: side — the bucketed frontend exists so it never fires steady-state.
_M_RETRACES = get_registry().counter(
    "edl_trainer_retraces_total",
    "steady-state jit recompilations (shape/dtype churn in the hot loop)",
)
#: weights files kept besides the live one: grace for a reader that loaded
#: an older manifest just before a newer export landed
#: orphaned .tmp files older than this are swept during the GC pass
_TMP_SWEEP_AGE_SEC = 300.0


def _encode_path(path) -> list:
    out = []
    for entry in path:
        if isinstance(entry, jax.tree_util.DictKey):
            out.append(["d", entry.key])
        elif isinstance(entry, jax.tree_util.SequenceKey):
            out.append(["s", entry.idx])
        else:
            raise TypeError(
                f"unsupported pytree key {entry!r}; params trees are "
                "dicts/lists by the zoo convention"
            )
    return out


def _rebuild(paths_and_leaves) -> Any:
    """Nested dicts/lists from (encoded path, leaf) pairs."""
    if not paths_and_leaves:
        return {}
    root: Any = {} if paths_and_leaves[0][0][0][0] == "d" else []

    def ensure(container, key, kind):
        template: Any = {} if kind == "d" else []
        if isinstance(container, dict):
            return container.setdefault(key, template)
        while len(container) <= key:
            container.append(None)
        if container[key] is None:
            container[key] = template
        return container[key]

    for path, leaf in paths_and_leaves:
        node = root
        for (kind, key), nxt in zip(path[:-1], path[1:]):
            node = ensure(node, key, nxt[0])
        kind, key = path[-1]
        if isinstance(node, dict):
            node[key] = leaf
        else:
            while len(node) <= key:
                node.append(None)
            node[key] = leaf
    return root


def _gather_host(params: Any):
    """Device->host as numpy, collective where shards span processes.

    ``process_allgather`` is a collective: in multi-process jobs EVERY rank
    must reach this call at the same step (see module docstring)."""

    def to_host(leaf):
        if getattr(leaf, "is_fully_addressable", True):
            return np.asarray(jax.device_get(leaf))
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(leaf, tiled=True))

    return [
        (path, to_host(leaf))
        for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]
    ]


def _write_artifact(directory, model_ref, host_flat, config, step) -> None:
    os.makedirs(directory, exist_ok=True)
    # Never regress a published artifact: a gang warm-restart resets the
    # in-process high-water mark, and the replayed steps between the
    # restored checkpoint and the crash would otherwise overwrite a newer
    # manifest with older weights. Writer-local by design (the collective
    # gather already ran on every rank).
    try:
        with open(os.path.join(directory, MANIFEST)) as f:
            prev_manifest = json.load(f)
    except (FileNotFoundError, json.JSONDecodeError):
        prev_manifest = {}
    if step is not None:
        published = prev_manifest.get("step")
        if published is not None and published >= step:
            return
    arrays: Dict[str, np.ndarray] = {}
    leaves = []
    for i, (path, arr) in enumerate(host_flat):
        logical = str(arr.dtype)
        if logical == "bfloat16":
            arr = arr.view(np.uint16)  # numpy-native container
        elif arr.dtype.kind not in "fiub":
            raise TypeError(
                f"leaf dtype {logical!r} has no wire representation; "
                "supported: numpy-native float/int/uint/bool + bfloat16"
            )
        arrays[f"leaf_{i:05d}"] = arr
        leaves.append({"path": _encode_path(path), "dtype": logical})
    # Unique weights name published BEFORE the manifest that names it: a
    # reader pairing manifest -> weights can never mix two exports. A
    # step-less save gets a random suffix (uniqueness is the invariant;
    # only ordering needs steps, and the regression guard above already
    # treats step-less saves as unordered).
    if step is not None:
        weights_name = f"params-{step}.npz"
    else:
        import uuid

        weights_name = f"params-final-{uuid.uuid4().hex[:8]}.npz"
    manifest = {
        "format": _FORMAT,
        "model": model_ref,
        "config": config or {},
        "step": step,
        "weights": weights_name,
        "leaves": leaves,
    }
    fd, tmp = tempfile.mkstemp(dir=directory, suffix=".npz.tmp")
    with os.fdopen(fd, "wb") as f:
        np.savez(f, **arrays)
    os.replace(tmp, os.path.join(directory, weights_name))
    fd, tmp = tempfile.mkstemp(dir=directory, suffix=".json.tmp")
    with os.fdopen(fd, "w") as f:
        json.dump(manifest, f)
    os.replace(tmp, os.path.join(directory, MANIFEST))
    # GC superseded weights. The grace generation is EXACTLY the file the
    # just-replaced manifest named (a reader that paired that manifest with
    # its weights must still find them); everything else is unreachable —
    # no reachable manifest names it — and goes. Filename-step or mtime
    # heuristics can both misidentify the grace file (step-less "final"
    # saves, coarse mtimes), so the manifest itself is the source of truth.
    spare = {weights_name, prev_manifest.get("weights")}
    for stale in os.listdir(directory):
        if (stale.startswith("params-") and stale.endswith(".npz")
                and stale not in spare):
            os.unlink(os.path.join(directory, stale))
    # Sweep orphaned mkstemp leftovers (a writer that died between mkstemp
    # and os.replace); age-gated so a concurrent writer's live tmp survives.
    # Intentionally host-side wall clock (EDL002 does not apply: this runs
    # after the collective gather, never under a trace) — mtime comparison
    # needs the same epoch clock os.path.getmtime reports.
    now = time.time()
    for p in os.listdir(directory):
        if p.endswith((".npz.tmp", ".json.tmp")):
            full = os.path.join(directory, p)
            try:
                if now - os.path.getmtime(full) > _TMP_SWEEP_AGE_SEC:
                    os.unlink(full)
            except OSError:
                pass  # already gone or being replaced


def _read_latest(directory: str) -> Optional[str]:
    try:
        with open(os.path.join(directory, LATEST)) as f:
            name = f.read().strip()
    except OSError:
        return None
    return name or None


def resolve_artifact_dir(directory: str) -> str:
    """Follow a versioned root's ``LATEST`` pointer to the version directory
    it names; a flat (unversioned) artifact directory resolves to itself."""
    name = _read_latest(directory)
    if name:
        candidate = os.path.join(directory, name)
        if os.path.isdir(candidate):
            return candidate
    return directory


def artifact_version(directory: str) -> Optional[Tuple]:
    """Published-artifact identity ``(step, weights_name, dir_name)`` or
    ``None`` when nothing complete is published. This is what the serving
    tier's swap watcher polls: LATEST is replaced atomically only after a
    version directory is complete, so the identity can never name a
    half-written export."""
    resolved = resolve_artifact_dir(directory)
    try:
        with open(os.path.join(resolved, MANIFEST)) as f:
            manifest = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    return (manifest.get("step"), manifest.get("weights"),
            os.path.basename(resolved))


def _version_step(name: str) -> Optional[int]:
    try:
        return int(name[len(_VERSION_PREFIX):])
    except (ValueError, TypeError):
        return None  # step-less "vfinal-<uuid>" dirs are unordered


def _write_versioned(directory, model_ref, host_flat, config, step) -> None:
    """One complete artifact per ``v<step>`` subdirectory, published by
    atomically replacing the ``LATEST`` pointer AFTER the directory is
    complete. A writer that crashes mid-export leaves an orphan directory
    LATEST never pointed at — readers keep getting the previous complete
    version, and the orphan is swept (age-gated) on a later export."""
    os.makedirs(directory, exist_ok=True)
    prev = _read_latest(directory)
    prev_step = _version_step(prev) if prev else None
    if step is not None and prev_step is not None and prev_step >= step:
        return  # same high-water regression guard as the flat layout
    if step is not None:
        vname = f"{_VERSION_PREFIX}{int(step):010d}"  # lexical == numeric
    else:
        import uuid

        vname = f"{_VERSION_PREFIX}final-{uuid.uuid4().hex[:8]}"
    _write_artifact(os.path.join(directory, vname), model_ref, host_flat,
                    config, step)
    fd, tmp = tempfile.mkstemp(dir=directory, suffix=".latest.tmp")
    with os.fdopen(fd, "w") as f:
        f.write(vname)
    os.replace(tmp, os.path.join(directory, LATEST))
    # GC: keep the generation LATEST names plus the one it just replaced
    # (grace for a reader that resolved the old pointer moments ago);
    # every other COMPLETE version is unreachable and goes. Incomplete
    # orphans (crashed writer) are swept only once aged, mirroring the
    # tmp-file sweep — a slow concurrent writer's live directory survives.
    spare = {vname, prev}
    now = time.time()
    for name in os.listdir(directory):
        full = os.path.join(directory, name)
        if (name in spare or not name.startswith(_VERSION_PREFIX)
                or not os.path.isdir(full)):
            continue
        complete = os.path.exists(os.path.join(full, MANIFEST))
        try:
            aged = now - os.path.getmtime(full) > _TMP_SWEEP_AGE_SEC
        except OSError:
            continue  # raced with another sweep
        if complete or aged:
            shutil.rmtree(full, ignore_errors=True)
    for name in os.listdir(directory):
        if name.endswith(".latest.tmp"):
            full = os.path.join(directory, name)
            try:
                if now - os.path.getmtime(full) > _TMP_SWEEP_AGE_SEC:
                    os.unlink(full)
            except OSError:
                pass  # already gone or being replaced


def _refuse_unservable(model_ref: str) -> None:
    """A zoo module that states why it cannot be served (its
    ``NOT_SERVABLE``) is refused here, by that reason, and not inside a
    trace of a predict or decode program it does not have."""
    from edl_tpu import models as zoo

    reason = zoo.serving_refusal(model_ref)
    if reason:
        raise NotImplementedError(
            f"model {model_ref!r} has no inference artifact: {reason}")


def save_inference_model(
    directory: str,
    model_ref: str,
    params: Any,
    config: Optional[Dict[str, Any]] = None,
    step: Optional[int] = None,
    write: bool = True,
    versioned: bool = False,
) -> None:
    """Write the serving artifact for ``params`` of zoo model ``model_ref``.

    ``model_ref`` is the zoo module name (``"ctr"``, ``"resnet"``, ...);
    ``config`` the ``make_model`` kwargs that built the trained variant
    (omit for the module's default ``MODEL``). In multi-process jobs every
    rank must call this at the same step (the gather is collective) with
    ``write=True`` on exactly one rank. ``versioned=True`` writes each
    export to its own ``v<step>`` subdirectory and atomically advances the
    ``LATEST`` pointer (the layout the serving tier's swap watcher needs).
    """
    _refuse_unservable(model_ref)
    host_flat = _gather_host(params)
    if write:
        writer = _write_versioned if versioned else _write_artifact
        writer(directory, model_ref, host_flat, config, step)


def _batch_signature(batch: Dict[str, Any]) -> Tuple:
    """Hashable per-key (shape, dtype) of a feature batch — what a jitted
    predict executable is specialized to. Key-order independent."""
    return tuple(sorted(
        (k, tuple(np.shape(v)), str(getattr(v, "dtype", None)
                                    or np.asarray(v).dtype))
        for k, v in batch.items()
    ))


@dataclass
class InferenceModel:
    """A loaded serving artifact: rebuilt model + placed params.

    ``predict`` is thread-safe: the executable cache is keyed per batch
    aval under a lock, so a threaded frontend racing two first calls
    builds one executable, and distinct batch shapes each compile exactly
    once (counted as retraces past the first — the continuous-batching
    frontend's bucket ladder exists so that count stays flat)."""

    model: Any
    params: Any
    mesh: Mesh
    step: Optional[int]
    config: Dict[str, Any]

    def __post_init__(self):
        self._predict_lock = threading.Lock()
        self._predict_cache: Dict[Tuple, Any] = {}

    def predict(self, batch: Dict[str, np.ndarray]):
        """Jitted forward through the zoo model's ``predict`` entrypoint."""
        if self.model.predict is None:
            raise NotImplementedError(
                f"model {self.model.name!r} defines no predict entrypoint"
            )
        sig = _batch_signature(batch)
        with self._predict_lock:
            fn = self._predict_cache.get(sig)
            if fn is None:
                if self._predict_cache:
                    # a second shape means the caller is feeding unbucketed
                    # batches — each new shape pays a full trace+compile
                    _M_RETRACES.inc()
                    log.warning(
                        "predict retrace: new batch signature %s "
                        "(%d already cached) — pad to fixed buckets to "
                        "avoid per-shape compiles", sig,
                        len(self._predict_cache),
                    )
                mesh = self.mesh
                pred = self.model.predict
                fn = jax.jit(lambda params, b: pred(params, b, mesh))
                self._predict_cache[sig] = fn
        return fn(self.params, batch)


def _spec_axes(spec_tree) -> set:
    """Mesh axis names referenced anywhere in a PartitionSpec tree."""
    from jax.sharding import PartitionSpec

    names = set()
    for s in jax.tree_util.tree_leaves(
        spec_tree, is_leaf=lambda x: isinstance(x, PartitionSpec)
    ):
        if not isinstance(s, PartitionSpec):
            continue
        for part in s:
            if part is None:
                continue
            for ax in (part if isinstance(part, tuple) else (part,)):
                names.add(ax)
    return names


def _serving_mesh(model) -> Mesh:
    """Local mesh that satisfies every axis the model's specs name: all
    devices on the data axis, size-1 axes for anything else (e.g. a table's
    ``expert`` axis when serving single-host)."""
    from edl_tpu.parallel.mesh import local_mesh

    mesh = local_mesh()
    missing = _spec_axes(model.param_spec(mesh)) - set(mesh.axis_names)
    if missing:
        mesh = local_mesh(
            {"data": len(jax.devices()), **{ax: 1 for ax in sorted(missing)}}
        )
    return mesh


def load_inference_model(
    directory: str, mesh: Optional[Mesh] = None
) -> InferenceModel:
    """Rebuild the zoo model and place its weights for serving.

    Weights land on ``mesh`` per the model's ``param_spec`` (so a sharded
    embedding table reshards onto the serving mesh — any size, same as
    checkpoint restore). Default: all local devices on the data axis, plus
    size-1 axes for any other axis the model's specs shard over.
    """
    from edl_tpu import models as zoo

    directory = resolve_artifact_dir(directory)
    with open(os.path.join(directory, MANIFEST)) as f:
        manifest = json.load(f)
    if manifest.get("format") != _FORMAT:
        raise ValueError(f"unknown artifact format {manifest.get('format')!r}")
    _refuse_unservable(manifest["model"])
    npz = np.load(os.path.join(directory, manifest["weights"]))
    pairs = []
    for i, entry in enumerate(manifest["leaves"]):
        arr = npz[f"leaf_{i:05d}"]
        if entry["dtype"] == "bfloat16":
            from ml_dtypes import bfloat16

            arr = arr.view(bfloat16)
        pairs.append((tuple(map(tuple, entry["path"])), arr))
    host_params = _rebuild(pairs)

    model = zoo.resolve(manifest["model"], manifest.get("config") or None)
    mesh = mesh or _serving_mesh(model)
    from jax.sharding import PartitionSpec

    spec = model.param_spec(mesh)
    params = jax.device_put(
        host_params,
        jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s),
            spec,
            is_leaf=lambda x: isinstance(x, PartitionSpec),
        ),
    )
    return InferenceModel(
        model=model,
        params=params,
        mesh=mesh,
        step=manifest.get("step"),
        config=manifest.get("config") or {},
    )


class PeriodicExporter:
    """Periodic serving export (ref `ctr/train.py:169-180`:
    ``save_inference_model`` every N batches, trainer 0's duty). Plug into
    ``ElasticConfig.step_callback``.

    Every rank invokes it (the gather is collective over sharded params —
    the lockstep loop hits identical steps on all ranks); only the rank
    whose ``rank`` matches ``writer_rank`` writes files, and its file write
    runs on a background thread so the step loop only pays the
    device->host gather (the sibling checkpoint duty is async for the same
    reason). A new export first waits for the previous write — bounded (at
    most one write duration, which already overlapped a whole interval of
    training) and surfaces background write errors instead of losing them.
    """

    def __init__(
        self,
        directory: str,
        model_ref: str,
        interval: int,
        config: Optional[Dict[str, Any]] = None,
        rank: int = 0,
        writer_rank: int = 0,
        versioned: bool = False,
    ):
        self.directory = directory
        self.model_ref = model_ref
        self.interval = max(1, int(interval))
        self.config = config
        self.rank = rank
        self.writer_rank = writer_rank
        #: versioned=True: each export lands in its own v<step> dir and the
        #: atomic LATEST pointer advances only once the dir is complete —
        #: required when a serving tier's swap watcher polls this directory.
        self.versioned = versioned
        self.exports = 0
        #: high-water mark, not last-seen: a post-restore replay re-visits
        #: old step numbers, and re-exporting step 104 after publishing 148
        #: would hand a serving poller OLDER weights. Identical trajectory
        #: on every rank (lockstep steps), so the skip stays collective-safe.
        self._high_water = -1
        self._pool = None
        self._inflight = None

    def __call__(self, step: int, state) -> None:
        if step <= self._high_water or step % self.interval:
            return
        self._high_water = step
        # Collective on every rank — must run unconditionally (a rank-local
        # skip would leave peers stuck in the allgather); discarded off the
        # writer.
        host_flat = _gather_host(state.params)
        if self.rank != self.writer_rank:
            return
        self.wait()  # bounded; surfaces a failed previous write loudly
        if self._pool is None:
            from concurrent.futures import ThreadPoolExecutor

            self._pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="edl-export"
            )
        writer = _write_versioned if self.versioned else _write_artifact
        self._inflight = self._pool.submit(
            writer, self.directory, self.model_ref, host_flat,
            self.config, step,
        )
        self.exports += 1

    def wait(self) -> None:
        """Block until the in-flight write (if any) is durable; surfaces
        write errors (a background failure would otherwise be silent)."""
        if self._inflight is not None:
            self._inflight.result()
