"""Persistent AOT compile cache: revisiting a layout costs zero compiles.

An elastic job walks a small set of layouts — ``{dcn:2, data:4}`` loses a
slice, becomes ``{data:6}``, the slice comes back, it returns to
``{dcn:2, data:4}`` — and before this module every return leg paid a full
XLA compile inside the recovery budget. ``Trainer.warm_compile`` already
AOT-compiles the step from abstract avals (so the executable is keyed by
*signatures*, not live data); this cache makes that executable durable:

- **key** = SHA-256 over (mesh topology + concrete device set, trainer/model
  configuration, batch avals, state avals, code fingerprint). Any drift in
  any component produces a different key — there is no "almost matches".
- **payload** = ``jax.experimental.serialize_executable`` bytes (the
  underlying PGLE-stable XLA executable serialization) plus the in/out
  trees, wrapped in a header carrying the code fingerprint and a payload
  checksum.
- **eviction** = verification at load time: a corrupted payload (checksum
  or unpickle failure) or a stale code fingerprint deletes the entry and
  counts a miss — the cache can only ever serve bytes written by the same
  code that is about to run them.

Two tiers: a process-local executable map (hot path for in-process
rescales, no deserialization) over the on-disk store (survives restarts —
the warm-restart path after RESCALE_EXIT_CODE lands on a ready executable).

Deserialized executables are dispatched exactly like freshly compiled ones
(``Trainer._warm_step``): direct AOT dispatch, never through the jit
dispatch cache — the retrace canary's "cache stays empty" discipline (PR 2)
holds bit-for-bit on a cache hit.

Metrics: ``edl_compile_cache_hits_total`` / ``edl_compile_cache_misses_total``
(tier-labelled) land in the process registry, so one scrape shows whether
recovery compiles are actually being amortized.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import pickle
import threading
from typing import Any, Dict, Optional

from edl_tpu.obs.metrics import get_registry

__all__ = ["CompileCache", "code_fingerprint"]

log = logging.getLogger("edl_tpu.runtime.compile_cache")

_HEADER_VERSION = 1

_fingerprint_lock = threading.Lock()
_fingerprint_cache: Optional[str] = None


def code_fingerprint() -> str:
    """Content hash of every ``edl_tpu`` Python source file.

    Coarse on purpose: any edit anywhere in the package invalidates the
    cache. False invalidations cost one recompile; a false HIT would run a
    stale executable against changed code — the asymmetry picks the coarse
    key. Computed once per process (the package cannot change under a
    running interpreter that already imported it).
    """
    global _fingerprint_cache
    with _fingerprint_lock:
        if _fingerprint_cache is not None:
            return _fingerprint_cache
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        h = hashlib.sha256()
        for dirpath, dirnames, filenames in os.walk(root):
            dirnames.sort()
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for fname in sorted(filenames):
                if not fname.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fname)
                h.update(os.path.relpath(path, root).encode())
                with open(path, "rb") as f:
                    h.update(f.read())
        _fingerprint_cache = h.hexdigest()[:16]
        return _fingerprint_cache


class CompileCache:
    """Two-tier (memory over disk) store of AOT-compiled step executables."""

    def __init__(self, directory: str, fingerprint: Optional[str] = None):
        self.directory = os.path.abspath(directory)
        os.makedirs(self.directory, exist_ok=True)
        #: overridable for tests (stale-fingerprint eviction without
        #: actually editing the package source).
        self.fingerprint = fingerprint or code_fingerprint()
        self._mem: Dict[str, Any] = {}
        self._lock = threading.Lock()
        r = get_registry()
        self.hits = r.counter(
            "edl_compile_cache_hits_total",
            "AOT step executables served from the compile cache",
            labelnames=("tier",),  # memory | disk
        )
        self.misses = r.counter(
            "edl_compile_cache_misses_total",
            "compile-cache lookups that had to fall through to XLA",
            labelnames=("reason",),  # absent | stale | corrupt
        )

    # -- keying ----------------------------------------------------------------

    def key(self, mesh, config_repr: str, batch_signature: Any,
            state_signature: Any) -> str:
        """Cache key for one (layout, program, avals) triple.

        The device list is part of the topology: a serialized executable is
        bound to the concrete devices it was compiled for, so the same
        logical ``{data: 4}`` on a different chip subset must miss.
        """
        topology = sorted((str(k), int(v)) for k, v in dict(mesh.shape).items())
        devices = sorted(
            (getattr(d, "platform", ""), int(getattr(d, "id", 0)))
            for d in mesh.devices.flat
        )
        blob = json.dumps(
            [
                _HEADER_VERSION,
                topology,
                devices,
                config_repr,
                repr(batch_signature),
                repr(state_signature),
                self.fingerprint,
            ],
            sort_keys=True,
        )
        return hashlib.sha256(blob.encode()).hexdigest()

    def _path(self, key: str) -> str:
        return os.path.join(self.directory, f"{key}.aot")

    # -- load ------------------------------------------------------------------

    def load(self, key: str, mesh) -> Optional[Any]:
        """Return a ready-to-dispatch executable for ``key`` or None.

        ``mesh`` is the mesh ``key`` was derived from: a stored executable
        is loaded onto exactly its devices, in its order (JAX would
        otherwise load it onto every device of the backend and refuse
        inputs placed on the mesh).

        Any defect in the stored entry — torn write, bit rot, a payload
        written by different code — evicts the entry and reports a miss;
        the caller compiles as if the cache were empty.
        """
        with self._lock:
            cached = self._mem.get(key)
        if cached is not None:
            self.hits.inc(tier="memory")
            return cached
        path = self._path(key)
        if not os.path.exists(path):
            self.misses.inc(reason="absent")
            return None
        try:
            with open(path, "rb") as f:
                header_line = f.readline()
                body = f.read()
            header = json.loads(header_line)
            if header.get("v") != _HEADER_VERSION:
                raise ValueError(f"unknown cache version {header.get('v')!r}")
            if header.get("fingerprint") != self.fingerprint:
                self._evict(path)
                self.misses.inc(reason="stale")
                log.info(
                    "compile-cache entry %s written by different code "
                    "(%s != %s); evicted", key[:12],
                    header.get("fingerprint"), self.fingerprint)
                return None
            if hashlib.sha256(body).hexdigest() != header.get("sha256"):
                raise ValueError("payload checksum mismatch")
            payload, in_tree, out_tree = pickle.loads(body)
            from jax.experimental import serialize_executable

            compiled = serialize_executable.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=list(mesh.devices.flat))
        except Exception as e:  # edl: noqa[EDL005] any unreadable/undeserializable entry (torn write, jax version drift, device set gone) must evict and demote to a normal compile, never fail the rescale
            self._evict(path)
            self.misses.inc(reason="corrupt")
            log.warning("compile-cache entry %s unreadable (%s); evicted",
                        key[:12], e)
            return None
        with self._lock:
            self._mem[key] = compiled
        self.hits.inc(tier="disk")
        return compiled

    # -- store -----------------------------------------------------------------

    def store(self, key: str, compiled: Any) -> bool:
        """Persist ``compiled`` under ``key`` (memory + disk). Returns False
        when the executable is not serializable on this backend — the
        memory tier still serves it for the life of the process."""
        with self._lock:
            self._mem[key] = compiled
        try:
            from jax.experimental import serialize_executable

            payload, in_tree, out_tree = serialize_executable.serialize(
                compiled)
            body = pickle.dumps((payload, in_tree, out_tree))
        except Exception as e:  # edl: noqa[EDL005] serialization support varies by backend/executable; an unserializable program degrades to memory-tier caching, it must not fail warm_compile
            log.warning("compile-cache: executable not serializable (%s); "
                        "memory tier only", e)
            return False
        header = json.dumps({
            "v": _HEADER_VERSION,
            "fingerprint": self.fingerprint,
            "sha256": hashlib.sha256(body).hexdigest(),
            "bytes": len(body),
        }).encode()
        path = self._path(key)
        tmp = f"{path}.tmp.{os.getpid()}"
        try:
            with open(tmp, "wb") as f:
                f.write(header + b"\n" + body)
            os.replace(tmp, path)  # atomic: readers see whole entries only
        except OSError as e:
            log.warning("compile-cache: write to %s failed (%s)", path, e)
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return False
        return True

    # -- maintenance -----------------------------------------------------------

    def _evict(self, path: str) -> None:
        try:
            os.unlink(path)
        except OSError:
            pass

    def entries(self) -> int:
        """On-disk entry count (tests/bench bookkeeping)."""
        try:
            return sum(1 for n in os.listdir(self.directory)
                       if n.endswith(".aot"))
        except OSError:
            return 0

    def clear_memory(self) -> None:
        """Drop the process-local tier (tests exercising the disk path)."""
        with self._lock:
            self._mem.clear()
