"""Compact host->device wire format for training batches.

The reference streams minibatches to trainers from recordio files on local
disk (`example/ctr/ctr/train.py:221-227` downloads its shard first), so its
input path is never the bottleneck. On TPU the host->device hop is often the
narrowest link in the system (PCIe on a TPU VM), so the framework ships a transport codec: batches cross the wire in
the smallest dtype that preserves training semantics and are decoded on
device inside the jitted step, where the casts fuse into the first consumers
for free.

Encodings (chosen per key from an example batch):

- ``bf16``: float32/64 -> bfloat16. The models' matmuls already run bf16 on
  the MXU, so feature precision beyond bf16 never reaches the math.
- ``u8``:  non-negative ints < 256 (labels, small categoricals) -> uint8.
- ``u24``: non-negative ints < 2^24 (hashed sparse ids; CTR's vocab is
  1e6+1) -> 3 little-endian bytes, reassembled with shifts on device.
- ``raw``: anything else passes through.

``encode`` validates every batch against the chosen encoding (a later batch
overflowing the example's range raises instead of corrupting), so inference
from one example batch is safe.
"""

from __future__ import annotations

import json
import time
from dataclasses import dataclass
from typing import Any, Dict, Iterable, Optional

import jax.numpy as jnp
import numpy as np
from ml_dtypes import bfloat16 as np_bfloat16

__all__ = ["WireCodec", "WireOverflowError", "KVCodecChannel", "WireRestartRequired"]

_U24_MAX = (1 << 24) - 1

#: widths ordered narrow -> wide, for floor comparisons
_WIDTH_ORDER = {"u8": 0, "u24": 1, "bf16": 1, "raw": 2}


class WireOverflowError(ValueError):
    """A batch value exceeds the range of its negotiated wire encoding."""

    def __init__(self, key: str, message: str):
        super().__init__(message)
        self.key = key


@dataclass(frozen=True)
class _KeyCodec:
    encoding: str  # "raw" | "bf16" | "u8" | "u24"
    dtype: np.dtype  # original host dtype (decode target modulo width)


class WireCodec:
    """Per-key transport encodings inferred once, applied per batch."""

    def __init__(self, keys: Dict[str, _KeyCodec]):
        self.keys = keys

    # -- inference -------------------------------------------------------------

    @classmethod
    def infer(
        cls,
        example: Dict[str, np.ndarray],
        no_lossy_keys: Iterable[str] = (),
    ) -> "WireCodec":
        """Infer per-key encodings from one example batch.

        ``no_lossy_keys`` names keys whose values must cross the wire
        exactly — regression targets / sample weights consumed directly by a
        float32 loss, where the "precision beyond bf16 never reaches the
        math" rationale does not hold. Float keys in the set stay ``raw``;
        integer keys keep their u8/u24 encodings, which are exact (validated
        per batch) and therefore safe even for labels.
        """
        no_lossy = frozenset(no_lossy_keys)
        keys: Dict[str, _KeyCodec] = {}
        for name, arr in example.items():
            a = np.asarray(arr)
            if a.dtype in (np.float32, np.float64):
                if name in no_lossy:
                    keys[name] = _KeyCodec("raw", a.dtype)
                else:
                    keys[name] = _KeyCodec("bf16", a.dtype)
            elif np.issubdtype(a.dtype, np.integer) and a.size:
                lo, hi = int(a.min()), int(a.max())
                if lo >= 0 and hi < 256:
                    keys[name] = _KeyCodec("u8", a.dtype)
                elif lo >= 0 and hi <= _U24_MAX:
                    keys[name] = _KeyCodec("u24", a.dtype)
                else:
                    keys[name] = _KeyCodec("raw", a.dtype)
            else:
                keys[name] = _KeyCodec("raw", a.dtype)
        return cls(keys)

    # -- host side -------------------------------------------------------------

    def encode(self, batch: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        out: Dict[str, np.ndarray] = {}
        for name, arr in batch.items():
            kc = self.keys.get(name)
            a = np.asarray(arr)
            if kc is None or kc.encoding == "raw":
                out[name] = a
            elif kc.encoding == "bf16":
                out[name] = a.astype(np_bfloat16)
            elif kc.encoding == "u8":
                if a.size and (a.min() < 0 or a.max() > 255):
                    raise WireOverflowError(name, f"{name}: value outside u8 range")
                out[name] = a.astype(np.uint8)
            elif kc.encoding == "u24":
                if a.size and (a.min() < 0 or a.max() > _U24_MAX):
                    raise WireOverflowError(name, f"{name}: value outside u24 range")
                le = np.ascontiguousarray(a.astype("<i4"))
                out[name] = le.view(np.uint8).reshape(a.shape + (4,))[..., :3].copy()
            else:  # pragma: no cover
                raise ValueError(f"unknown encoding {kc.encoding}")
        return out

    # -- device side (jit-traceable) -------------------------------------------

    def decode(self, batch: Dict[str, Any]) -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        for name, arr in batch.items():
            kc = self.keys.get(name)
            if kc is None or kc.encoding == "raw":
                out[name] = arr
            elif kc.encoding == "bf16":
                out[name] = arr.astype(jnp.dtype(kc.dtype))
            elif kc.encoding == "u8":
                out[name] = arr.astype(jnp.dtype(kc.dtype))
            elif kc.encoding == "u24":
                b = arr.astype(jnp.int32)
                v = b[..., 0] | (b[..., 1] << 8) | (b[..., 2] << 16)
                out[name] = v.astype(jnp.dtype(kc.dtype))
            else:  # pragma: no cover
                raise ValueError(f"unknown encoding {kc.encoding}")
        return out

    def widen(self, key: str) -> "WireCodec":
        """Return a codec with ``key``'s int encoding one step wider
        (u8 -> u24 -> raw). Used to self-heal after a WireOverflowError when a
        later batch exceeds the example batch's range; float encodings never
        overflow. Raises KeyError for keys that cannot widen further."""
        kc = self.keys[key]
        if kc.encoding == "u8":
            wider = _KeyCodec("u24", kc.dtype)
        elif kc.encoding == "u24":
            wider = _KeyCodec("raw", kc.dtype)
        else:
            raise KeyError(f"{key}: encoding {kc.encoding!r} cannot widen")
        return WireCodec({**self.keys, key: wider})

    # -- cross-process agreement ----------------------------------------------

    def to_spec(self) -> str:
        """JSON wire-spec: enough for a peer process to rebuild the IDENTICAL
        codec (and therefore the identical decode-jit — multi-process SPMD
        requires every process to compile the same program)."""
        return json.dumps(
            {k: {"e": kc.encoding, "d": np.dtype(kc.dtype).str}
             for k, kc in sorted(self.keys.items())},
            sort_keys=True,
        )

    @classmethod
    def from_spec(cls, spec: str) -> "WireCodec":
        return cls({
            k: _KeyCodec(v["e"], np.dtype(v["d"]))
            for k, v in json.loads(spec).items()
        })

    def apply_floor(self, floor: Dict[str, str]) -> "WireCodec":
        """Return a codec whose int encodings are at least as wide as
        ``floor`` (key -> encoding). The floor records widths that previous
        incarnations learned the hard way (a batch overflowed), so a
        renegotiated codec cannot repeat the overflow."""
        keys = dict(self.keys)
        for k, enc in floor.items():
            kc = keys.get(k)
            if kc is None or kc.encoding in ("raw", "bf16"):
                continue
            if _WIDTH_ORDER.get(enc, 0) > _WIDTH_ORDER[kc.encoding]:
                keys[k] = _KeyCodec(enc, kc.dtype)
        return WireCodec(keys)

    def is_encoded(self, batch: Dict[str, Any]) -> bool:
        """True if ``batch`` looks wire-encoded (used to route jit variants)."""
        for name, kc in self.keys.items():
            if name in batch and kc.encoding != "raw":
                enc = batch[name].dtype
                if kc.encoding == "bf16":
                    return str(enc) == "bfloat16"
                return enc == np.uint8
        return False

    def wire_bytes(self, batch: Dict[str, np.ndarray]) -> int:
        return sum(int(np.asarray(v).nbytes) for v in self.encode(batch).values())


class WireRestartRequired(RuntimeError):
    """Multi-process codec agreement broke (a batch overflowed the negotiated
    codec, or rank 0 died before publishing one). In-place repair would
    desynchronize the gang (peers would keep the old decode-jit and mis-pair
    collectives), so every process must warm-restart and renegotiate — the
    same gang-restart path a rescale takes."""

    def __init__(self, key: str, message: Optional[str] = None):
        super().__init__(
            message
            or f"wire key {key!r} overflowed the negotiated codec; widened "
               "floor published — exit for gang warm-restart to renegotiate"
        )
        self.key = key


class KVCodecChannel:
    """Codec agreement for multi-process jobs, over the coordinator KV.

    Every process must jit the IDENTICAL decode program, so the codec cannot
    be inferred per-process from local batches (ranges differ; the jits would
    diverge and mis-pair collectives). Protocol:

    - rank 0 infers from its first batch, applies the persistent widen
      floor, and publishes the spec under an EPOCH-SCOPED key — a rescale
      (new epoch, possibly new rank 0) renegotiates from scratch;
    - other ranks poll that key and build the same codec;
    - an overflow on ANY rank raises that key's width in the (epoch-less)
      floor and triggers a gang warm-restart; the renegotiated codec starts
      from the floor, so the overflow cannot recur (u8 -> u24 -> raw, at
      most two restarts per key, ever).

    The reference's analog is static: every trainer got the same dense/sparse
    transport config stamped by the job parser (`pkg/jobparser.go:232-247`);
    here the agreement is negotiated once and pinned the same way.

    One KV key holds {"epoch": N, "spec": ...}: each incarnation's publish
    overwrites its predecessor's, so dead epochs never accumulate in the
    coordinator KV or its durable snapshots (the round-plan keys need
    explicit GC; this one is self-compacting).
    """

    SPEC_KEY = "edl/wire_codec"
    FLOOR_KEY = "edl/wire_floor"

    def __init__(self, client, epoch: int):
        self.client = client
        self.epoch = int(epoch)

    def floor(self) -> Dict[str, str]:
        raw = self.client.kv_get(self.FLOOR_KEY)
        return json.loads(raw) if raw else {}

    def publish(self, codec: "WireCodec") -> "WireCodec":
        """Rank 0: pin the (floored) codec for this epoch; returns it."""
        floored = codec.apply_floor(self.floor())
        self.client.kv_put(
            self.SPEC_KEY,
            json.dumps({"epoch": self.epoch, "spec": floored.to_spec()}),
        )
        return floored

    def fetch(self, timeout: float = 60.0) -> "WireCodec":
        """Ranks > 0: block until rank 0 publishes THIS epoch's codec.

        Heartbeats while polling — negotiation can outlast the coordinator's
        heartbeat TTL (rank 0 may be opening a cold shard), and a silent
        waiter would be TTL-evicted, bumping the epoch and restarting the
        gang for nothing. A timeout means rank 0 died pre-publish; recovery
        is the same gang warm-restart a rescale takes, so that is what the
        raised error demands.
        """
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            raw = self.client.kv_get(self.SPEC_KEY)
            if raw:
                msg = json.loads(raw)
                if int(msg.get("epoch", -1)) == self.epoch:
                    return WireCodec.from_spec(msg["spec"])
            self.client.heartbeat()
            time.sleep(0.05)
        raise WireRestartRequired(
            "",
            message=f"no wire codec published for epoch {self.epoch} within "
                    f"{timeout}s (rank 0 died pre-publish?) — exit for gang "
                    "warm-restart",
        )

    def raise_floor(self, key: str, encoding: str) -> None:
        """Record that ``key`` needs at least ``encoding`` before restarting.

        Read-modify-write is safe enough here: floors only ever widen, and
        the restart path re-applies them idempotently — a lost concurrent
        update costs at most one extra restart for the other key.
        """
        floor = self.floor()
        if _WIDTH_ORDER.get(encoding, 0) > _WIDTH_ORDER.get(floor.get(key, "u8"), -1):
            floor[key] = encoding
            self.client.kv_put(self.FLOOR_KEY, json.dumps(floor, sort_keys=True))
