"""From a profiler trace to the step's phases, its kernels by name, and the
device's idle gaps by what the host was doing: the reduction behind the
per-layer metrics that read names the PROGRAM puts into the trace (ISSUE 25).
Like ``trace_reduce.py``: pure functions over events, and one thin adapter
that reads them out of the ``.xplane.pb``. Times here are the file's own whole
picoseconds (``PS`` seconds each), so that an event which ends where the next
starts is seen to: in floating seconds a rounding nests neighbours.
``ReadContext.trace`` holds only the short name, start and duration of each
device operation and no path, so this module finds the file itself (one cell to a process: ``run.py``
clears the cell's work directory before and after its run), parses it once
and keeps the result here, at module level: ``run.load_module`` executes a
reader's file anew for every metric, but a reader imports this module
normally.

What the chip's trace holds (one v5e chip, JAX 0.9.0, my chip run, PR 25):
each event of a device's ``XLA Ops`` line points at an ``XEventMetadata`` whose
``name`` is the instruction's text and whose STATS hold what the compiler knew
of it: ``tf_op`` is the HLO metadata's op_name with a ``:`` after it
(``jit(_step)/fwd_bwd/jvp()/while/body/closed_call/mlp/bsf,fd->bsd/dot_general:``),
beside ``hlo_category``, ``flops``, ``bytes_accessed`` and ``source``. The
event's own stats are only its device offset and duration, and
``jax.profiler.ProfileData`` shows an event's own stats and not its
metadata's, so the adapter here reads the file's wire format itself (six
messages of ``xplane.proto``; no protobuf package is needed). In an op_name
JAX writes the backward pass as ``transpose(fwd_bwd)/jvp()`` after the
program's ``fwd_bwd`` scope, the body of ``jax.checkpoint`` evaluated there
as ``checkpoint`` and the forward recomputed in it as
``checkpoint/rematted_computation``. A ``while`` carries no op_name, and none
is needed: with the file's picosecond offsets its self time is 0.03 ms of a
1.44 s loop (``run.py``'s ``breakdown`` reads nanoseconds rounded by
``ProfileData``, lets neighbours nest by a rounding, and so gave the two
``while`` 0.22 s of self time they do not have). A Pallas kernel's ``name=``
names the instruction (``%flash_fwd.16``, ``%flash_bwd_dq.9``) and the
``jax.named_scope`` of the same name is in its op_name: both routes arrive.
The host plane is ``/host:CPU``, one line a thread (the worker's and the pump's
are both called ``python``); a mirrored ``Tracer`` span is an event named as
the span, the worker's step marker an event ``train`` with a ``step_num``
stat (the device's ``Steps`` line counts from 0 on its own). Device and host
lines share one time base, but the device's stamps run early: a program
starts on the device 0.4 to 0.75 ms before the host line shows its enqueue,
so a gap's attribution is good to about a millisecond.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
PS = 1e-12  # seconds in a picosecond


@dataclass(frozen=True)
class Op:
    """One executed instruction on a device's ``XLA Ops`` line."""

    name: str      # the instruction's short name: ``flash_fwd.16``
    op_name: str   # its metadata's op_name, the scopes the program named
    start: int     # picoseconds
    dur: int

    @property
    def end(self) -> int:
        return self.start + self.dur


Host = Tuple[str, int, int]  # an event of a host line: name, start, duration

# -- scopes and phases --------------------------------------------------------------


def under(op: Op, scope: str) -> bool:
    """The program named ``op`` under ``scope``: by a ``jax.named_scope``
    (a component of its op_name) or by the kernel's own ``name`` (the
    instruction is ``<scope>.<n>``): either route is enough."""
    return scope in op.op_name.split("/") \
        or op.name.rsplit(".", 1)[0] == scope


PHASES = ("forward", "recompute", "backward", "optimizer")


def phase_of(op_name: str) -> str:
    """The train step's phase an operation belongs to, read off its op_name
    as JAX builds it: ``optimizer`` and ``fwd_bwd`` are the program's own
    scopes (``runtime/train_loop.py::_step``); inside ``fwd_bwd`` JAX marks
    the backward pass ``transpose(...)`` (and the body of a
    ``jax.checkpoint`` evaluated there ``checkpoint``) and the forward
    recomputed in it ``rematted_computation``. XLA drops the head of some
    operations' names (``checkpoint/rematted_computation/reduce_sum``), so
    the markers count wherever they stand. Anything else is ``other``."""
    parts = op_name.split("/")
    if "optimizer" in parts:
        return "optimizer"
    if "rematted_computation" in parts:
        return "recompute"
    if "checkpoint" in parts or any(p.startswith("transpose(") for p in parts):
        return "backward"
    if "fwd_bwd" in parts:
        return "forward"
    return "other"


def self_times(ops: Sequence[Op]) -> List[Tuple[Op, int]]:
    """Every event with its own time: its duration less the events nested
    in it (a ``while`` holds its body's operations), so that the times sum
    to the busy time of a line whose events nest and never cross."""
    out: List[Tuple[Op, int]] = []
    stack: List[List] = []  # [op, own time]

    def close(upto: float) -> None:
        while stack and stack[-1][0].end <= upto:
            op, own = stack.pop()
            out.append((op, max(own, 0)))

    for op in sorted(ops, key=lambda o: (o.start, -o.dur)):
        close(op.start)
        if stack:
            stack[-1][1] -= min(op.dur, stack[-1][0].end - op.start)
        stack.append([op, op.dur])
    close(float("inf"))
    return out


def time_by(ops: Sequence[Op], key: Callable[[Op], str]) -> Dict[str, int]:
    """Self time summed by ``key(op)``."""
    total: Dict[str, int] = {}
    for op, own in self_times(ops):
        k = key(op)
        total[k] = total.get(k, 0) + own
    return total


def scope_calls(ops: Sequence[Op], scope: str) -> List[int]:
    """The device time of each call under ``scope``. A call is a run of
    consecutive events under the scope, with what nests in them: one kernel
    launch, or the handful of operations that implement the scope where no
    kernel does. The time is what its events cover."""
    calls: List[int] = []
    run: List[Op] = []
    run_end = 0
    for op in sorted(ops, key=lambda o: (o.start, -o.dur)):
        if under(op, scope):
            run.append(op)
            run_end = max(run_end, op.end)
        elif run and op.start >= run_end:  # not nested in the run: it is over
            calls.append(trace_reduce.union_seconds(
                [(o.name, o.start, o.dur) for o in run]))
            run, run_end = [], 0
    if run:
        calls.append(trace_reduce.union_seconds(
            [(o.name, o.start, o.dur) for o in run]))
    return calls


def steps_with(ops: Sequence[Op], modules: Sequence[Host], scope: str) -> int:
    """How many program runs (events of the ``XLA Modules`` line) hold an
    operation under ``scope``: the traced steps."""
    starts = sorted(op.start for op in ops if under(op, scope))
    n = 0
    for _, start, dur in modules:
        n += any(start <= s < start + dur for s in starts)
    return n


# -- idle gaps and the host ---------------------------------------------------------


def gaps(ops: Sequence[Op]) -> List[Tuple[int, int]]:
    """Every interval inside the span of ``ops`` that no event covers, as
    (start, end): the device idle."""
    out, end = [], None
    for op in sorted(ops, key=lambda o: o.start):
        if end is not None and op.start > end:
            out.append((end, op.start))
        end = op.end if end is None else max(end, op.end)
    return out


def attribute(idle: Sequence[Tuple[int, int]], host: Sequence[Host]
              ) -> Tuple[Dict[str, int], List[Tuple[int, str]]]:
    """Idle time by the host span it falls in, and each gap with the span
    that holds most of it. Where spans nest or overlap (the pump's
    beside the worker's) the innermost wins: the shortest span that covers
    the moment. What no span covers goes to ``none``."""
    by_span: Dict[str, int] = {}
    labelled: List[Tuple[int, str]] = []
    for a, b in idle:
        over = [(n, s, s + d) for n, s, d in host if s < b and s + d > a]
        cuts = sorted({a, b, *(t for _, s, e in over for t in (s, e)
                               if a < t < b)})
        mine: Dict[str, int] = {}
        for lo, hi in zip(cuts, cuts[1:]):
            covering = [(e - s, n) for n, s, e in over if s <= lo and e >= hi]
            name = min(covering)[1] if covering else "none"
            mine[name] = mine.get(name, 0) + hi - lo
        for name, took in mine.items():
            by_span[name] = by_span.get(name, 0) + took
        labelled.append((b - a, max(mine, key=mine.get)))
    return by_span, sorted(labelled, reverse=True)


# -- the adapter --------------------------------------------------------------------
#
# The fields of tsl/profiler/protobuf/xplane.proto that are read here, by
# number: XSpace.planes 1; XPlane.name 2, .lines 3, .event_metadata 4 and
# .stat_metadata 5 (maps: key 1, value 2); XLine.name 2, .timestamp_ns 3,
# .events 4; XEvent.metadata_id 1, .offset_ps 2, .duration_ps 3;
# XEventMetadata.name 2, .stats 5; XStat.metadata_id 1, .str_value 5;
# XStatMetadata.name 2.


def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one serialized message:
    an int for a varint, the bytes for a length-delimited field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):  # fixed 64 and 32: a double, a float
            size = 8 if kind == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {kind} in an xplane")
        yield key >> 3, value


def _map_entry(buf) -> Tuple[int, bytes]:
    key, value = 0, b""
    for num, got in _fields(buf):
        if num == 1:
            key = got
        elif num == 2:
            value = got
    return key, value


def _first(buf, number: int, default=b""):
    return next((v for n, v in _fields(buf) if n == number), default)


@dataclass
class ScopedTrace:
    """What the readers need of one trace."""

    devices: Dict[str, List[Op]] = field(default_factory=dict)
    modules: Dict[str, List[Host]] = field(default_factory=dict)
    #: every event of every host line, by name (the program's spans are
    #: picked out of them by the names its ``Tracer`` recorded)
    host: List[Host] = field(default_factory=list)

    def host_spans(self, names) -> List[Host]:
        names = set(names)
        return [h for h in self.host if h[0] in names]


def from_xspace(data: bytes) -> ScopedTrace:
    """The device and host planes of one serialized ``XSpace``."""
    trace = ScopedTrace()
    for num, plane in _fields(memoryview(data)):
        if num != 1:
            continue
        name = str(_first(plane, 2), "utf-8")
        device = bool(trace_reduce.DEVICE_PLANE.match(name))
        if not device and not name.startswith("/host:"):
            continue
        stat_names, metadata, lines = {}, {}, []
        for num, value in _fields(plane):
            if num == 3:
                lines.append(value)
            elif num == 4:
                metadata.__setitem__(*_map_entry(value))
            elif num == 5:
                key, meta = _map_entry(value)
                stat_names[key] = str(_first(meta, 2), "utf-8")

        def describe(meta) -> Tuple[str, str]:
            """An event's name, and the op_name of its metadata."""
            name, op_name = "", ""
            for num, value in _fields(meta):
                if num == 2:
                    name = str(value, "utf-8")
                elif num == 5 and device:
                    if stat_names.get(_first(value, 1, 0)) == "tf_op":
                        op_name = str(_first(value, 5), "utf-8")
            return name, op_name.rsplit(":", 1)[0]

        described = {key: describe(meta) for key, meta in metadata.items()}
        for line in lines:
            line_name = str(_first(line, 2), "utf-8")
            if device and line_name not in (trace_reduce.OP_LINE, "XLA Modules"):
                continue
            t0 = _first(line, 3, 0) * 1000  # timestamp_ns
            events = []
            for num, event in _fields(line):
                if num == 4:
                    got = dict(_fields(event))
                    events.append((described.get(got.get(1, 0), ("", "")),
                                   t0 + got.get(2, 0), got.get(3, 0)))
            if not device:
                trace.host.extend((n, s, d) for (n, _), s, d in events)
            elif line_name == "XLA Modules":
                trace.modules[name] = [(n, s, d) for (n, _), s, d in events]
            else:
                trace.devices[name] = [
                    Op(trace_reduce.short_name(n), op_name, s, d)
                    for (n, op_name), s, d in events]
    return trace


_LOADED: Dict[str, ScopedTrace] = {}


def current() -> Optional[ScopedTrace]:
    """The trace of this process's run, parsed once; None where there is
    none."""
    path = trace_reduce.find_xplane(os.path.join(HERE, ".work", "*", "trace"))
    if path is None:
        return None
    if path not in _LOADED:
        _LOADED.clear()
        with open(path, "rb") as f:
            _LOADED[path] = from_xspace(f.read())
    return _LOADED[path]
