"""From a profiler trace to numbers: device busy and idle time, time by
operation, idle gaps. Pure functions over ``(name, start, duration)`` events
(seconds), and one thin adapter that reads them out of the ``.xplane.pb`` file
``jax.profiler`` writes. The trace itself never leaves the machine.

What the chip's trace calls things (one v5e chip, JAX 0.9.0, my chip run,
PR 24): the device plane is ``/device:TPU:0``; its line ``XLA Ops`` holds one
event per executed HLO instruction, nested ones (the body of a ``while``)
inside their parent's interval on the same line; ``XLA Modules`` holds one
event per program run and ``Steps`` one per step. An event's name is the
instruction's whole text, ``%fusion.306 = bf16[...] fusion(...)``, up to a
thousand characters. A Pallas kernel is ``%branch_0_fun.34 = ...
custom-call(...), custom_call_target="tpu_custom_call"`` with an empty
``kernel_metadata``: the kernel function's name is not in the trace.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]  # name, start (s), duration (s)

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"


def union_seconds(events: Sequence[Event]) -> float:
    """Seconds covered by at least one event."""
    busy, end = 0.0, None
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        stop = start + dur
        if end is None or start > end:
            busy += dur
            end = stop
        elif stop > end:
            busy += stop - end
            end = stop
    return busy


def span_seconds(events: Sequence[Event]) -> float:
    """First start to last end."""
    if not events:
        return 0.0
    return max(s + d for _, s, d in events) - min(s for _, s, _ in events)


def idle_gaps(events: Sequence[Event]) -> List[Tuple[str, float]]:
    """Every interval inside the span that no event covers, longest first,
    named by the operation that ends it. Naming what the host did in a gap
    needs host spans on the profiler's clock, which the program does not
    write yet: the label says so."""
    out, end = [], None
    for name, start, dur in sorted(events, key=lambda e: e[1]):
        if end is not None and start > end:
            out.append((f"unattributed, then {name}", start - end))
        end = start + dur if end is None else max(end, start + dur)
    return sorted(out, key=lambda g: -g[1])


def self_seconds(events: Sequence[Event]) -> Dict[str, float]:
    """Seconds by operation name, a nested event's time taken out of its
    parent's (a ``while`` holds its body's operations), so that the sum over
    names is the busy time of a line whose events nest and never cross."""
    total: Dict[str, float] = {}
    stack: List[List] = []  # [name, end, self seconds]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            total[name] = total.get(name, 0.0) + max(own, 0.0)

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return total


def matching_seconds(events: Sequence[Event], pattern: str) -> float:
    """Seconds covered by the events whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return union_seconds([e for e in events if rx.search(e[0])])


@dataclass
class DeviceTrace:
    """The operation events of each traced device, and what was seen on the
    way (for the dump that tells a builder what the trace calls things)."""

    devices: Dict[str, List[Event]] = field(default_factory=dict)
    seen: Dict[str, Dict[str, int]] = field(default_factory=dict)
    #: each custom call's whole text as the trace has it, by its short name
    custom_calls: Dict[str, str] = field(default_factory=dict)

    def per_device(self, fn) -> float:
        """``fn(events)`` averaged over the devices."""
        vals = [fn(ev) for ev in self.devices.values()]
        return sum(vals) / len(vals) if vals else 0.0

    @property
    def busy_s(self) -> float:
        return self.per_device(union_seconds)

    @property
    def window_s(self) -> float:
        return self.per_device(span_seconds)

    def breakdown(self, top: int = 10) -> dict:
        n = max(len(self.devices), 1)
        ops = sorted(self_seconds_by_device(self).items(),
                     key=lambda kv: -kv[1])[:top]
        gaps = [g for ev in self.devices.values() for g in idle_gaps(ev)]
        gaps.sort(key=lambda g: -g[1])
        return {"device_ops": [[k, v / n] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps[:top]]}


def self_seconds_by_device(trace: DeviceTrace) -> Dict[str, float]:
    total: Dict[str, float] = {}
    for ev in trace.devices.values():
        for name, sec in self_seconds(ev).items():
            total[name] = total.get(name, 0.0) + sec
    return total


CUSTOM_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(name: str) -> str:
    """``fusion.306`` of ``%fusion.306 = bf16[...] fusion(...)``, and
    ``branch_0_fun.34[tpu_custom_call]`` of a custom call: the instruction's
    name, which is the same in every run of one program, with the target."""
    target = CUSTOM_TARGET.search(name)
    head = name.split(" = ", 1)[0].lstrip("%")
    return f"{head}[{target.group(1)}]" if target else head


def find_xplane(logdir: str) -> Optional[str]:
    paths = sorted(glob.glob(
        os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def load(path: str) -> DeviceTrace:
    """Read the device planes of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(path))


def from_profile(profile) -> DeviceTrace:
    """The device planes of a ``jax.profiler.ProfileData``."""
    trace = DeviceTrace()
    for plane in profile.planes:
        lines = {}
        for line in plane.lines:
            events = list(line.events)
            lines[line.name] = len(events)
            if DEVICE_PLANE.match(plane.name) and line.name == OP_LINE:
                trace.devices[plane.name] = [
                    (short_name(e.name), e.start_ns * 1e-9,
                     e.duration_ns * 1e-9) for e in events]
                for e in events:
                    if CUSTOM_TARGET.search(e.name):
                        trace.custom_calls.setdefault(short_name(e.name),
                                                      e.name[:300])
        trace.seen[plane.name] = lines
    return trace
