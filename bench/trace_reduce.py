"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read.

The traced window is the host span ``bench.window`` that the harness
opens at the end of one step and closes at the end of a later one. From
each TPU's plane the reduction keeps the operations on its ``XLA Ops``
line, clipped to that window; from the host plane, the harness's spans
(names starting ``bench.``). From those it gives

- a device's busy seconds: the union of its operations' intervals;
- the window's idle gaps on a device, each labelled with the innermost
  harness span open on the host when the gap began;
- operation totals by name, and the time of operations whose name
  matches a pattern, with the part of it during which no other operation
  ran on that device. An operation is named by its HLO instruction and
  first result shape (``fusion.85 f32[1,64,1408,2048]``); the
  asynchronous ones (``Async XLA Ops``) count for a pattern's time but
  not for busy time.

``jax.profiler.ProfileData`` gives every event's start and duration in
nanoseconds on one clock for host and device planes.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
WINDOW = "bench.window"
SHAPE = re.compile(r"\(?([a-z0-9]+\[[0-9,]*\])")


def op_name(text: str) -> str:
    """An operation's short name and first result shape, from the HLO text
    that names it in the trace: ``%fusion.85 = (f32[1,64]{...}, ...) ...``
    gives ``fusion.85 f32[1,64]``."""
    head, _, rest = text.partition(" = ")
    m = SHAPE.match(rest)
    return head.lstrip("%") + (" " + m.group(1) if m else "")


def union(intervals: List[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[list] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(intervals: List[Interval]) -> float:
    return sum(b - a for a, b in union(intervals))


def minus(intervals: List[Interval], cover: List[Interval]) -> float:
    """Length of ``intervals`` not covered by ``cover``."""
    cover = union(cover)
    total = 0.0
    for a, b in union(intervals):
        left = b - a
        for c, d in cover:
            if d <= a or c >= b:
                continue
            left -= min(b, d) - max(a, c)
        total += left
    return total


@dataclasses.dataclass
class Reduced:
    window: Interval                         # ns, host clock
    ops: Dict[str, List[Tuple[str, float, float]]]   # device -> ops
    host: List[Tuple[str, float, float]]     # harness spans
    #: device -> asynchronous operations (copies, collectives started on
    #: one op and finished on another), which overlap the others
    async_ops: Dict[str, List[Tuple[str, float, float]]] = \
        dataclasses.field(default_factory=dict)

    @property
    def devices(self) -> List[str]:
        return sorted(self.ops, key=lambda d: int(DEVICE_PLANE.match(d)[1]))

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self, device: str) -> float:
        return length([(a, b) for _, a, b in self.ops[device]]) * 1e-9

    def idle_share(self, device: str) -> float:
        return 1.0 - self.busy_s(device) / self.window_s

    def op_s(self, device: str, pattern: str) -> Tuple[float, float]:
        """(seconds of operations whose name matches ``pattern`` at its
        start, synchronous or asynchronous, and the part of them during
        which no other synchronous operation ran on the device)."""
        rx = re.compile(pattern)
        hit = [(a, b) for n, a, b in
               self.ops[device] + self.async_ops.get(device, [])
               if rx.match(n)]
        other = [(a, b) for n, a, b in self.ops[device] if not rx.match(n)]
        return length(hit) * 1e-9, minus(hit, other) * 1e-9

    def top_ops(self, n: int) -> List[list]:
        """The ``n`` operation names with the most device time, seconds
        averaged over the devices."""
        tot: Dict[str, float] = defaultdict(float)
        for dev in self.ops:
            for name, a, b in self.ops[dev]:
                tot[name] += (b - a) * 1e-9 / len(self.ops)
        return [[k, v] for k, v in sorted(tot.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def gaps(self, device: str) -> List[Interval]:
        busy = union([(a, b) for _, a, b in self.ops[device]])
        out, t = [], self.window[0]
        for a, b in busy:
            if a > t:
                out.append((t, a))
            t = max(t, b)
        if self.window[1] > t:
            out.append((t, self.window[1]))
        return out

    def host_span_at(self, t: float) -> str:
        """The innermost harness span open on the host at ``t``."""
        best, best_len = "none", float("inf")
        for name, a, b in self.host:
            if a <= t < b and name != WINDOW and b - a < best_len:
                best, best_len = name, b - a
        return best

    def idle_gaps(self, n: int) -> List[list]:
        """The ``n`` longest idle gaps on any device, in seconds, each
        named by what the host was doing when it began."""
        gaps = [(b - a, a) for dev in self.ops for a, b in self.gaps(dev)]
        gaps.sort(reverse=True)
        return [[self.host_span_at(a), g * 1e-9] for g, a in gaps[:n]]


def reduce_profile(profile) -> Reduced:
    host, ops, async_ops = [], {}, {}
    keep = {OPS_LINE: ops, ASYNC_LINE: async_ops}
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name in keep:
                    keep[line.name][plane.name] = [
                        (op_name(e.name), e.start_ns,
                         e.start_ns + e.duration_ns) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append((e.name, e.start_ns,
                                     e.start_ns + e.duration_ns))
    windows = [(a, b) for name, a, b in host if name == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(windows)}")
    if not ops:
        raise ValueError("the trace has no TPU operations")
    w0, w1 = windows[0]

    def clip(evs):
        return [(n, max(a, w0), min(b, w1)) for n, a, b in evs
                if b > w0 and a < w1]
    return Reduced((w0, w1), {d: clip(v) for d, v in ops.items()}, host,
                   {d: clip(async_ops.get(d, [])) for d in ops})


def find_profile(directory) -> str:
    found = glob.glob(os.path.join(str(directory), "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise ValueError(f"expected one .xplane.pb under {directory}, "
                         f"found {found}")
    return found[0]
