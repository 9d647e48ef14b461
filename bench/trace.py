"""From a profiler trace (``.xplane.pb``) to device busy and idle time,
device time by name pattern, and the exposed part of collective time.

A TPU plane (``/device:TPU:<i>``) holds one line of compiled programs
(``XLA Modules``) and one of the operations inside them (``XLA Ops``);
host planes hold what the host threads did. Name patterns live in
``names.json`` beside this file, so every reader matches the same way.
Times are in seconds.
"""
from __future__ import annotations

import bisect
import json
import pathlib
import re

NAMES = json.loads((pathlib.Path(__file__).parent / "names.json").read_text())


def _union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _length(merged) -> float:
    return sum(e - s for s, e in merged)


def short_name(name: str) -> str:
    """``%fusion.3 = f32[8,128]{...} fusion(...), ...`` -> ``%fusion.3
    fusion f32[8,128]``: an HLO operation's name, opcode and first
    result shape, as the trace prints operations in full."""
    m = _HLO.match(name)
    if not m:
        return name[:120]
    shape = _SHAPE.search(name)
    return " ".join(x for x in (m.group(1), m.group(2),
                                shape.group(1) if shape else "") if x)


_HLO = re.compile(r"^(%[\w.\-]+) = .*?\s([a-z][\w\-]*)\(")
_SHAPE = re.compile(r"= \(?([a-z0-9]+\[[^\]]*\])")


def _inside(merged, s) -> bool:
    """Whether point ``s`` lies in one of the sorted disjoint intervals."""
    i = bisect.bisect_right(merged, [s, float("inf")]) - 1
    return i >= 0 and merged[i][0] <= s <= merged[i][1]


def _overlap(merged, s, e) -> float:
    """Length of [s, e] covered by the sorted disjoint intervals."""
    tot = 0.0
    for a, b in merged:
        if b <= s:
            continue
        if a >= e:
            break
        tot += min(b, e) - max(a, s)
    return tot


class Trace:
    """Events of one trace: per device, (start, end, name) of every
    operation and every program execution; and the host's events."""

    def __init__(self, ops: dict, modules: dict, host: list):
        self.ops = ops          # device index -> [(start, end, name)]
        self.modules = modules  # device index -> [(start, end, name)]
        self.host = host        # [(start, end, name)]

    @classmethod
    def load(cls, path) -> "Trace":
        from jax.profiler import ProfileData

        data = ProfileData.from_file(str(path))
        dev = re.compile(NAMES["device_plane"])
        ops, modules, host = {}, {}, []
        for plane in data.planes:
            m = dev.match(plane.name)
            for line in plane.lines:
                evs = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns)
                        * 1e-9, e.name) for e in line.events]
                if m is None:
                    if plane.name.startswith("/host:"):
                        host.extend(evs)
                    continue
                i = int(m.group(1))
                if line.name == NAMES["ops_line"]:
                    ops.setdefault(i, []).extend(evs)
                elif line.name == NAMES["modules_line"]:
                    modules.setdefault(i, []).extend(evs)
        return cls(ops, modules, host)

    @property
    def devices(self) -> list:
        return sorted(self.ops)

    def busy_s(self, dev: int) -> float:
        return _length(_union((s, e) for s, e, _ in self.ops.get(dev, [])))

    def mean_busy_s(self) -> float:
        devs = self.devices
        return sum(self.busy_s(d) for d in devs) / len(devs) if devs else 0.0

    def modules_matching(self, pattern: str, dev: int) -> list:
        rx = re.compile(pattern)
        return [(s, e) for s, e, n in self.modules.get(dev, [])
                if rx.search(n)]

    def op_time_in(self, pattern: str, dev: int, module: str) -> float:
        """Device time of the operations whose name matches ``pattern``
        and whose midpoint lies inside an execution of a program whose
        name matches ``module`` (overlaps counted once)."""
        spans = _union(self.modules_matching(module, dev))
        rx = re.compile(pattern)
        return _length(_union(
            (s, e) for s, e, n in self.ops.get(dev, [])
            if rx.search(n) and _inside(spans, (s + e) / 2)))

    def exposed_s(self, pattern: str, dev: int) -> tuple:
        """(time of the matching operations, the part of it during which
        no other operation runs on ``dev``)."""
        rx = re.compile(pattern)
        coll = _union((s, e) for s, e, n in self.ops.get(dev, [])
                      if rx.search(n))
        other = _union((s, e) for s, e, n in self.ops.get(dev, [])
                       if not rx.search(n))
        total = _length(coll)
        hidden = sum(_overlap(other, s, e) for s, e in coll)
        return total, total - hidden

    def top_ops(self, k: int = 10) -> list:
        """[name, seconds] of the operations that took most device time,
        averaged over the devices."""
        tot: dict = {}
        devs = self.devices
        for d in devs:
            for s, e, n in self.ops[d]:
                n = short_name(n)
                tot[n] = tot.get(n, 0.0) + (e - s) / len(devs)
        return [[n, t] for n, t in sorted(tot.items(),
                                          key=lambda x: -x[1])[:k]]

    def idle_gaps(self, k: int = 10, dev: int | None = None) -> list:
        """[what the host was doing, seconds] of the longest gaps between
        device operations on one device: the host event that covers most
        of the gap names it."""
        if not self.devices:
            return []
        dev = self.devices[0] if dev is None else dev
        busy = _union((s, e) for s, e, _ in self.ops[dev])
        gaps = sorted(((b[0] - a[1], a[1], b[0])
                       for a, b in zip(busy, busy[1:])), reverse=True)[:k]
        out = []
        for length, s, e in gaps:
            best, name = 0.0, "no host event"
            for hs, he, hn in self.host:
                cover = min(he, e) - max(hs, s)
                if cover > best and he - hs < 10 * length + 1e-3:
                    best, name = cover, hn
            out.append([name, length])
        return out
