"""The program's own spans in a profile, and the reductions the readers of
``engine.prefill_wait_ms`` and ``device_idle.<phase>`` make from them.

While the profiler records, the engine's phase spans (``engine.admit``,
``engine.prefill``, ``engine.retire``, ...) sit on the host plane. The
device's events come on a clock of their own: on a TPU v5e a program
appears to start up to about a millisecond before the host call that
launched it. So host and device are joined by order, not by time:

- the device runs programs in the order the host launched them, and the
  n-th ``engine.prefill`` span of a profile launched its n-th prefill
  execution: the engine fetches each admission's first token before it
  goes on, and the benchmark starts and stops the profile between steps,
  so no prefill is in flight at either end;
- each prefill span holds one host launch (``PJRT_LoadedExecutable_Execute``),
  which ties the profile's launches to its executions in order; the least
  time from a launch to the start of its execution (the clock offset plus
  the fastest launch) puts the device's times on the host's clock.

A profile that holds no prefill span, or whose prefill spans and
executions do not pair one to one, reads as None, never as 0. Times are in
seconds.
"""
from __future__ import annotations

import bisect

from bench.trace import _union

ADMIT = "engine.admit"
PREFILL = "engine.prefill"
RETIRE = "engine.retire"
LAUNCH = "PJRT_LoadedExecutable_Execute"


def host_spans(trace, name: str) -> list:
    """(start, end) of every host span named ``name``, in order."""
    return sorted((s, e) for s, e, n in trace.host if n == name)


def joined(trace, program: str, dev: int):
    """(pairs, offset): each ``engine.prefill`` span's start and the start
    of the execution of ``program`` on ``dev`` that it launched, and the
    device's clock minus the host's (the least launch-to-start time); None
    when the profile does not pair them one to one."""
    spans = host_spans(trace, PREFILL)
    runs = sorted(s for s, _ in trace.modules_matching(program, dev))
    if not spans or len(spans) != len(runs):
        return None
    launches = sorted(s for s, _, n in trace.host if n == LAUNCH)
    starts = sorted(s for s, _, _ in trace.modules.get(dev, []))
    shifts = set()
    for (s, e), r in zip(spans, runs):
        i = bisect.bisect_left(launches, s)
        if i == len(launches) or launches[i] > e:
            return None
        shifts.add(bisect.bisect_left(starts, r) - i)
    if len(shifts) != 1:
        return None
    (k,) = shifts
    offset = min(starts[j + k] - launches[j]
                 for j in range(max(0, -k), min(len(launches),
                                                len(starts) - k)))
    return [(s, r) for (s, _), r in zip(spans, runs)], offset


def waits(trace, program: str, dev: int):
    """For each ``engine.prefill`` span: from its start to the start of
    the prefill it launched, on the host's clock; None as ``joined``."""
    j = joined(trace, program, dev)
    if j is None:
        return None
    pairs, offset = j
    return [r - offset - s for s, r in pairs]


def _intersection(a: list, b: list) -> float:
    """Length covered by both of two sorted lists of disjoint intervals."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def idle_under(trace, span: str, dev: int, offset: float):
    """Seconds in which the host is inside a span named ``span`` and no
    operation runs on ``dev``, within the first and last operation on
    ``dev``, with device times moved onto the host's clock by ``offset``
    (``joined``'s); None when the profile holds no such span or no
    operation."""
    spans = _union(host_spans(trace, span))
    busy = _union((s - offset, e - offset)
                  for s, e, _ in trace.ops.get(dev, []))
    if not spans or not busy:
        return None
    extent = [[busy[0][0], busy[-1][1]]]
    return _intersection(spans, extent) - _intersection(spans, busy)


def idle_share(ctx, span: str):
    """``idle_under`` over the traced slice, in %: the reading of
    ``device_idle.<phase>``; None as ``joined`` and ``idle_under``."""
    if not ctx.devices or not ctx.window_s:
        return None
    dev = ctx.devices[0]
    j = joined(ctx.trace, ctx.names["prefill_module"], dev)
    idle = None if j is None else idle_under(ctx.trace, span, dev, j[1])
    return None if idle is None else 100.0 * idle / ctx.window_s
