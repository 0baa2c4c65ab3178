"""Reduction of a profiler trace to device busy time, kernel time and idle gaps.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but JAX
(``jax.profiler.ProfileData``).  Device planes are ``/device:TPU:<n>``; an
operation's interval is an event on the plane's ``XLA Ops`` line, and a
jitted program's is an event on its ``XLA Modules`` line.  The program's own
spans (perf_counter clock) are put on the trace's clock by a marker event
that the harness opens at a perf_counter instant it records.

* busy: the union of the operation intervals inside the window, per chip;
* operations are named ``<program>:<instruction>`` (``jit_block_query:%while.2``),
  the program being the jitted module the operation ran in;
* idle gaps: the stretches of the window with no operation running, each
  labelled by the innermost program span open on the host at its midpoint
  (``host.no_span`` when none is).
"""
from __future__ import annotations

import bisect
import heapq
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass
class DeviceTrace:
    """Device events per chip (name, start_ns, end_ns) and the marker instant."""

    ops: dict[str, list] = field(default_factory=dict)
    modules: dict[str, list] = field(default_factory=dict)
    markers: dict[str, int] = field(default_factory=dict)  # name -> start_ns


def load(path: str, marker_names=("bench.window_open",)) -> DeviceTrace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = DeviceTrace()
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    dest = out.ops if line.name == OPS_LINE else out.modules
                    dest[plane.name] = [
                        (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                        for e in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in marker_names and e.name not in out.markers:
                        out.markers[e.name] = int(e.start_ns)
    for chip, ops in out.ops.items():
        out.ops[chip] = _short_names(ops, out.modules.get(chip, []))
    return out


def _short_names(ops, modules) -> list:
    """HLO text -> ``<module>:<instruction>`` by the module running at the op's start."""
    mods = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in mods]
    out = []
    for name, s, e in ops:
        j = bisect.bisect_right(starts, s) - 1
        mod = mods[j][0].split("(")[0] if j >= 0 and s < mods[j][2] else "?"
        out.append((f"{mod}:{name.split(' = ')[0]}", s, e))
    return out


def union(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """Merged [start, end) intervals clipped to [lo, hi)."""
    merged: list[list[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for _, s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def gaps(busy: list[tuple[int, int]], lo: int, hi: int) -> list[tuple[int, int]]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def labels(times, spans) -> list[str]:
    """For each trace time, the innermost span (name, start, end, depth) open
    then: one sweep over the spans by start, the open ones in a heap by depth
    (closed ones leave it lazily; the times are visited in ascending order)."""
    by_start = sorted(spans, key=lambda sp: sp[1])
    open_: list = []  # (-depth, start order, end, name)
    out: dict[int, str] = {}
    i = 0
    for j in sorted(range(len(times)), key=lambda j: times[j]):
        t = times[j]
        while i < len(by_start) and by_start[i][1] <= t:
            name, _, end, depth = by_start[i]
            heapq.heappush(open_, (-depth, i, end, name))
            i += 1
        while open_ and open_[0][2] <= t:
            heapq.heappop(open_)
        out[j] = open_[0][3] if open_ else "host.no_span"
    return [out[j] for j in range(len(times))]


def reduce(tr: DeviceTrace, lo: int, hi: int, spans=(), top: int = 10) -> dict:
    """Busy and window seconds (mean over chips), top device ops, idle by label,
    and device seconds per jitted program name."""
    chips = sorted(tr.ops)
    if not chips:
        raise ValueError("the trace has no TPU operations")
    busy_ns, op_ns, idle_ns, module_ns = 0, {}, {}, {}
    for chip in chips:
        merged = union(tr.ops[chip], lo, hi)
        busy_ns += sum(e - s for s, e in merged)
        for name, s, e in tr.ops[chip]:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                op_ns[name] = op_ns.get(name, 0) + d
        idle = gaps(merged, lo, hi)
        for (s, e), key in zip(idle, labels([(s + e) // 2 for s, e in idle], spans)):
            idle_ns[key] = idle_ns.get(key, 0) + (e - s)
        for name, s, e in tr.modules.get(chip, []):
            d = min(e, hi) - max(s, lo)
            if d > 0:
                module_ns[name] = module_ns.get(name, 0) + d
    n = len(chips)

    def ranked(d):
        return [[k, v / n / 1e9] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "busy_s": busy_ns / n / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_ops": ranked(op_ns),
        "idle_gaps": ranked(idle_ns),
        "modules_s": {k: v / n / 1e9 for k, v in module_ns.items()},
    }


def program_spans(tracer, offset_ns: int) -> list:
    """The tracer's spans as (name, start, end, depth) on the trace clock;
    ``offset_ns`` is trace time minus perf_counter_ns."""
    base = tracer.epoch_ns + offset_ns
    return [
        (s.name, int(base + s.ts_us * 1e3), int(base + (s.ts_us + s.dur_us) * 1e3), s.depth)
        for s in tracer.spans
    ]
