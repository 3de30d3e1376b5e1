"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device busy intervals, per-program device time, the
benchmark's own host spans, and the breakdown of device operations and
idle gaps.

Device planes are named ``/device:<KIND>:<n>``; their ``XLA Ops`` line
holds one event per operation run on the device and their ``XLA Modules``
line one event per program execution, named after the jitted function
(``jit_serve_step``, ``jit__prefill``). Host spans are the events named
``bench.*`` on any host line.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Trace:
    """Events as (name, start_ns, end_ns), per device for ops and modules."""

    ops: dict[str, list]          # device plane -> op events
    modules: dict[str, list]      # device plane -> program events
    spans: list                   # the benchmark's host spans
    window: tuple[float, float]   # traced window, ns

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def _events(line):
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def from_profile(profile) -> Trace:
    """Reduce a ``jax.profiler.ProfileData`` (or anything shaped like it:
    planes with ``name`` and ``lines``, lines with ``name`` and ``events``,
    events with ``name``, ``start_ns`` and ``duration_ns``)."""
    ops, modules, spans = {}, {}, []
    for plane in profile.planes:
        lines = {line.name: line for line in plane.lines}
        if plane.name.startswith("/device:"):
            if OPS_LINE in lines:
                ops[plane.name] = _events(lines[OPS_LINE])
            if MODULES_LINE in lines:
                modules[plane.name] = _events(lines[MODULES_LINE])
        elif plane.name.startswith("/host:"):
            for line in lines.values():
                spans += [e for e in _events(line)
                          if e[0].startswith(SPAN_PREFIX)]
    ops = {k: v for k, v in ops.items() if v}
    modules = {k: v for k, v in modules.items() if v}
    if spans:
        window = (min(s[1] for s in spans), max(s[2] for s in spans))
    else:
        every = [e for evs in ops.values() for e in evs]
        window = ((min(e[1] for e in every), max(e[2] for e in every))
                  if every else (0.0, 0.0))
    return Trace(ops, modules, spans, window)


def load(trace_dir: str) -> Trace:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` trace directory."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_profile(ProfileData.from_file(paths[-1]))


def clip(events, window):
    lo, hi = window
    return [(n, max(a, lo), min(b, hi)) for n, a, b in events
            if b > lo and a < hi]


def union(events) -> list[tuple[float, float]]:
    """Merged intervals covered by ``events``."""
    out: list[list[float]] = []
    for _, a, b in sorted(events, key=lambda e: e[1]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_s(trace: Trace, device: str) -> float:
    """Seconds of the traced window in which an operation ran on
    ``device``."""
    return sum(b - a for a, b in union(clip(trace.ops[device],
                                            trace.window))) * 1e-9


def mean_busy_s(trace: Trace) -> float:
    """Busy seconds averaged over the devices that ran operations."""
    if not trace.ops:
        return 0.0
    return sum(busy_s(trace, d) for d in trace.ops) / len(trace.ops)


_SUFFIX = re.compile(r"[.(\[].*$")


def program_name(module: str) -> str:
    """``jit_serve_step(123)`` -> ``jit_serve_step``."""
    return _SUFFIX.sub("", module)


def program_times(trace: Trace, program: str) -> list[float]:
    """Device seconds of each execution of ``program`` (every device),
    for executions that start inside the traced window."""
    lo, hi = trace.window
    return [(b - a) * 1e-9 for evs in trace.modules.values()
            for name, a, b in evs
            if program_name(name) == program and lo <= a < hi]


_INSTR = re.compile(r"^%?([\w.\-]+) = ([a-z0-9]+\[[\d,]*\])?")
# Control-flow ops whose device interval holds the ops of their body.
_CONTAINERS = ("while", "conditional", "call")


def op_label(name: str) -> str:
    """``%fusion.3 = f32[8,128]{1,0} fusion(...)`` -> ``fusion.3 f32[8,128]``
    (an HLO instruction's name and result shape)."""
    m = _INSTR.match(name)
    if not m:
        return name[:80]
    return f"{m.group(1)} {m.group(2)}" if m.group(2) else m.group(1)


def top_ops(trace: Trace, n: int = 10) -> list[list]:
    """The ``n`` device operations that took most time in the window
    (summed over executions, averaged over devices), each labelled with
    the program it ran in; loops that hold other ops are left out."""
    tot: collections.Counter = collections.Counter()
    for dev, evs in trace.ops.items():
        mods = sorted(trace.modules.get(dev, []), key=lambda e: e[1])
        starts = [m[1] for m in mods]
        for name, a, b in clip(evs, trace.window):
            label = op_label(name)
            if label.split(".")[0] in _CONTAINERS:
                continue
            i = bisect.bisect_right(starts, a) - 1
            if i >= 0 and a < mods[i][2]:
                label = f"{program_name(mods[i][0])}/{label}"
            tot[label] += (b - a) * 1e-9
    k = max(len(trace.ops), 1)
    return [[name, s / k] for name, s in tot.most_common(n)]


def idle_gaps(trace: Trace, n: int = 10) -> list[list]:
    """The ``n`` longest idle gaps of the busiest device in the window,
    each named by the innermost benchmark span around its midpoint
    (``bench.step``, ``bench.admit``; ``bench.tick`` alone means the
    batcher's own loop; ``untraced`` where no span was open)."""
    if not trace.ops:
        return []
    dev = max(trace.ops, key=lambda d: busy_s(trace, d))
    busy = union(clip(trace.ops[dev], trace.window))
    lo, hi = trace.window
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])

    def label(mid):
        open_ = [s for s in trace.spans if s[1] <= mid < s[2]]
        if not open_:
            return "untraced"
        return min(open_, key=lambda s: s[2] - s[1])[0]

    return [[label((a + b) / 2), (b - a) * 1e-9] for a, b in gaps[:n]]
