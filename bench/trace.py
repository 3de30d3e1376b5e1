"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device busy intervals, per-program device time, the
benchmark's and the program's host spans, each operation's scope, and the
breakdown of device operations and idle gaps.

Device planes are named ``/device:<KIND>:<n>``; their ``XLA Ops`` line
holds one event per operation run on the device and their ``XLA Modules``
line one event per program execution, named after the jitted function
and its program id (``jit_serve_step(7)``). Host spans are the events
named ``bench.*`` on any host line; the program's own spans are the
other host events named ``<layer>.<part>`` (``batcher.tick``,
``serve.sync``). An operation's scope is its ``op_name``
(``jit(serve_step)/while/body/attention/dot_general``), which the
profiler keeps as stat ``tf_op`` of the operation's event metadata.
``jax.profiler.ProfileData`` does not expose event metadata, so
``op_scopes`` reads those fields from the file itself.
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
PROGRAM_SPAN = re.compile(r"^[a-z][a-z0-9_]*\.[a-z][a-z0-9_]*$")


@dataclasses.dataclass
class Trace:
    """Events as (name, start_ns, end_ns), per device for ops and modules."""

    ops: dict[str, list]          # device plane -> op events
    modules: dict[str, list]      # device plane -> program events
    spans: list                   # the benchmark's host spans
    window: tuple[float, float]   # traced window, ns
    program_spans: list = dataclasses.field(default_factory=list)
    # device plane -> {(program id, op name): the op's tf_op}
    scopes: dict = dataclasses.field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def _events(line):
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def from_profile(profile, scopes: dict | None = None) -> Trace:
    """Reduce a ``jax.profiler.ProfileData`` (or anything shaped like it:
    planes with ``name`` and ``lines``, lines with ``name`` and ``events``,
    events with ``name``, ``start_ns`` and ``duration_ns``); ``scopes``
    is what ``op_scopes`` read from the same profile."""
    ops, modules, spans, program = {}, {}, [], []
    for plane in profile.planes:
        lines = {line.name: line for line in plane.lines}
        if plane.name.startswith("/device:"):
            if OPS_LINE in lines:
                ops[plane.name] = _events(lines[OPS_LINE])
            if MODULES_LINE in lines:
                modules[plane.name] = _events(lines[MODULES_LINE])
        elif plane.name.startswith("/host:"):
            for line in lines.values():
                for e in _events(line):
                    if e[0].startswith(SPAN_PREFIX):
                        spans.append(e)
                    elif PROGRAM_SPAN.match(e[0]):
                        program.append(e)
    ops = {k: v for k, v in ops.items() if v}
    modules = {k: v for k, v in modules.items() if v}
    if spans:
        window = (min(s[1] for s in spans), max(s[2] for s in spans))
    else:
        every = [e for evs in ops.values() for e in evs]
        window = ((min(e[1] for e in every), max(e[2] for e in every))
                  if every else (0.0, 0.0))
    return Trace(ops, modules, spans, window, program, scopes or {})


def load(trace_dir: str) -> Trace:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` trace directory."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    with open(paths[-1], "rb") as f:
        raw = f.read()
    return from_profile(ProfileData.from_serialized_xspace(raw),
                        op_scopes(raw))


# ---------------------------------------------------------------------------
# Event metadata, read from the serialized XSpace
# ---------------------------------------------------------------------------
# Field numbers of tensorflow/tsl's xplane.proto: XSpace.planes 1;
# XPlane.name 2, .event_metadata 4, .stat_metadata 5 (maps: key 1, value
# 2); XEventMetadata.name 2, .display_name 4, .stats 5; XStatMetadata.id
# 1, .name 2; XStat.metadata_id 1, .uint64_value 3, .int64_value 4,
# .str_value 5, .ref_value 7 (the id of a stat metadata whose name is the
# string).

def _fields(buf: bytes, lo: int, hi: int):
    """(field number, value) of each field of the message in
    ``buf[lo:hi]``: an int for a varint, a (start, end) span for a
    length-delimited field; fixed-width fields are skipped."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield field, value
        elif wire == 2:
            n, i = _varint(buf, i)
            yield field, (i, i + n)
            i += n
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _str(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode(errors="replace")


def _map_value(buf: bytes, span) -> tuple:
    """(key, value span) of one map entry."""
    key, value = 0, (span[0], span[0])
    for f, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def op_scopes(raw: bytes) -> dict:
    """device plane -> {(program id, op name): tf_op} of every event
    metadata of a serialized XSpace that carries ``program_id`` and
    ``tf_op`` stats, keyed by both its name and its display name. Only
    the planes' metadata maps are decoded, not their events."""
    out: dict = {}
    for f, plane in _fields(raw, 0, len(raw)):
        if f != 1:
            continue
        name, metas, stat_names = "", [], {}
        for pf, v in _fields(raw, *plane):
            if pf == 2:
                name = _str(raw, v)
            elif pf == 4:
                metas.append(_map_value(raw, v)[1])
            elif pf == 5:
                sid, sv = _map_value(raw, v)
                for sf, x in _fields(raw, *sv):
                    if sf == 2:
                        stat_names[sid] = _str(raw, x)
        if not name.startswith("/device:"):
            continue
        table = out.setdefault(name, {})
        for span in metas:
            names, stats = [], {}
            for mf, v in _fields(raw, *span):
                if mf in (2, 4) and v[1] > v[0]:
                    names.append(_str(raw, v))
                elif mf == 5:
                    sid, value = 0, None
                    for sf, x in _fields(raw, *v):
                        if sf == 1:
                            sid = x
                        elif sf in (3, 4):
                            value = x
                        elif sf == 5:
                            value = _str(raw, x)
                        elif sf == 7:
                            value = stat_names.get(x)
                    stats[stat_names.get(sid)] = value
            if "tf_op" in stats and "program_id" in stats:
                for n in names:
                    table[(stats["program_id"], n)] = stats["tf_op"]
    return out


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


def idle_intervals(trace: Trace) -> list[tuple[float, float]]:
    """Intervals of the window (ns, in order) in which no operation ran
    on the busiest device."""
    if not trace.ops:
        return []
    dev = max(trace.ops, key=lambda d: busy_s(trace, d))
    busy = union(clip(trace.ops[dev], trace.window))
    lo, hi = trace.window
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def idle_gaps(trace: Trace, n: int = 10) -> list[list]:
    """The ``n`` longest idle gaps of the busiest device in the window,
    each named by the innermost benchmark span around its midpoint
    (``bench.step``, ``bench.admit``; ``bench.tick`` alone means the
    batcher's own loop; ``untraced`` where no span was open)."""
    gaps = sorted(idle_intervals(trace), key=lambda g: g[0] - g[1])

    def label(mid):
        open_ = [s for s in trace.spans if s[1] <= mid < s[2]]
        if not open_:
            return "untraced"
        return min(open_, key=lambda s: s[2] - s[1])[0]

    return [[label((a + b) / 2), (b - a) * 1e-9] for a, b in gaps[:n]]


# ---------------------------------------------------------------------------
# What the program marks: its host spans and its operations' scopes
# ---------------------------------------------------------------------------

def innermost_pieces(spans, window) -> list[tuple[float, float, str]]:
    """The window cut where any of ``spans`` opens or closes, each piece
    named by the innermost span open over it (the latest opened, the
    shorter of two opened together; ``""`` where none is)."""
    lo, hi = window
    bounds = sorted({lo, hi} | {x for _, a, b in spans for x in (a, b)
                                if lo < x < hi})
    by_start = sorted(spans, key=lambda s: s[1])
    by_end = sorted(spans, key=lambda s: s[2])
    open_: dict = {}
    si = ei = 0
    out = []
    for a, b in zip(bounds, bounds[1:]):
        while si < len(by_start) and by_start[si][1] <= a:
            open_[id(by_start[si])] = by_start[si]
            si += 1
        while ei < len(by_end) and by_end[ei][2] <= a:
            open_.pop(id(by_end[ei]), None)
            ei += 1
        inner = max(open_.values(), key=lambda s: (s[1], s[1] - s[2]),
                    default=("", 0.0, 0.0))
        out.append((a, b, inner[0]))
    return out


def idle_by_span(trace: Trace) -> dict[str, float]:
    """Idle seconds of the busiest device in the window by the innermost
    program span open meanwhile (``""``: none)."""
    idle = idle_intervals(trace)
    pieces = innermost_pieces(trace.program_spans, trace.window)
    out: dict = collections.Counter()
    i = j = 0
    while i < len(idle) and j < len(pieces):
        a = max(idle[i][0], pieces[j][0])
        b = min(idle[i][1], pieces[j][1])
        if b > a:
            out[pieces[j][2]] += (b - a) * 1e-9
        if idle[i][1] < pieces[j][1]:
            i += 1
        else:
            j += 1
    return dict(out)


def span_times(trace: Trace, name: str, less=()) -> list[float]:
    """Seconds of each program span ``name`` that lies in the window,
    less the time of the program spans named in ``less`` (none inside
    another) that lie inside it."""
    lo, hi = trace.window
    inner = sorted((a, b) for n, a, b in trace.program_spans if n in less)
    starts = [a for a, _ in inner]
    out = []
    for n, a, b in trace.program_spans:
        if n != name or a < lo or b > hi:
            continue
        t = b - a
        k = bisect.bisect_left(starts, a)
        while k < len(inner) and inner[k][0] < b:
            if inner[k][1] <= b:
                t -= inner[k][1] - inner[k][0]
            k += 1
        out.append(t * 1e-9)
    return out


_PROGRAM_ID = re.compile(r"\((\d+)\)")


def program_id(module: str):
    """``jit_serve_step(123)`` -> 123 (None without one)."""
    m = _PROGRAM_ID.search(module)
    return int(m.group(1)) if m else None


def scope_times(trace: Trace, program: str) -> dict[str, float]:
    """Device seconds per execution of ``program`` by scope: every
    operation run inside an execution of it that starts in the window,
    keyed by its scope path (its ``tf_op`` less the operation itself,
    ``jit(serve_step)/while/body/attention``, whose last part is the
    innermost scope; ``""`` where the profile gives none), summed over
    devices and divided by the executions. Loops that hold other
    operations are left out, as in ``top_ops``."""
    lo, hi = trace.window
    tot: collections.Counter = collections.Counter()
    runs = 0
    for dev, evs in trace.ops.items():
        mods = sorted((m for m in trace.modules.get(dev, [])
                       if program_name(m[0]) == program), key=lambda e: e[1])
        runs += sum(1 for m in mods if lo <= m[1] < hi)
        starts = [m[1] for m in mods]
        table = trace.scopes.get(dev, {})
        scope: dict = {}          # (module, op name) -> scope path or None
        for name, a, b in evs:
            i = bisect.bisect_right(starts, a) - 1
            if i < 0 or a >= mods[i][2] or not lo <= mods[i][1] < hi:
                continue
            key = (mods[i][0], name)
            if key not in scope:
                loop = op_label(name).split(".")[0] in _CONTAINERS
                tf_op = table.get((program_id(key[0]), name), "")
                scope[key] = None if loop else tf_op.rpartition("/")[0]
            if scope[key] is not None:
                tot[scope[key]] += (b - a) * 1e-9
    return {k: v / runs for k, v in tot.items()} if runs else {}


def under(times: dict[str, float], *scopes: str) -> float:
    """The part of ``scope_times`` under any of ``scopes`` (a part of the
    scope path)."""
    want = set(scopes)
    return sum(v for k, v in times.items() if want & set(k.split("/")))
