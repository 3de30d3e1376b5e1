"""Process-local span/event bus: structured tracing for the serving stack.

End-of-run snapshots (``ServeReport`` / ``TrafficReport`` /
``DriftStatus``) say *how much* happened; the trace says *when*, on which
slot/tenant/request, and *why*. Every event is a :class:`TraceEvent` —
a kind from the serving taxonomy (``program``, ``reload``, ``recal``,
``retrim``, ``drift_probe``, ``admit`` / ``shed`` / ``evict``,
``prefill_wave``, ``sanitize``, ...), a monotonic timestamp, the engine's
input-stream index, optional slot / request / layer coordinates, and a
JSON-safe payload (nJ / bits figures pulled from the Eq. 4 roll-up, drift
residues, queue depths).

Two sinks, one API:

1. **The bus**, for the serving story. Host-side emitters are one
   ``None`` check when no bus is installed. The bus is a ring buffer: the
   newest ``capacity`` events win, ``dropped`` counts what the ring
   evicted, so a week-long serve cannot OOM the host.
2. **The profiler**, for time. :func:`span` always opens a
   ``jax.profiler.TraceAnnotation``, so a running ``jax.profiler`` trace
   holds the program's host spans (``<layer>.<part>``: ``batcher.tick``,
   ``serve.step``, ``serve.sync``, ...) on the same clock as the device's
   operations. With no profiler running that costs about a microsecond
   per span. The device side needs no host code: ``jax.named_scope``
   marks the step's layers (``attention``, ``kv_write``,
   ``mf_projection``/<route>, ``lm_head``, ``sampling``) in the compiled
   program's op metadata.

The bus is process-local and deliberately global (one serving process =
one timeline); concurrent engines tag events with their ``engine`` field
and readers filter. Not thread-safe beyond CPython list-append atomicity
— the serving loop is single-threaded.
"""
# repro-lint: module=observability

from __future__ import annotations

import dataclasses
import time
from contextlib import contextmanager
from typing import Any, Iterator, Optional

from jax.profiler import TraceAnnotation

# Events whose payloads may carry large per-slot arrays (drift residue /
# tier vectors). They are emitted only when the installed bus asks for
# detail — the fleet heatmap needs them, steady-state tracing does not.
DETAIL_KINDS = frozenset({"drift_probe", "retrim"})


@dataclasses.dataclass(frozen=True)
class TraceEvent:
    """One structured event on the bus (JSON-safe by construction)."""

    kind: str
    t_ns: int                      # monotonic nanoseconds (host clock)
    seq: int                       # bus-wide emission index (total order)
    stream: Optional[int] = None   # engine input-stream counter
    slot: Optional[int] = None     # fleet tile slot / cache slot
    rid: Optional[Any] = None      # request id
    layer: Optional[str] = None    # projection / layer name
    engine: Optional[int] = None   # emitting engine's id() tag
    data: dict = dataclasses.field(default_factory=dict)

    def to_json(self) -> dict:
        out = {"kind": self.kind, "t_ns": self.t_ns, "seq": self.seq}
        for f in ("stream", "slot", "rid", "layer", "engine"):
            v = getattr(self, f)
            if v is not None:
                out[f] = v
        if self.data:
            out["data"] = self.data
        return out

    @classmethod
    def from_json(cls, obj: dict) -> "TraceEvent":
        return cls(kind=obj["kind"], t_ns=int(obj["t_ns"]),
                   seq=int(obj["seq"]), stream=obj.get("stream"),
                   slot=obj.get("slot"), rid=obj.get("rid"),
                   layer=obj.get("layer"), engine=obj.get("engine"),
                   data=obj.get("data", {}))


class TraceBuffer:
    """Fixed-capacity ring of :class:`TraceEvent`; newest events win."""

    def __init__(self, capacity: int = 65536, detail: bool = False):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self.detail = detail          # ship per-slot arrays in payloads
        self._ring: list[Optional[TraceEvent]] = [None] * capacity
        self._next = 0                # next write position
        self.total = 0                # events ever appended
        self.dropped = 0              # events the ring evicted

    def append(self, ev: TraceEvent) -> None:
        if self._ring[self._next] is not None:
            self.dropped += 1
        self._ring[self._next] = ev
        self._next = (self._next + 1) % self.capacity
        self.total += 1

    def __len__(self) -> int:
        return min(self.total, self.capacity)

    def events(self) -> list[TraceEvent]:
        """Retained events in emission order (oldest surviving first)."""
        if self.total <= self.capacity:
            return [e for e in self._ring[:self._next] if e is not None]
        return ([e for e in self._ring[self._next:] if e is not None]
                + [e for e in self._ring[:self._next] if e is not None])

    def by_kind(self, *kinds: str) -> list[TraceEvent]:
        want = frozenset(kinds)
        return [e for e in self.events() if e.kind in want]

    def clear(self) -> None:
        self._ring = [None] * self.capacity
        self._next = 0
        self.total = 0
        self.dropped = 0


# ---------------------------------------------------------------------------
# The process-local bus.
# ---------------------------------------------------------------------------

_BUS: Optional[TraceBuffer] = None
_SEQ = 0


def bus() -> Optional[TraceBuffer]:
    """The currently installed bus (None = tracing off)."""
    return _BUS


def enabled() -> bool:
    return _BUS is not None


def detail_enabled() -> bool:
    return _BUS is not None and _BUS.detail


def install(capacity: int = 65536, detail: bool = False) -> TraceBuffer:
    """Install (and return) a fresh process-local bus."""
    global _BUS
    _BUS = TraceBuffer(capacity, detail=detail)
    return _BUS


def uninstall() -> None:
    global _BUS
    _BUS = None


@contextmanager
def tracing(capacity: int = 65536,
            detail: bool = False) -> Iterator[TraceBuffer]:
    """Scoped bus: install for the block, restore the previous one after.

    The buffer stays readable after the block — exports and health
    timelines are typically built from it once serving finished.
    """
    global _BUS
    prev, _BUS = _BUS, TraceBuffer(capacity, detail=detail)
    try:
        yield _BUS
    finally:
        _BUS = prev


def emit(kind: str, *, stream: Optional[int] = None,
         slot: Optional[int] = None, rid: Optional[Any] = None,
         layer: Optional[str] = None, engine: Optional[int] = None,
         **data: Any) -> None:
    """Host-side emit: one dict-build + list-append when a bus is
    installed, one global read when not."""
    if _BUS is None:
        return
    global _SEQ
    _SEQ += 1
    _BUS.append(TraceEvent(kind=kind, t_ns=time.monotonic_ns(), seq=_SEQ,
                           stream=stream, slot=slot, rid=rid, layer=layer,
                           engine=engine, data=data))


class _BusSpan:
    """A profiler span that also emits ``kind`` once on exit, with the
    block's duration in ``dur_ns`` (single-event spans: cheap, and
    ring-eviction cannot orphan a begin/end pair)."""

    __slots__ = ("kind", "fields", "t0", "annotation")

    def __init__(self, kind: str, fields: dict):
        self.kind, self.fields = kind, fields
        self.annotation = TraceAnnotation(kind, **fields)

    def __enter__(self) -> None:
        self.annotation.__enter__()
        self.t0 = time.monotonic_ns()

    def __exit__(self, *exc) -> None:
        emit(self.kind, dur_ns=time.monotonic_ns() - self.t0, **self.fields)
        self.annotation.__exit__(*exc)


def span(kind: str, **fields: Any):
    """Context manager around a block of host work: a
    ``jax.profiler.TraceAnnotation`` named ``kind`` with ``fields`` as its
    arguments (recorded only while a profiler trace runs), plus one bus
    event on exit when a bus is installed.

    A field value reaches the profiler as text, and the profiler splits
    its arguments at commas: join lists with spaces."""
    if _BUS is None:
        return TraceAnnotation(kind, **fields)
    return _BusSpan(kind, fields)
