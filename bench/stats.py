"""Tail arithmetic of the end-to-end metrics, from per-request records.

Every metric pools all requests of the window: no metric is a median of
chunks. The percentile is Hyndman-Fan type 7 (numpy's default), as the
program's traffic report computes it.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence


def percentile(xs: Sequence[float], q: float) -> float:
    """Linear-interpolation percentile, q in [0, 100]; NaN when empty."""
    if not xs:
        return float("nan")
    s = sorted(float(x) for x in xs)
    pos = (len(s) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(s) - 1)
    frac = pos - lo
    if frac == 0.0:
        return s[lo]
    if math.isinf(s[hi]):
        return math.inf
    return s[lo] + (s[hi] - s[lo]) * frac


@dataclasses.dataclass
class Record:
    """What the benchmark saw of one request, in seconds from window
    start: when it was due, admitted, and each output token's arrival on
    the host. ``key`` names the request inside the engine (the id of the
    prompt list it was served from)."""

    rid: int
    due_s: float
    prompt: tuple
    max_new_tokens: int
    admit_s: Optional[float] = None
    token_s: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    done: bool = False
    key: int = 0

    @property
    def first_token_s(self) -> Optional[float]:
        return self.token_s[0] if self.token_s else None


def window_tokens(records: Sequence[Record], window_s: float) -> int:
    """Output tokens that reached the host inside the window."""
    return sum(1 for r in records for t in r.token_s if t <= window_s)


def itl_gaps(records: Sequence[Record], window_s: float) -> list[float]:
    """Gaps between consecutive output tokens of each request, pooled over
    all requests, for every gap that ends inside the window."""
    return [b - a for r in records
            for a, b in zip(r.token_s, r.token_s[1:]) if b <= window_s]


def due_in_window(records: Sequence[Record], window_s: float
                  ) -> list[Record]:
    return [r for r in records if r.due_s < window_s]


def ttfts(records: Sequence[Record], window_s: float) -> list[float]:
    """Due time to first token of every request due in the window; one
    that never produced a token counts as infinitely late."""
    return [(r.first_token_s - r.due_s) if r.first_token_s is not None
            else math.inf for r in due_in_window(records, window_s)]


def queue_waits(records: Sequence[Record], window_s: float) -> list[float]:
    """Due time to admission of every request due in the window."""
    return [(r.admit_s - r.due_s) if r.admit_s is not None else math.inf
            for r in due_in_window(records, window_s)]
