"""Traffic generator: one general reader of the mix files in ``bench/traffic``.

The MMPP arrival process and the keyed Philox generator are copied from
the program's traffic lab (``repro.traffic.workload``), so a later change
there cannot move the yardstick. Added for a steady benchmark:

* Sizes are stratified. For ``n`` requests the prompt and output lengths are
  the quantiles ``(i + 0.5) / n`` of the mix's clipped log-normal, and the
  Poisson gaps the same quantiles of the exponential; the seed only permutes
  them. So every seed carries the same work, in another order.
* With ``block`` set, the order is stratified too: every ``block``
  consecutive requests hold one size from each of ``block`` strata, so
  whatever prefix of the queue a window serves has the same composition
  (a closed loop serves only the first few hundred of its requests); and
  in an open loop every ``block`` consecutive Poisson gaps are the same
  ``block`` quantiles in another order, so each seed brings the same
  number of arrivals into the window.
* A closed loop: ``clients`` conversations are under way when the window
  opens, each at a residual length drawn from the seed (uniform over its
  own output length), and every later request waits in the queue from time
  0, so a slot is refilled as soon as its request completes.

A mix file is a JSON object::

    {"loop": "open" | "closed",
     "process": "poisson" | "mmpp",       # open loop only
     "rate_rps": 12.0,                     # open loop only
     "clients": 32,                        # closed loop only
     "block": 32,                          # optional: stratified order
     "prompt": {"median": 32, "sigma": 0.5, "min": 16, "max": 64},
     "output": {"median": 320, "sigma": 0.3, "min": 256, "max": 448}}

plus, for ``mmpp``, ``burst_rate_mult``, ``burst_fraction`` and
``switch_rate_hz`` as in the traffic lab.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass(frozen=True)
class Spec:
    """One generated request: due time (s from window start), prompt ids
    and output budget. Every request is greedy."""

    rid: int
    due_s: float
    prompt: tuple[int, ...]
    max_new_tokens: int


def rng_for(seed: int, stream: int = 0) -> np.random.Generator:
    """Counter-based generator keyed by the seed (any int below 2**64)."""
    return np.random.Generator(np.random.Philox(key=[seed % 2**64, stream]))


def _mmpp_arrivals(rng: np.random.Generator, n: int, rate: float,
                   mult: float, f: float, switch_hz: float) -> np.ndarray:
    """2-state MMPP whose stationary mean rate is ``rate``."""
    r_slow = rate / ((1.0 - f) + f * mult)
    rates = (r_slow, r_slow * mult)
    sojourn = ((1.0 - f) / switch_hz, f / switch_hz)
    out = np.empty(n)
    t, state = 0.0, 0
    t_switch = rng.exponential(sojourn[state])
    i = 0
    while i < n:
        dt = rng.exponential(1.0 / rates[state])
        if t + dt >= t_switch:
            t = t_switch
            state = 1 - state
            t_switch = t + rng.exponential(sojourn[state])
            continue
        t += dt
        out[i] = t
        i += 1
    return out


def _strata(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def stratified_lengths(dist: dict, n: int) -> np.ndarray:
    """The ``n`` stratified quantiles of a clipped log-normal (ints)."""
    inv = NormalDist().inv_cdf
    z = np.array([inv(u) for u in _strata(n)])
    v = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    return np.clip(np.rint(v), dist["min"], dist["max"]).astype(np.int64)


def arrange(sizes: np.ndarray, rng: np.random.Generator,
            block: int = 0) -> np.ndarray:
    """``sizes`` in an order drawn from ``rng``: a plain permutation, or
    with ``block`` (which divides ``len(sizes)``) blocks of consecutive
    requests that each take one size from every stratum."""
    if not block:
        return rng.permutation(sizes)
    strata = np.sort(sizes).reshape(block, -1)       # (block, n / block)
    strata = np.stack([rng.permutation(row) for row in strata])
    cols = [rng.permutation(strata[:, j]) for j in range(strata.shape[1])]
    return np.concatenate(cols)


# No decode step of any cell is shorter than this (a step reads at least
# its weights and the filled part of its KV cache): a closed loop's queue
# holds what its clients could finish at one token per slot per step.
MIN_STEP_S = 2e-3


def poisson_gaps(rate: float, n: int, rng: np.random.Generator,
                 block: int = 0) -> np.ndarray:
    """``n`` stratified gaps of a Poisson process at ``rate``, in an order
    drawn from ``rng``. With ``block``: every ``block`` consecutive gaps
    are the same ``block`` quantiles of the exponential (scaled to mean
    ``1 / rate``), each block in its own order, so every seed brings the
    same number of arrivals into a window, up to one block's order."""
    if not block:
        return rng.permutation(-np.log1p(-_strata(n)) / rate)
    one = -np.log1p(-_strata(block))
    one /= one.mean() * rate
    return np.concatenate([rng.permutation(one) for _ in range(n // block)])


def request_count(mix: dict, seconds: float, tail_s: float) -> int:
    """Requests to generate so that the window never runs dry: an open
    loop covers ``seconds + tail_s`` of arrivals; a closed loop what its
    clients could finish in ``seconds`` at one step per ``MIN_STEP_S``,
    at the mix's mean output length (the harness fails a run whose queue
    empties before the window closes). A multiple of the mix's
    ``block``."""
    if mix["loop"] == "open":
        n = max(1, math.ceil(mix["rate_rps"] * (seconds + tail_s)))
    else:
        mean_out = stratified_lengths(mix["output"], 1024).mean()
        n = mix["clients"] + math.ceil(
            mix["clients"] * seconds / MIN_STEP_S / mean_out)
    block = mix.get("block", 1)
    return -(-n // block) * block


def generate(mix: dict, seed: int, n: int, vocab: int) -> list[Spec]:
    """The keyed request list of one mix (same seed, same list)."""
    rng = rng_for(seed)
    block = mix.get("block", 0)
    prompt_lens = arrange(stratified_lengths(mix["prompt"], n), rng, block)
    out_lens = arrange(stratified_lengths(mix["output"], n), rng, block)
    if mix["loop"] == "closed":
        due = np.zeros(n)
        k = min(mix["clients"], n)
        # Conversations already under way: a residual of each output.
        resid = rng.permutation(_strata(k))
        out_lens[:k] = np.maximum(1, np.ceil(resid * out_lens[:k]))
    elif mix.get("process", "poisson") == "poisson":
        due = np.cumsum(poisson_gaps(mix["rate_rps"], n, rng, block))
    else:
        due = _mmpp_arrivals(rng, n, mix["rate_rps"],
                             mix["burst_rate_mult"], mix["burst_fraction"],
                             mix["switch_rate_hz"])
    # Token 0 is the engine's prefill padding; prompts draw from [1, vocab).
    toks = rng.integers(1, vocab, size=int(prompt_lens.sum()))
    ends = np.cumsum(prompt_lens)
    return [Spec(rid=i, due_s=float(due[i]),
                 prompt=tuple(int(t) for t in toks[ends[i] - prompt_lens[i]:
                                                   ends[i]]),
                 max_new_tokens=int(out_lens[i]))
            for i in range(n)]


def prefill_buckets(mix: dict, max_len: int) -> list[int]:
    """Slab lengths the engine's prefill waves can reach for this mix: the
    next power of two of ``len - 1`` over the prompt range, capped at
    ``max_len`` (``ServeEngine._prefill_wave``'s bucketing)."""
    def bucket(t: int) -> int:
        return min(1 << (t - 1).bit_length(), max_len)
    lo, hi = mix["prompt"]["min"] - 1, mix["prompt"]["max"] - 1
    if hi < 1:
        return []
    out = []
    b = bucket(max(lo, 1))
    while True:
        out.append(b)
        if b >= bucket(hi):
            return out
        b = min(2 * b, max_len)
