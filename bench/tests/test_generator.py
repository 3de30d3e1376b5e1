"""The traffic generator: keyed repeatability and the same work for every
seed."""

import json
from collections import Counter
from pathlib import Path

import pytest

from bench import generator

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"
MIXES = sorted(p.stem for p in TRAFFIC.glob("*.json"))
BIG_SEED = 2**31 + 12345


def mix(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests_bit_for_bit(name):
    m = mix(name)
    n = generator.request_count(m, 30, 10)
    a = generator.generate(m, BIG_SEED, n, 151936)
    b = generator.generate(m, BIG_SEED, n, 151936)
    assert a == b
    assert a != generator.generate(m, BIG_SEED + 1, n, 151936)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_carries_the_same_sizes(name):
    m = mix(name)
    n = generator.request_count(m, 30, 10)
    one, two = (generator.generate(m, s, n, 151936) for s in (1, BIG_SEED))
    assert Counter(len(r.prompt) for r in one) == \
        Counter(len(r.prompt) for r in two)
    if m["loop"] == "open":
        assert Counter(r.max_new_tokens for r in one) == \
            Counter(r.max_new_tokens for r in two)
        gaps = [b.due_s - a.due_s for a, b in zip(one, one[1:])]
        assert one[-1].due_s == pytest.approx(
            two[-1].due_s, rel=1e-9)            # same gaps, other order
        assert min(gaps) > 0
    for r in one:
        assert m["prompt"]["min"] <= len(r.prompt) <= m["prompt"]["max"]
        assert 1 <= r.max_new_tokens <= m["output"]["max"]
        assert all(1 <= t < 151936 for t in r.prompt)


def test_poisson_rate_and_closed_loop_residuals():
    m = {"loop": "open", "process": "poisson", "rate_rps": 10.0,
         "prompt": {"median": 8, "sigma": 0.5, "min": 2, "max": 32},
         "output": {"median": 8, "sigma": 0.5, "min": 2, "max": 32}}
    reqs = generator.generate(m, 7, 1000, 100)
    assert reqs[-1].due_s == pytest.approx(100.0, rel=0.02)
    c = dict(m, loop="closed", clients=4)
    reqs = generator.generate(c, 7, 20, 100)
    assert all(r.due_s == 0.0 for r in reqs)
    assert all(r.max_new_tokens >= 2 for r in reqs[4:])


def test_blocks_give_every_seed_the_same_arrivals_in_a_window():
    m = mix("chat-open")
    n = generator.request_count(m, 51, 10)
    b = m["block"]
    runs = [generator.generate(m, s, n, 1000) for s in (1, 2, BIG_SEED)]
    for k in range(1, n // b):
        ends = [r[k * b - 1].due_s for r in runs]
        assert ends == pytest.approx([k * b / m["rate_rps"]] * 3, rel=1e-9)
    assert len({tuple(r.due_s for r in run) for run in runs}) == 3


def test_a_closed_loop_queue_outlasts_the_fastest_step():
    m = mix("long-decode")
    n = generator.request_count(m, 51, 10)
    reqs = generator.generate(m, BIG_SEED, n, 1000)
    tokens = sum(r.max_new_tokens for r in reqs[m["clients"]:])
    assert tokens >= m["clients"] * 51 / generator.MIN_STEP_S
    assert n % m["block"] == 0


def test_mmpp_repeats():
    m = {"loop": "open", "process": "mmpp", "rate_rps": 5.0,
         "burst_rate_mult": 4.0, "burst_fraction": 0.25,
         "switch_rate_hz": 0.5,
         "prompt": {"median": 8, "sigma": 0.5, "min": 2, "max": 32},
         "output": {"median": 8, "sigma": 0.5, "min": 2, "max": 32}}
    a = generator.generate(m, BIG_SEED, 200, 100)
    assert a == generator.generate(m, BIG_SEED, 200, 100)
    assert all(x.due_s < y.due_s for x, y in zip(a, a[1:]))


@pytest.mark.parametrize("lo,hi,max_len,want", [
    (16, 64, 512, [16, 32, 64]),
    (64, 384, 512, [64, 128, 256, 512]),
    (2, 2, 512, [1]),
    (300, 600, 512, [512]),
])
def test_prefill_buckets(lo, hi, max_len, want):
    m = {"prompt": {"min": lo, "max": hi}}
    assert generator.prefill_buckets(m, max_len) == want
