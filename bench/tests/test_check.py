"""``correct`` on the CPU at a small size: the harness's look for a chip is
skipped and the rest of a run is driven. A sound run is correct; with the
timed path broken underneath, a decode step that returns its state
unchanged or a token altered where the step produces it, it is not."""

import time

import pytest

from bench import harness

ROOT = harness.BENCH.parent


def small_cell(name="qwen3-pinned.long-decode", traced=False):
    cell = harness.load_cell(harness.load_json(ROOT / "BENCHMARK.json"),
                             name, traced)
    cell.conf["model"].update(
        hidden_size=128, intermediate_size=384, num_hidden_layers=4,
        num_attention_heads=4, num_key_value_heads=2, head_dim=32,
        vocab_size=2048)
    cell.conf["serving"].update(slots=4, max_len=128)
    if cell.mix["loop"] == "closed":
        cell.mix["clients"] = 4
        cell.mix["output"].update(min=20, median=30, max=40)
    else:
        cell.mix["rate_rps"] = 4.0
        cell.mix["prompt"].update(min=8, median=16, max=64)
        cell.mix["output"].update(min=8, median=16, max=32)
    return cell


def run(cell, fault=None, traced=False, seconds=3.0, control=""):
    return harness.run_cell(cell, 2**33 + 5, seconds, traced,
                            time.perf_counter(), None, fault=fault,
                            control=control)


@pytest.mark.parametrize("name", ["qwen3-pinned.long-decode",
                                  "qwen3-pinned.chat-open"])
def test_a_sound_run_is_correct(name):
    cell = small_cell(name)
    r = run(cell)
    assert r["correct"] is True, r["compared"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {m["name"] for m in cell.metrics}
    assert list(r)[-1] == "compared"
    assert set(r["device"]) >= {"platform", "kind", "count",
                                "memory_peak_bytes"}


@pytest.mark.parametrize("name", ["qwen3-pinned.long-decode",
                                  "qwen3-pinned.chat-open"])
@pytest.mark.parametrize("fault", ["stale_state", "altered_token"])
def test_a_broken_step_is_not_correct(name, fault):
    r = run(small_cell(name), fault=fault)
    assert r["correct"] is False
    assert any(v["value"] is None or v["value"] > v["limit"]
               for v in r["compared"].values())
