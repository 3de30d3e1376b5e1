"""The lower-precision control at a size a test run holds, on the CPU: the
plain reference computed in bfloat16, put in the program's place for the
check of a run, makes ``correct`` come out false. On the CPU the program
itself reads every number it is held to at exactly 0."""

import pytest

from bench.tests.test_check import run, small_cell

CELLS = ["qwen3-pinned.long-decode", "qwen3-pinned.chat-open"]


@pytest.mark.parametrize("name", CELLS)
def test_the_program_reads_the_reference_exactly_on_the_cpu(name):
    r = run(small_cell(name))
    assert r["correct"] is True
    assert all(v["value"] == 0 for v in r["compared"].values())


@pytest.mark.parametrize("name", CELLS)
def test_the_bf16_control_is_not_correct(name):
    r = run(small_cell(name), control="bf16")
    assert r["correct"] is False
    assert r["compared"]["kv_mismatch_share"]["value"] > \
        r["compared"]["kv_mismatch_share"]["limit"]


def test_the_step_logits_kept_are_those_of_each_served_token():
    """On the CPU the program's step logits equal the reference's at most
    served positions and lie within rounding chaos of them at the rest
    (a logit row of another step or slot would read about 1), so the
    capture maps each output to its own step and slot; the control's
    depart."""
    import time
    from bench import harness
    cell = small_cell(CELLS[0])
    sv = harness.serve(cell, 2**33 + 7, 3.0, False, time.perf_counter())
    assert sum(len(r.tokens) for r in sv.sample) >= 100
    program = harness.compare(cell, sv)
    assert program["logit_rel_err_median"] == 0.0
    assert program["logit_rel_err_max"] < 0.1
    assert program["kv_rel_err_median"] == 0.0
    assert harness.compare(cell, sv, "bf16")["logit_rel_err_median"] > 0.0
