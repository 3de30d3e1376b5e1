"""The check reads any configuration's cache from its own files: the cache
rows are read by leaf, nested published keys reach the reference as
static arguments, and sub-configurations are built from dicts.

``old_cached_rows`` and ``old_compare`` are the dense-GQA check as it was
before the check read the cache by leaf, kept as the oracle: on the qwen3
cells the check reads exactly what it read then."""

import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.harness import KV_TOL, _rel, reference_rows
from bench.tests.test_check import ROOT, run, small_cell

CELLS = ["qwen3-pinned.long-decode", "qwen3-pinned.chat-open"]


def old_cached_rows(engine, records: dict) -> dict:
    slots, seqs = [], []
    for s, req in enumerate(engine.requests):
        if req is None:
            continue
        rec = records[id(req.prompt)]
        n = len(rec.prompt) - 1 + len(req.out)
        slots.append(s)
        seqs.append((list(rec.prompt) + list(req.out))[:n])
    attn = engine.cache["layers"][0]["attn"]
    idx = jnp.asarray(slots + [s for s in range(engine.slots)
                               if s not in slots], jnp.int32)
    k, v, n_prog = jax.device_get((attn["k"][0, idx], attn["v"][0, idx],
                                   attn["len"][0, idx]))
    return {"seqs": seqs, "k": k, "v": v, "len": n_prog}


def old_compare(cell, sv, control: str = "") -> dict:
    ref, conf = cell.reference, cell.conf
    items = (tuple(sorted(conf["model"].items())),
             tuple(sorted(conf["cim"].items())))

    def kv_rows(*a):
        # The reference gave (k, v); it now gives them by leaf name.
        rows = ref.kv_rows(*a)
        return rows["k"], rows["v"]

    out: dict = {}
    if sv.sample:
        tokens, served, mask = reference_rows(sv.sample,
                                              conf["serving"]["max_len"])
        ids = np.broadcast_to(sv.ids, tokens.shape + sv.ids.shape)
        if control:
            _, first, got = jax.device_get(ref.row_logits(
                sv.params, jnp.asarray(tokens), jnp.asarray(ids), *items,
                control))
            picks = np.concatenate([first[..., None], ids], axis=-1)
        else:
            got = sv.logits
            picks = np.concatenate([served[..., None], ids], axis=-1)
        best, _, at = jax.device_get(ref.row_logits(
            sv.params, jnp.asarray(tokens), jnp.asarray(picks), *items))
        gaps = (best - at[..., 0])[mask]
        rel = _rel(got, at[..., 1:], -1)[mask]
        if np.all(np.isfinite(gaps)) and np.all(np.isfinite(rel)):
            out["served_logit_gap_max"] = float(gaps.max())
            out["served_not_first_share"] = float(np.mean(gaps > 0))
            out["logit_rel_err_median"] = float(np.median(rel))
            out["logit_rel_err_max"] = float(rel.max())
    kv = sv.kv
    if kv["seqs"]:
        tokens = np.zeros(kv["k"].shape[:2], np.int32)
        for b, q in enumerate(kv["seqs"]):
            tokens[b, :len(q)] = q
        k, v = jax.device_get(kv_rows(sv.params, jnp.asarray(tokens),
                                      *items))
        if control:
            gk, gv = jax.device_get(kv_rows(
                sv.params, jnp.asarray(tokens), *items, control))
            n_prog = [len(q) for q in kv["seqs"]]
        else:
            gk, gv, n_prog = kv["k"], kv["v"], kv["len"]
        err = np.maximum(_rel(gk, k, (-2, -1)), _rel(gv, v, (-2, -1)))
        errs, bad = [], 0
        for b, q in enumerate(kv["seqs"]):
            e = err[b, :len(q)]
            off = ~(e <= KV_TOL) | (np.arange(len(q)) >= int(n_prog[b]))
            bad += int(off.sum())
            errs.append(e)
        out["kv_mismatch_share"] = bad / sum(len(q) for q in kv["seqs"])
        out["kv_rel_err_median"] = float(np.median(np.concatenate(errs)))
    return out


def spy_cached_rows(monkeypatch, also=None) -> dict:
    """Records what ``harness.cached_rows`` returns (``new``) and, with
    ``also``, what ``also(engine, records)`` reads of the same engine."""
    seen: dict = {}
    new = harness.cached_rows

    def both(engine, records, *a):
        if also is not None:
            seen["old"] = also(engine, records)
        seen["new"] = new(engine, records, *a)
        return seen["new"]

    monkeypatch.setattr(harness, "cached_rows", both)
    return seen


@pytest.mark.parametrize("fault", [None, "stale_state"])
@pytest.mark.parametrize("name", CELLS)
def test_the_check_reads_the_qwen3_cells_as_before(name, fault, monkeypatch):
    cell = small_cell(name)
    seen = spy_cached_rows(monkeypatch, old_cached_rows)
    sv = harness.serve(cell, 2**33 + 11, 3.0, False, time.perf_counter(),
                       fault)
    old = seen["old"]
    assert sv.kv["seqs"] and sv.kv["seqs"] == old["seqs"]
    assert sorted(sv.kv["rows"]) == ["k", "v"]
    np.testing.assert_array_equal(sv.kv["rows"]["k"], old["k"])
    np.testing.assert_array_equal(sv.kv["rows"]["v"], old["v"])
    np.testing.assert_array_equal(sv.kv["len"], old["len"])
    old_sv = dataclasses.replace(sv, kv=old)
    for control in ("", "bf16"):
        got = harness.compare(cell, sv, control)
        assert "kv_mismatch_share" in got
        assert got == old_compare(cell, old_sv, control)


def test_freeze_keeps_a_flat_section_as_it_was():
    flat = {"b": 2, "a": 1.5, "c": True}
    assert harness.freeze(flat) == tuple(sorted(flat.items()))
    nested = {"rope_scaling": {"type": "yarn", "factor": 40},
              "layer_types": ["full", "full"], "a": 1}
    frozen = harness.freeze(nested)
    hash(frozen)
    assert frozen == (("a", 1), ("layer_types", ("full", "full")),
                      ("rope_scaling", (("factor", 40), ("type", "yarn"))))


# ---------------------------------------------------------------------------
# A configuration beyond dense GQA, from new files alone
# ---------------------------------------------------------------------------

SMOKE = "mla-moe-smoke"

# A plumbing reference: it checks that the harness hands the reference
# what it needs and reads the cache by leaf, not the program, so it
# computes with the program's own decode path (every position of each row
# through a fresh cache) on the weights the program programs.
SMOKE_REF = '''
import json
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp

from bench import system
from repro.core.programmed import program_weights
from repro.models import transformer as T

CONF = json.loads(Path(__file__).with_name("%s.json").read_text())
CFG = system.model_config(CONF)


def init_params(seed, sizes):
    assert sizes["rope_scaling"]["type"] == "yarn"
    return T.lm_init(jax.random.PRNGKey(seed %% 2**31), CFG)


@partial(jax.jit, static_argnums=(2, 3))
def _decode(params, tokens, sizes_items, cim_items):
    assert dict(sizes_items)["layer_types"] == ("full_attention",) * 2
    cache = T.lm_init_cache(CFG, *tokens.shape)

    def step(cache, tok):
        logits, cache = T.lm_decode_step(params, cache, tok, CFG)
        return cache, logits

    cache, logits = jax.lax.scan(step, cache, tokens.T)
    return jnp.swapaxes(logits, 0, 1), cache["layers"][0]["attn"]


def _run(params, tokens, sizes_items, cim_items, precision):
    assert precision == "highest"
    return _decode(program_weights(params, CFG.mf.cim, prefer_lossless=True),
                   tokens, sizes_items, cim_items)


def row_logits(params, tokens, picks, sizes_items, cim_items,
               precision="highest"):
    logits, _ = _run(params, tokens, sizes_items, cim_items, precision)
    return (jnp.max(logits, axis=-1), jnp.argmax(logits, axis=-1),
            jnp.take_along_axis(logits, picks, axis=-1))


def kv_rows(params, tokens, sizes_items, cim_items, precision="highest"):
    _, attn = _run(params, tokens, sizes_items, cim_items, precision)
    return {"ckv": attn["ckv"][0], "kr": attn["kr"][0]}
''' % SMOKE


def smoke_cell(tmp_path) -> harness.Cell:
    """The repo's MLA+MoE smoke shape (``deepseek_v3_671b.SMOKE``) as a
    configuration file with nested published keys, its reference and its
    traffic, all under ``tmp_path``."""
    root = tmp_path / "bench"
    (root / "configs").mkdir(parents=True)
    (root / "traffic").mkdir()
    conf = json.loads((ROOT / "bench" / "configs" /
                       "qwen3-0.6b.cim8x62.pinned.json").read_text())
    conf.update(name=SMOKE, reference="smoke_ref")
    conf["model"] = {
        "hidden_size": 64, "intermediate_size": 32, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "vocab_size": 128, "rms_norm_eps": 1e-6, "rope_theta": 10000,
        "tie_word_embeddings": False,
        "rope_scaling": {"type": "yarn", "factor": 40, "beta_fast": 32},
        "layer_types": ["full_attention", "full_attention"]}
    conf["program"]["model"] = {
        "family": "moe", "attn_type": "mla", "mlp_type": "silu_glu",
        "block_pattern": ["attn"],
        "mla": {"q_lora_rank": 32, "kv_lora_rank": 16,
                "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
                "v_head_dim": 16},
        "moe": {"n_experts": 8, "top_k": 2, "n_shared": 1,
                "d_ff_expert": 32}}
    conf["serving"].update(slots=4, max_len=96)
    (root / "configs" / f"{SMOKE}.json").write_text(json.dumps(conf))
    (root / "configs" / "smoke_ref.py").write_text(SMOKE_REF)
    (root / "traffic" / "short.json").write_text(json.dumps(
        {"loop": "closed", "clients": 4, "block": 4,
         "prompt": {"median": 24, "sigma": 0.3, "min": 16, "max": 40},
         "output": {"median": 12, "sigma": 0.2, "min": 8, "max": 16}}))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "smoke.short", "config": SMOKE,
                               "traffic": "short", "chips": 1,
                               "why": "test"})
    return harness.load_cell(bench, "smoke.short", False, root=root)


def test_an_mla_moe_configuration_runs_from_its_own_files(tmp_path,
                                                          monkeypatch):
    from bench import system
    cell = smoke_cell(tmp_path)
    cfg = system.model_config(cell.conf)
    assert (cfg.attn_type, cfg.moe.top_k, cfg.mla.kv_lora_rank,
            cfg.block_pattern) == ("mla", 2, 16, ("attn",))
    # Prompts go through decode steps here (no batched prefill for MLA),
    # and are long beside the outputs: requests in flight at the close
    # are part-way through theirs, with fewer positions written than a
    # prefill wave would have left.
    seen = spy_cached_rows(monkeypatch, lambda engine, records: [
        len(records[id(r.prompt)].prompt) - 1 + len(r.out)
        for r in engine.requests if r is not None])
    r = run(cell)
    assert r["correct"] is True, r["compared"]
    rows = seen["new"]["rows"]
    assert sorted(rows) == ["ckv", "kr"]
    assert rows["ckv"].shape == (4, 96, 16) and rows["kr"].shape == (4, 96, 8)
    fed = [len(q) for q in seen["new"]["seqs"]]
    assert sum(fed) > 0
    assert any(n < waved for n, waved in zip(fed, seen["old"]))
    assert r["compared"]["kv_mismatch_share"]["value"] == 0.0
    assert r["compared"]["served_logit_gap_max"]["value"] is not None

    broken = run(smoke_cell(tmp_path / "again"), fault="stale_state")
    assert broken["correct"] is False
    assert broken["compared"]["kv_mismatch_share"]["value"] > \
        broken["compared"]["kv_mismatch_share"]["limit"]
