"""Cells, mixes, configurations and metrics are found by name: a new one
needs only new files and new entries, no edit of an existing file. And
without a TPU the benchmark prints no result and fails."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"


def benchmark():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_every_declared_cell_and_metric_has_its_files():
    b = benchmark()
    for w in b["workloads"]:
        for traced in (False, True):
            cell = harness.load_cell(b, w["name"], traced)
            assert cell.metrics
        assert (BENCH / "traffic" / f"{w['traffic']}.json").exists()
    for m in b["end_to_end"] + b["per_layer"]:
        assert callable(harness.reader(m["name"]))
    for c in b["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["name"] == c["name"]


def test_a_new_cell_mix_config_and_metric_are_found_by_name(tmp_path):
    root = tmp_path / "bench"
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    (root / "traffic" / "burst.json").write_text(json.dumps(
        {"loop": "open", "process": "mmpp", "rate_rps": 3.0,
         "burst_rate_mult": 4.0, "burst_fraction": 0.25,
         "switch_rate_hz": 0.5,
         "prompt": {"median": 8, "sigma": 0.5, "min": 2, "max": 16},
         "output": {"median": 8, "sigma": 0.5, "min": 2, "max": 16}}))
    conf = json.loads((root / "configs" /
                       "qwen3-0.6b.cim8x62.pinned.json").read_text())
    conf["name"] = "tiny"
    (root / "configs" / "tiny.json").write_text(json.dumps(conf))
    (root / "metrics" / "burst_metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    b = benchmark()
    b["workloads"].append({"name": "tiny.burst", "config": "tiny",
                           "traffic": "burst", "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "burst_metric", "unit": "ms",
                           "better": "lower", "source": "host_clock",
                           "layer": "test", "moves": "itl_p99_ms",
                           "workloads": ["tiny.burst"]})
    cell = harness.load_cell(b, "tiny.burst", True, root=root)
    assert cell.mix["process"] == "mmpp" and cell.conf["name"] == "tiny"
    assert [m["name"] for m in cell.metrics] == ["burst_metric"]
    assert harness.reader("burst_metric", root=root)(None) == 42.0
    assert cell.reference.init_params    # the reference beside the config


def test_a_metric_split_by_what_it_moves_shares_one_reader(tmp_path):
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "wave.py").write_text(
        "def read(run):\n    return run\n")
    for name in ("wave", "wave.chat", "wave.decode"):
        assert harness.reader(name, root=tmp_path)(7) == 7


def tiny_conf(**program):
    conf = json.loads((BENCH / "configs" /
                       "qwen3-0.6b.cim8x62.pinned.json").read_text())
    conf["model"].update(hidden_size=64, intermediate_size=128,
                         num_hidden_layers=2, num_attention_heads=2,
                         num_key_value_heads=1, head_dim=32,
                         vocab_size=256)
    conf["serving"].update(slots=2, max_len=32)
    conf["program"].update(program)
    return conf


def test_the_engine_is_built_from_the_config_alone():
    """Model flags, fleet policy and silicon come from the configuration
    file: a configuration that differs in each builds the engine it
    states, with no edit of the builder."""
    import jax
    from repro.models import transformer as T
    from bench import system
    conf = tiny_conf(model={"family": "lm", "qk_norm": False,
                            "mlp_type": "gelu"},
                     fleet={"n_macros": 1},
                     silicon={"thermal_sigma_v": 0.002})
    cfg = system.model_config(conf)
    assert (cfg.qk_norm, cfg.mlp_type, cfg.n_layers) == (False, "gelu", 2)
    params = T.lm_init(jax.random.PRNGKey(0), cfg)
    engine = system.build_engine(params, conf, 2**33 + 1)
    assert engine.schedule.pinned is False
    assert engine.schedule.rounds_max >= 2
    assert engine.silicon is not None
    pinned = system.build_engine(params, tiny_conf(
        model={"family": "lm", "qk_norm": False, "mlp_type": "gelu"}), 1)
    assert pinned.schedule.pinned and pinned.silicon is None


def run_py(cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "qwen3-pinned.long-decode", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def last_line_is_json(out: str) -> bool:
    lines = out.strip().splitlines()
    if not lines:
        return False
    try:
        json.loads(lines[-1])
        return True
    except ValueError:
        return False


def test_without_a_tpu_the_run_fails_and_prints_no_result():
    p = run_py(ROOT)
    assert p.returncode != 0
    assert not last_line_is_json(p.stdout)
    assert "no TPU" in p.stderr


def test_without_the_program_the_run_fails(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run_py(tmp_path)
    assert p.returncode != 0
    assert not last_line_is_json(p.stdout)


@pytest.mark.parametrize("key", ["command", "paths", "run_seconds",
                                 "configs", "workloads", "end_to_end",
                                 "per_layer"])
def test_benchmark_json_has_the_contract_keys(key):
    assert key in benchmark()
