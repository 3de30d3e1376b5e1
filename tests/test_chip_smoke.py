"""chip_smoke.py's phases on the CPU at the qwen3 smoke size.

The script's ``main`` needs a TPU; these tests drive its phase functions
(the same engines, checks and assertions) at a size the CPU serves in
seconds, so a change that breaks the on-chip smoke shows up here first.
"""

import json
import os
import shutil
import subprocess
import sys
import textwrap

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke as cs  # noqa: E402
from repro.configs.qwen3_0_6b import SMOKE  # noqa: E402

SMALL = cs.Sizes(slots=4, max_len=32, n_requests=4, prompt_min=4,
                 prompt_max=12, new_tokens=4, silicon_new_tokens=2)


@pytest.fixture(scope="module")
def model():
    cfg = cs.serve_config(SMOKE)
    params = cs.T.lm_init(jax.random.PRNGKey(0), cfg)
    return cfg, params, cs.pinned_fleet(params, cfg.mf.cim, SMALL.slots)


@pytest.fixture(scope="module")
def phase_a_tokens(model):
    return cs.phase_a(*model, SMALL, seed=0)


def test_pinned_fleet_is_the_smallest_that_pins(model):
    cfg, params, fleet = model
    sched, _ = cs.fleet_schedule(params, fleet, SMALL.slots)
    assert sched.pinned
    smaller = cs.Fleet(n_macros=fleet.n_macros - 1, cfg=cfg.mf.cim)
    assert not cs.fleet_schedule(params, smaller, SMALL.slots)[0].pinned


def test_phase_a_serves_every_request(phase_a_tokens):
    assert len(phase_a_tokens) == SMALL.n_requests
    assert all(len(t) == SMALL.new_tokens for t in phase_a_tokens)


def test_phase_b_kernel_tokens_equal_phase_a(model, phase_a_tokens):
    cs.phase_b(*model, SMALL, 0, phase_a_tokens)


def test_phase_b_rejects_diverged_tokens(model, phase_a_tokens):
    wrong = [[(t + 1) % SMOKE.vocab_size for t in toks]
             for toks in phase_a_tokens]
    with pytest.raises(AssertionError, match="tokens diverged"):
        cs.phase_b(*model, SMALL, 0, wrong)


def test_phase_c_silicon_kernel_under_sanitizer():
    cs.phase_c(SMOKE, SMALL, seed=0, layers=SMOKE.n_layers)


def test_silicon_depth_cut_at_published_widths():
    cfg = cs.serve_config(cs.CONFIG)
    per_layer, fixed = cs.silicon_bytes_per_layer(cfg)
    # The f32 cap fold alone is 4 * (Pw + 1) * Kp/K >= 32 B per weight.
    weights = sum(
        k * n for k, n in [(1024, 2048), (1024, 1024), (1024, 1024),
                           (2048, 1024), (1024, 3072), (1024, 3072),
                           (3072, 1024)])
    assert per_layer > 32 * weights
    assert fixed == 4 * cfg.vocab_size * cfg.d_model
    depth = cs.silicon_depth(cfg, 16e9)
    assert 1 <= depth < cfg.n_layers
    assert cs.silicon_depth(cfg, 32e9) > depth
    assert cs.silicon_depth(cfg, 1e12) == cfg.n_layers


def test_main_refuses_without_a_tpu():
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert '"ok"' not in r.stdout


def test_script_alone_fails(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=300,
                       env=dict(env, JAX_PLATFORMS="cpu"))
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


MESH_SCRIPT = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, os.getcwd())
    import jax
    import chip_smoke as cs
    from repro.configs.qwen3_0_6b import SMOKE
    sizes = cs.Sizes(**{sizes})
    cfg = cs.serve_config(SMOKE)
    params = cs.T.lm_init(jax.random.PRNGKey(0), cfg)
    fleet = cs.pinned_fleet(params, cfg.mf.cim, sizes.slots)
    cs.phase_mesh(cfg, params, fleet, sizes, 0, jax.devices())
    print("MESH_OK", len(jax.devices()))

    # Under the fleet-sharded mesh, projections gather their outputs: the
    # float model reduces whole rows, with no all-reduce in the step.
    import jax.numpy as jnp
    from repro.launch.mesh import make_serve_mesh
    from repro.serve.engine import make_serve_step
    from repro.traffic import shard_engine
    eng = cs.ServeEngine(params, cfg, slots=sizes.slots,
                         max_len=sizes.max_len, fleet=fleet)
    mesh = make_serve_mesh(data=2, fleet=2, devices=jax.devices()[:4])
    shard_engine(eng, mesh)
    with jax.set_mesh(mesh):
        hlo = jax.jit(make_serve_step(cfg)).lower(
            eng._exec_params, eng.cache,
            jnp.zeros((sizes.slots,), jnp.int32), jax.random.PRNGKey(0),
            jnp.int32(0)).compile().as_text()
    print("ALL_REDUCES", hlo.count(" all-reduce("))
""")


def test_mesh_phase_on_four_cpu_devices():
    """The ``--chips 4`` path on four forced host devices (a CPU child, so
    the device count does not leak into this session)."""
    sizes = json.dumps(cs.dataclasses.asdict(SMALL))
    r = subprocess.run([sys.executable, "-c",
                        MESH_SCRIPT.replace("{sizes}", sizes)],
                       capture_output=True, text=True, timeout=600,
                       cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert "MESH_OK 4" in r.stdout, r.stdout + r.stderr
    assert "sharded tokens equal unsharded" in r.stdout
    assert "ALL_REDUCES 0" in r.stdout, r.stdout
