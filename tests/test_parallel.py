"""Distribution-layer tests: sharding rules + a multi-device subprocess
check of the EP MoE and a miniature production-mesh dry-run."""

import os
import subprocess
import sys
import textwrap

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import ParallelConfig
from repro.configs.registry import get_config
from repro.models import transformer as T
from repro.parallel import sharding as shd


def _mesh_stub(axis_sizes):
    class M:
        axis_names = tuple(axis_sizes)
        class devices:
            shape = tuple(axis_sizes.values())
    return M


class TestParamSpecs:
    def _specs(self, arch="qwen3-0.6b", pcfg=None, axes=None):
        cfg = get_config(arch, smoke=False)
        params = jax.eval_shape(lambda: T.lm_init(jax.random.PRNGKey(0),
                                                  cfg))
        pcfg = pcfg or ParallelConfig()
        axis_sizes = axes or {"data": 16, "model": 16}
        return jax.tree_util.tree_map_with_path(
            lambda p, l: shd.spec_for_param(p, l, pcfg, axis_sizes), params)

    def test_megatron_pattern(self):
        specs = self._specs()
        layer = specs["layers"][0]
        assert layer["attn"]["q"]["w"] == P(None, "data", "model")
        assert layer["attn"]["o"]["w"] == P(None, "model", "data")
        assert layer["mlp"]["up"]["w"] == P(None, "data", "model")
        assert layer["mlp"]["down"]["w"] == P(None, "model", "data")
        assert specs["embed"]["table"] == P("model", "data")

    def test_divisibility_guard(self):
        # whisper vocab 51865 is not divisible by 16 -> unsharded vocab dim
        cfg = get_config("whisper-base")
        from repro.models import encdec as E
        params = jax.eval_shape(lambda: E.encdec_init(jax.random.PRNGKey(0),
                                                      cfg))
        specs = jax.tree_util.tree_map_with_path(
            lambda p, l: shd.spec_for_param(p, l, ParallelConfig(),
                                            {"data": 16, "model": 16}),
            params)
        assert specs["embed"]["table"] == P(None, "data")

    def test_expert_specs_follow_ep_axes(self):
        cfg = get_config("deepseek-v3-671b")
        params = jax.eval_shape(lambda: T.lm_init(jax.random.PRNGKey(0),
                                                  cfg))
        pcfg = ParallelConfig(ep_axes=("data", "model"))
        specs = jax.tree_util.tree_map_with_path(
            lambda p, l: shd.spec_for_param(p, l, pcfg,
                                            {"data": 16, "model": 16}),
            params)
        up = specs["layers"][0]["moe"]["experts"]["up"]
        assert up == P(None, ("data", "model"), None, None)

    def test_norms_replicated(self):
        specs = self._specs()
        assert specs["final_norm"]["scale"] == P()

    def test_cache_specs_match_cache_structure(self):
        for arch in ("qwen3-0.6b", "recurrentgemma-2b", "xlstm-350m",
                     "deepseek-v3-671b"):
            cfg = get_config(arch)
            cache = jax.eval_shape(lambda c=cfg: T.lm_init_cache(c, 8, 64))
            pcfg = ParallelConfig()
            specs = T.lm_cache_pspecs(cfg, cache, pcfg,
                                      {"data": 16, "model": 16})
            # structures must match exactly (same treedef)
            jax.tree.map(lambda a, b: None, cache, specs,
                         is_leaf=lambda x: isinstance(x, P))


MULTIDEV_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp
    from repro.configs.base import (ModelConfig, MoEConfig, ParallelConfig,
                                    MFTechniqueConfig)
    from repro.models import transformer as T

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = ModelConfig(name="t", family="moe", n_layers=2, d_model=32,
                      n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=64,
                      moe=MoEConfig(n_experts=8, top_k=2, n_shared=1,
                                    d_ff_expert=48, capacity_factor=4.0,
                                    expert_capacity_factor=4.0),
                      dtype=jnp.float32, mf=MFTechniqueConfig(enabled=False))
    params = T.lm_init(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 16),
                                          0, 64),
             "targets": jax.random.randint(jax.random.PRNGKey(2), (4, 16),
                                           0, 64)}
    ref, _ = T.lm_loss(params, batch, cfg)
    pcfg = ParallelConfig(remat="none")
    pctx = T.ParallelContext(mesh=mesh, cfg=pcfg)
    with mesh:
        ep, _ = jax.jit(lambda p, b: T.lm_loss(p, b, cfg, pctx))(params,
                                                                 batch)
    diff = abs(float(ref) - float(ep))
    assert diff < 0.05, diff
    # mini production-style dry-run on the 2x4 mesh
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.parallel import sharding as shd
    specs = shd.params_pspecs(jax.eval_shape(
        lambda: T.lm_init(jax.random.PRNGKey(0), cfg)), pcfg, mesh)
    sh = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                      is_leaf=lambda x: isinstance(x, P))
    with mesh:
        lowered = jax.jit(lambda p, b: T.lm_loss(p, b, cfg, pctx)[0],
                          in_shardings=(sh, {"tokens": NamedSharding(
                              mesh, P("data", None)), "targets":
                              NamedSharding(mesh, P("data", None))})
                          ).lower(jax.eval_shape(
                              lambda: T.lm_init(jax.random.PRNGKey(0),
                                                cfg)), batch)
        compiled = lowered.compile()
        assert compiled.cost_analysis() is not None
    print("MULTIDEV_OK", diff)
""")


@pytest.mark.slow
def test_ep_moe_multidevice_subprocess():
    """EP MoE == dense MoE on a real 2x4 device mesh (subprocess so the
    fake device count doesn't leak into this test session)."""
    # Pinned to the CPU backend: the forced host devices are the point,
    # and the child must never contend for an accelerator this session
    # may hold.
    r = subprocess.run([sys.executable, "-c", MULTIDEV_SCRIPT],
                       capture_output=True, text=True, timeout=540,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert "MULTIDEV_OK" in r.stdout, r.stdout + r.stderr
