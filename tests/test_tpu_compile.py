"""Mosaic lowering of the Pallas kernels for a described TPU v5e chip.

Compiles, never runs, the kernels of the serving path at qwen3-0.6b widths
(d_model 1024, d_ff 3072) for one chip of a described ``v5e:2x2``
topology. This catches what interpret mode cannot: block shapes off the
TPU's (8, 128) tiling, dtypes Mosaic refuses, VMEM overuse. The topology
is described inside a module fixture (one process per libtpu), and every
compiled program must contain the kernel's ``tpu_custom_call``.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.cim_mav import (CHUNK_PAD, CHUNKS_PER_TILE,
                                   cim_mav_pallas, cim_mav_sil_pallas)
from repro.kernels.mf_matmul import mf_matmul_pallas

M, PW, B, N = 31, 7, 8, 3072


def _kp(k: int) -> int:
    """Packed K of a K-deep projection: chunks of M real lanes, padded
    to CHUNK_PAD lanes, chunk count rounded up to whole 128-lane tiles."""
    c = -(-k // M)
    return -(-c // CHUNKS_PER_TILE) * CHUNKS_PER_TILE * CHUNK_PAD


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any describe failure skips
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # A compile for a described chip cannot be read back from the
        # persistent cache without that chip: keep it out of the cache.
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("k", [1024, 3072], ids=["K1024", "K3072"])
def test_cim_mav_lowers_with_int8_planes(one_chip, k):
    """S1 side: f32 input gates against the int8 programmed planes."""
    kp = _kp(k)
    assert kp == {1024: 1152, 3072: 3200}[k]
    _compile(one_chip,
             lambda g, p: cim_mav_pallas(g, p, m_columns=M, adc_bits=5),
             ((1, B, kp), jnp.float32), ((PW, kp, N), jnp.int8))


@pytest.mark.parametrize("k", [1024, 3072], ids=["K1024", "K3072"])
def test_cim_mav_lowers_with_int8_gates(one_chip, k):
    """S2 side: f32 input planes streamed against the int8 programmed
    gates."""
    kp = _kp(k)
    _compile(one_chip,
             lambda g, p: cim_mav_pallas(g, p, m_columns=M, adc_bits=5),
             ((PW, B, kp), jnp.float32), ((1, kp, N), jnp.int8))


@pytest.mark.parametrize("dither", [False, True],
                         ids=["static", "dither"])
@pytest.mark.parametrize("k", [1024, 3072], ids=["K1024", "K3072"])
def test_cim_mav_sil_lowers(one_chip, k, dither):
    """The fused silicon kernel: cap-folded planes, per-(chunk, channel)
    den/off tiles and optional thermal dither."""
    kp = _kp(k)
    ct = kp // CHUNK_PAD
    shapes = [((1, B, kp), jnp.float32), ((PW, kp, N), jnp.float32),
              ((ct, N), jnp.float32), ((ct, N), jnp.float32)]
    if dither:
        shapes.append(((PW, ct, B, N), jnp.float32))
    _compile(one_chip,
             lambda *ops: cim_mav_sil_pallas(*ops, adc_bits=5), *shapes)


def test_cim_mav_sil_lowers_streamed_planes(one_chip):
    """S2 side of the silicon kernel: bit-serial input planes against
    plane-static cap-folded gates."""
    kp = _kp(1024)
    ct = kp // CHUNK_PAD
    _compile(one_chip,
             lambda *ops: cim_mav_sil_pallas(*ops, adc_bits=5),
             ((PW, B, kp), jnp.float32), ((1, kp, N), jnp.float32),
             ((ct, N), jnp.float32), ((ct, N), jnp.float32))


def test_mf_matmul_lowers(one_chip):
    _compile(one_chip, lambda x, w: mf_matmul_pallas(x, w),
             ((128, 1024), jnp.float32), ((1024, N), jnp.float32))
