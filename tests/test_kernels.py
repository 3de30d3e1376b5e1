"""Pallas kernel sweeps vs pure-jnp oracles (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.ref import cim_mav_ref, mf_matmul_ref


def _tol(dtype):
    # f32 tolerance allows tiling-order accumulation differences.
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=1e-4, atol=1e-4)


class TestMFMatmulKernel:
    @pytest.mark.parametrize("m,k,n", [
        (8, 128, 128), (128, 128, 128), (5, 37, 9), (130, 260, 70),
        (1, 512, 256), (256, 96, 384),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_shapes_dtypes(self, m, k, n, dtype):
        x = jax.random.normal(jax.random.PRNGKey(0), (m, k)).astype(dtype)
        w = jax.random.normal(jax.random.PRNGKey(1), (k, n)).astype(dtype)
        y = ops.mf_matmul(x, w)
        yr = mf_matmul_ref(x, w)
        np.testing.assert_allclose(np.asarray(y, np.float32),
                                   np.asarray(yr, np.float32), **_tol(dtype))

    def test_batched(self):
        x = jax.random.normal(jax.random.PRNGKey(2), (3, 4, 40))
        w = jax.random.normal(jax.random.PRNGKey(3), (40, 24))
        y = ops.mf_matmul(x, w)
        assert y.shape == (3, 4, 24)
        np.testing.assert_allclose(
            y.reshape(-1, 24), mf_matmul_ref(x.reshape(-1, 40), w),
            rtol=1e-5, atol=1e-5)

    def test_block_size_invariance(self):
        x = jax.random.normal(jax.random.PRNGKey(4), (64, 200))
        w = jax.random.normal(jax.random.PRNGKey(5), (200, 72))
        y1 = ops.mf_matmul(x, w, bm=32, bn=128, bk=128)
        y2 = ops.mf_matmul(x, w, bm=64, bn=256, bk=256)
        np.testing.assert_allclose(y1, y2, rtol=1e-4, atol=1e-4)


class TestCimMavKernel:
    @pytest.mark.parametrize("b,k,n", [(6, 70, 17), (8, 128, 128),
                                       (1, 31, 5), (16, 300, 64)])
    @pytest.mark.parametrize("m_cols,adc", [(31, 5), (15, 4), (31, 3)])
    def test_sweep(self, b, k, n, m_cols, adc):
        kg = jax.random.PRNGKey(b * 100 + k)
        kp = jax.random.PRNGKey(n)
        gates = jax.random.bernoulli(kg, 0.5, (b, k)).astype(jnp.float32)
        planes = jax.random.bernoulli(kp, 0.5, (7, k, n)).astype(jnp.float32)
        y = ops.cim_mav(gates, planes, m_columns=m_cols, adc_bits=adc)
        g2 = ops.pack_chunks(gates, m_cols)
        p2 = jnp.moveaxis(ops.pack_chunks(jnp.moveaxis(planes, -1, 1),
                                          m_cols), 1, -1)
        yr = cim_mav_ref(g2, p2, m_columns=m_cols, adc_bits=adc)
        np.testing.assert_allclose(y, yr, rtol=1e-5, atol=1e-5)

    def test_pad_columns_inert(self):
        # Zero-pad lanes never 'discharge': result independent of K padding.
        gates = jnp.ones((2, 31), jnp.float32)
        planes = jnp.ones((3, 31, 8), jnp.float32)
        y1 = ops.cim_mav(gates, planes, m_columns=31, adc_bits=5)
        gates2 = jnp.pad(gates, ((0, 0), (0, 10)))
        planes2 = jnp.pad(planes, ((0, 0), (0, 10), (0, 0)))
        y2 = ops.cim_mav(gates2, planes2, m_columns=31, adc_bits=5)
        np.testing.assert_allclose(y1, y2)


@pytest.mark.parametrize("backend,interpret", [("cpu", True), ("tpu", False),
                                               ("gpu", None)])
def test_interpret_mode_per_backend(monkeypatch, backend, interpret):
    """CPU interprets, TPU compiles to Mosaic, anything else raises rather
    than interpreting on an accelerator."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match="'gpu'"):
            ops.interpret_mode()
    else:
        assert ops.interpret_mode() is interpret
