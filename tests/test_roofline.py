"""Roofline analyzer tests: HLO collective parser, terms, param counting."""

try:
    import hypothesis
    import hypothesis.strategies as st
except ImportError:  # deterministic fallback, see tests/_hypothesis_stub.py
    from _hypothesis_stub import hypothesis, st
import jax
import jax.numpy as jnp
import pytest

from repro.roofline import analysis as R

HLO_SAMPLE = """
HloModule test
ENTRY main {
  %p0 = f32[8,128]{1,0} parameter(0)
  %ag = f32[8,2048]{1,0} all-gather(%p0), dimensions={1}
  %ar = bf16[1024]{0} all-reduce(%x), to_apply=%add
  %rs = f32[4,64]{1,0} reduce-scatter(%y), dimensions={0}
  %a2a = s32[16,32]{1,0} all-to-all(%z)
  %cp = bf16[2,2]{1,0} collective-permute(%w)
  %ars = f32[512]{0} all-reduce-start(%v)
  %dot = f32[8,8]{1,0} dot(%p0, %p0t)
}
"""


class TestCollectiveParser:
    def test_counts_each_kind(self):
        out = R.collective_bytes(HLO_SAMPLE)
        assert out["all-gather"] == 8 * 2048 * 4
        # all-reduce + all-reduce-start both count
        assert out["all-reduce"] == 1024 * 2 + 512 * 4
        assert out["reduce-scatter"] == 4 * 64 * 4
        assert out["all-to-all"] == 16 * 32 * 4
        assert out["collective-permute"] == 2 * 2 * 2

    def test_ignores_non_collectives(self):
        out = R.collective_bytes("%d = f32[64,64]{1,0} dot(%a, %b)")
        assert sum(out.values()) == 0

    @hypothesis.given(st.integers(1, 64), st.integers(1, 64))
    @hypothesis.settings(max_examples=20, deadline=None)
    def test_shape_bytes(self, a, b):
        assert R._shape_bytes(f"f32[{a},{b}]") == a * b * 4
        assert R._shape_bytes(f"bf16[{a}]") == a * 2

    def test_tuple_result(self):
        s = "%t = (f32[8]{0}, bf16[4]{0}) all-reduce(%a, %b)"
        out = R.collective_bytes(s)
        assert out["all-reduce"] == 8 * 4 + 4 * 2


class TestTerms:
    def test_dominant_and_seconds(self):
        pk = R.peaks_for("TPU v5 lite")
        t = R.RooflineTerms(flops=pk.flops, hbm_bytes=pk.hbm_bw * 2,
                            coll_bytes=pk.link_bw * 0.5, chips=1,
                            device_kind="TPU v5 lite")
        assert t.compute_s == 1.0
        assert t.memory_s == 2.0
        assert t.collective_s == 0.5
        assert t.dominant == "memory"
        assert t.bound_s == 2.0

    def test_real_compiled_cost(self):
        f = jax.jit(lambda a, b: a @ b)
        x = jnp.ones((128, 128))
        c = f.lower(x, x).compile()
        t = R.terms_from_compiled(c, chips=1, device_kind="TPU v5 lite")
        # 2*M*N*K flops for a 128^3 matmul
        assert t.flops >= 2 * 128 ** 3 * 0.9
        assert t.coll_bytes == 0


    def test_v5e_peaks_match_published(self):
        pk = R.peaks_for("TPU v5 lite")
        assert (pk.flops, pk.hbm_bw) == (197e12, 819e9)
        assert pk.link_bw * 4 * 8 == 1600e9
        assert "TPU v5e" in pk.source

    @pytest.mark.parametrize("kind", ["cpu", "TPU v4", ""])
    def test_unknown_device_kind_raises(self, kind):
        with pytest.raises(ValueError, match="no published peaks"):
            R.peaks_for(kind)
        with pytest.raises(ValueError, match="no published peaks"):
            R.RooflineTerms(flops=1.0, hbm_bytes=1.0, coll_bytes=0.0,
                            chips=1, device_kind=kind)


class TestModelFlops:
    def test_count_params_moe_active_fraction(self):
        tree = {"layers": {"moe": {"experts": {
            "up": jax.ShapeDtypeStruct((8, 4, 16), jnp.float32)},
            "router": {"w": jax.ShapeDtypeStruct((4, 8), jnp.float32)}}},
            "embed": {"table": jax.ShapeDtypeStruct((10, 4), jnp.float32)}}
        counts = R.count_params(tree, active_expert_fraction=0.25)
        total_experts = 8 * 4 * 16
        assert counts["total"] == total_experts + 32 + 40
        assert counts["active"] == int(total_experts * 0.25) + 32 + 40

    def test_model_flops_conventions(self):
        assert R.model_flops(100, 10, "train") == 6 * 100 * 10
        assert R.model_flops(100, 10, "decode") == 2 * 100 * 10
