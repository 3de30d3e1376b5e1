"""repro-lint (ISSUE 9): rule fixtures, suppression/baseline machinery,
and the REPRO_SANITIZE runtime sanitizer.

Each rule class is tested on the *historical bug shape* it encodes (true
positive) AND on the repaired/idiomatic shape (false-positive guard). The
self-scan pins the repo's finding count to the checked-in baseline, and
the engine tests show the sanitizer accepting the real fused-vs-einsum
contract and catching a deliberately injected violation.
"""

import dataclasses
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.analysis.engine import (analyze_source, diff_baseline,
                                   load_baseline, save_baseline)

REPO = Path(__file__).resolve().parents[1]


def rules_of(src: str, path: str = "mod.py") -> list[str]:
    report = analyze_source(textwrap.dedent(src), path)
    return [f.rule for f in report.findings]


def report_of(src: str, path: str = "mod.py"):
    return analyze_source(textwrap.dedent(src), path)


class TestR001KeyReuse:
    def test_fires_on_sequential_reuse(self):
        src = """
        import jax
        def f(key):
            a = jax.random.normal(key, (3,))
            b = jax.random.normal(key, (3,))
            return a + b
        """
        assert rules_of(src) == ["R001"]

    def test_fires_on_loop_replay(self):
        # the PR 6 shape: one key drawn from inside the step loop
        src = """
        import jax
        def f(key, n):
            outs = []
            for i in range(n):
                outs.append(jax.random.normal(key, (3,)))
            return outs
        """
        assert rules_of(src) == ["R001"]

    def test_split_and_fold_in_pass(self):
        src = """
        import jax
        def f(key, n):
            key, sub = jax.random.split(key)
            a = jax.random.normal(sub, (3,))
            outs = [a]
            for i in range(n):
                key, sub = jax.random.split(key)
                outs.append(jax.random.normal(sub, (3,)))
            for i in range(n):
                outs.append(jax.random.normal(
                    jax.random.fold_in(key, i), (3,)))
            return outs
        """
        assert rules_of(src) == []

    def test_reassignment_resets(self):
        src = """
        import jax
        def f(key):
            a = jax.random.normal(key, (3,))
            key = jax.random.fold_in(key, 1)
            b = jax.random.normal(key, (3,))
            return a + b
        """
        assert rules_of(src) == []

    def test_branches_do_not_cross_flag(self):
        src = """
        import jax
        def f(key, flip):
            if flip:
                return jax.random.normal(key, (3,))
            else:
                return jax.random.uniform(key, (3,))
        """
        assert rules_of(src) == []


class TestR002PytreeRebuild:
    BAD = """
    def strip(params):
        def walk(node):
            if isinstance(node, dict):
                return {k: walk(v) for k, v in node.items()}
            if isinstance(node, tuple):
                return tuple(walk(v) for v in node)
            return node
        return walk(params)
    """

    def test_fires_on_strip_silicon_shape(self):
        assert rules_of(self.BAD) == ["R002"]

    def test_fields_guard_passes(self):
        src = """
        def strip(params):
            def walk(node):
                if isinstance(node, dict):
                    return {k: walk(v) for k, v in node.items()}
                if isinstance(node, tuple):
                    if hasattr(node, "_fields"):
                        return node
                    return tuple(walk(v) for v in node)
                return node
            return walk(params)
        """
        assert rules_of(src) == []

    def test_type_reconstruction_passes(self):
        src = """
        def strip(params):
            def walk(node):
                if isinstance(node, tuple):
                    return type(node)(*[walk(v) for v in node])
                return node
            return walk(params)
        """
        assert rules_of(src) == []

    def test_plain_tuple_call_without_typetest_passes(self):
        src = """
        def f(xs):
            return tuple(x + 1 for x in xs)
        """
        assert rules_of(src) == []


class TestR003TraceCache:
    def test_fires_on_jit_in_loop(self):
        src = """
        import jax
        def f(fns, x):
            for fn in fns:
                x = jax.jit(fn)(x)
            return x
        """
        assert "R003" in rules_of(src)

    def test_fires_on_immediately_invoked(self):
        # the DriftMonitor shape: a fresh wrapper per probe call
        src = """
        import jax
        class Monitor:
            def probe(self, params, batch):
                return jax.jit(self._observe)(params, batch)
        """
        assert rules_of(src) == ["R003"]

    def test_fires_on_local_bind_and_call(self):
        src = """
        import jax
        def probe(fn, x):
            g = jax.jit(fn)
            return g(x)
        """
        assert rules_of(src) == ["R003"]

    def test_module_level_bind_passes(self):
        src = """
        import jax
        def _step(x):
            return x + 1
        step = jax.jit(_step)
        def serve(x):
            return step(x)
        """
        assert rules_of(src) == []

    def test_init_stash_and_factory_pass(self):
        src = """
        import jax
        class Engine:
            def __init__(self, fn):
                self.step_fn = jax.jit(fn)
        def make(fn):
            g = jax.jit(fn)
            return g
        """
        assert rules_of(src) == []

    def test_fires_on_mutable_closure(self):
        src = """
        import jax
        def build():
            acc = []
            @jax.jit
            def step(x):
                return x + len(acc)
            return step
        """
        assert rules_of(src) == ["R003"]


TAGGED = "# repro-lint: module=deterministic\n"


class TestR004Nondeterminism:
    def test_fires_on_clock_and_global_rng(self):
        src = TAGGED + textwrap.dedent("""
        import time, random
        import numpy as np
        def build(n):
            t = time.time()
            a = np.random.rand(n)
            b = random.random()
            return t, a, b
        """)
        assert sorted(rules_of(src)) == ["R004", "R004", "R004"]

    def test_fires_on_set_iteration(self):
        src = TAGGED + "def f(xs):\n    return [x for x in set(xs)]\n"
        assert rules_of(src) == ["R004"]

    def test_seeded_generator_and_sorted_pass(self):
        src = TAGGED + textwrap.dedent("""
        import numpy as np
        def build(n, seed):
            rng = np.random.default_rng(seed)
            return rng.normal(size=n), [x for x in sorted(set(range(n)))]
        """)
        assert rules_of(src) == []

    def test_untagged_module_is_exempt(self):
        src = "import time\ndef f():\n    return time.time()\n"
        assert rules_of(src) == []


EXACT = "# repro-lint: module=exactness-critical\n"


class TestR005FloatAccumulation:
    def test_fires_without_pragma(self):
        src = EXACT + textwrap.dedent("""
        import jax.numpy as jnp
        def recombine(codes, pw):
            return jnp.einsum("bnpc,p->bn", codes, pw)
        """)
        assert rules_of(src) == ["R005"]

    def test_fires_on_matmul_op_and_x64(self):
        src = EXACT + textwrap.dedent("""
        import jax.numpy as jnp
        def f(a, b):
            y = a @ b
            return y.astype(jnp.float64)
        """)
        assert sorted(rules_of(src)) == ["R005", "R005"]

    def test_exact_ok_pragma_passes(self):
        src = EXACT + textwrap.dedent("""
        import jax.numpy as jnp
        def recombine(codes, pw):
            # exact-ok: integer ADC codes x power-of-two plane weights
            return jnp.einsum("bnpc,p->bn", codes, pw)
        """)
        assert rules_of(src) == []

    def test_untagged_module_is_exempt(self):
        src = "import jax.numpy as jnp\ndef f(x):\n    return jnp.sum(x)\n"
        assert rules_of(src) == []


STEP = "# repro-lint: module=step-time\n"


class TestR006UnkeyedNoise:
    def test_fires_on_static_key(self):
        src = STEP + textwrap.dedent("""
        import jax
        def dither(noise_key, shape):
            return jax.random.normal(noise_key, shape)
        """)
        assert rules_of(src) == ["R006"]

    def test_clock_keyed_draw_passes(self):
        # the core/cim.py ProjectionSilicon.dither idiom, incl. the
        # transitive derivation through an intermediate name
        src = STEP + textwrap.dedent("""
        import jax
        from repro.core.cim import conversion_step
        def dither(noise_key, shape, salt):
            k = jax.random.fold_in(noise_key, conversion_step())
            k = jax.random.fold_in(k, salt)
            return jax.random.normal(k, shape)
        """)
        assert rules_of(src) == []

    def test_untagged_module_is_exempt(self):
        src = """
        import jax
        def dither(noise_key, shape):
            return jax.random.normal(noise_key, shape)
        """
        assert rules_of(src) == []


class TestSuppressions:
    BAD = TestR002PytreeRebuild.BAD

    def test_reasoned_suppression_suppresses(self):
        src = self.BAD.replace(
            "return tuple(walk(v) for v in node)",
            "return tuple(walk(v) for v in node)"
            "  # repro-lint: disable=R002 reason=tree is dict/list only")
        report = report_of(src)
        assert report.findings == []
        assert [f.rule for f, _ in report.suppressed] == ["R002"]

    def test_suppression_without_reason_is_a_finding(self):
        src = self.BAD.replace(
            "return tuple(walk(v) for v in node)",
            "return tuple(walk(v) for v in node)"
            "  # repro-lint: disable=R002")
        rules = rules_of(src)
        assert "R000" in rules and "R002" in rules

    def test_unused_suppression_is_a_finding(self):
        src = ("x = 1  # repro-lint: disable=R001 reason=nothing "
               "fires here\n")
        assert rules_of(src) == ["R000"]

    def test_comment_line_above_covers_next_line(self):
        src = self.BAD.replace(
            "            if isinstance(node, tuple):",
            "            # repro-lint: disable=R002 reason=dict-only "
            "trees\n            if isinstance(node, tuple):")
        # directive sits above the isinstance line, not the tuple() line:
        # it must NOT suppress the finding two lines down
        assert rules_of(src) == ["R002", "R000"] or \
            sorted(rules_of(src)) == ["R000", "R002"]

    def test_wrong_rule_id_does_not_suppress(self):
        src = self.BAD.replace(
            "return tuple(walk(v) for v in node)",
            "return tuple(walk(v) for v in node)"
            "  # repro-lint: disable=R001 reason=wrong id")
        rules = rules_of(src)
        assert "R002" in rules


class TestBaseline:
    def test_diff_flags_new_and_stale(self):
        report = report_of(TestR002PytreeRebuild.BAD, "a.py")
        base = [{"rule": "R002", "path": "a.py", "line": 999,
                 "message": "gone"}]
        new, stale = diff_baseline(report.findings, base)
        assert [f.rule for f in new] == ["R002"]
        assert stale == base

    def test_accepted_finding_passes(self):
        report = report_of(TestR002PytreeRebuild.BAD, "a.py")
        base = [{"rule": f.rule, "path": f.path, "line": f.line,
                 "message": f.message} for f in report.findings]
        new, stale = diff_baseline(report.findings, base)
        assert new == [] and stale == []

    def test_save_load_roundtrip(self, tmp_path):
        report = report_of(TestR002PytreeRebuild.BAD, "a.py")
        p = tmp_path / "baseline.json"
        save_baseline(p, report.findings)
        new, stale = diff_baseline(report.findings, load_baseline(p))
        assert new == [] and stale == []


class TestSelfScan:
    def test_repo_scan_matches_baseline(self):
        """The zero-unsuppressed-findings gate, in-process: scanning the
        repo's own src/benchmarks/tests must reproduce exactly the
        checked-in baseline (empty since ISSUE 9 paid all debt down)."""
        from repro.analysis.engine import (all_rules, analyze_file,
                                           iter_python_files)
        rules = all_rules()
        assert len([r for r in rules if r.startswith("R0") and
                    r != "R000"]) >= 6
        findings = []
        for f in iter_python_files(["src", "benchmarks", "tests"], REPO):
            findings.extend(analyze_file(f, REPO, rules).findings)
        baseline = load_baseline(REPO / "analysis_baseline.json")
        new, stale = diff_baseline(findings, baseline)
        assert new == [], "\n".join(f.human() for f in new)
        assert stale == []
        assert baseline == []   # the ledger finished ISSUE 9 empty

    def test_cli_gate(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text(textwrap.dedent(TestR002PytreeRebuild.BAD))
        env_root = str(REPO / "src")
        r = subprocess.run(
            [sys.executable, "-m", "repro.analysis", str(bad), "--json"],
            capture_output=True, text=True, cwd=tmp_path,
            env={"PYTHONPATH": env_root, "PATH": "/usr/bin:/bin"})
        assert r.returncode == 1, r.stderr
        payload = json.loads(r.stdout)
        assert [f["rule"] for f in payload["findings"]] == ["R002"]
        ok = tmp_path / "ok.py"
        ok.write_text("x = 1\n")
        r = subprocess.run(
            [sys.executable, "-m", "repro.analysis", str(ok)],
            capture_output=True, text=True, cwd=tmp_path,
            env={"PYTHONPATH": env_root, "PATH": "/usr/bin:/bin"})
        assert r.returncode == 0, r.stdout + r.stderr


class TestSanitizerUnits:
    def _silk(self, **over):
        from repro.core.cim import CimKernelSilicon
        g = 2.0 ** -14
        base = dict(
            wpc=jnp.full((2, 8, 3), 4096 * g, jnp.float32),
            gwc=jnp.full((3, 8), 16384 * g, jnp.float32),
            den=jnp.full((2, 3), 31.0, jnp.float32),
            off=jnp.zeros((2, 3), jnp.float32),
            rxp=jnp.full((8,), 16384 * g, jnp.float32),
            rx_den=jnp.full((2,), 31.0, jnp.float32),
            rx_off=jnp.zeros((2,), jnp.float32),
        )
        base.update(over)
        return CimKernelSilicon(**base)

    def test_quanta_invariant_accepts_grid(self):
        from repro.analysis.sanitize import check_cap_quanta
        check_cap_quanta({"layer": {"silk": self._silk()}})

    def test_quanta_invariant_rejects_off_grid(self):
        from repro.analysis.sanitize import SanitizeError, check_cap_quanta
        bad = self._silk(wpc=jnp.full((2, 8, 3), 1.0 / 3.0, jnp.float32))
        with pytest.raises(SanitizeError, match="fixed-point grid"):
            check_cap_quanta({"layer": {"silk": bad}})

    def test_quanta_invariant_rejects_overflow_budget(self):
        from repro.analysis.sanitize import SanitizeError, check_cap_quanta
        bad = self._silk(den=jnp.full((2, 3), 2048.0, jnp.float32))
        with pytest.raises(SanitizeError, match="2\\^24"):
            check_cap_quanta({"layer": {"silk": bad}})

    def test_tripwire_records_nan_and_saturation(self):
        from repro.analysis import sanitize
        from repro.core.cim import adc_codes
        sanitize.arm_tripwires(True)
        try:
            sanitize.drain_tripwires()
            codes = adc_codes(jnp.array([jnp.nan, 0.5]), 5)
            jax.block_until_ready(codes)
            log = sanitize.drain_tripwires()
            assert len(log) == 1 and log[0][0] > 0.0
            codes = adc_codes(jnp.array([2.0, 3.0]), 5)
            jax.block_until_ready(codes)
            log = sanitize.drain_tripwires()
            assert len(log) == 1 and log[0][1] == 1.0
        finally:
            sanitize.arm_tripwires(False)


def _kernel_engine(monkeypatch):
    from repro.configs.base import MFTechniqueConfig
    from repro.configs.qwen3_0_6b import SMOKE
    from repro.core.cim import CimConfig
    from repro.models import transformer as T
    from repro.serve.engine import ServeEngine
    monkeypatch.setenv("REPRO_SANITIZE", "1")
    cim = CimConfig(w_bits=8, x_bits=8, adc_bits=5, m_columns=31,
                    use_kernel=True)
    cfg = dataclasses.replace(SMOKE, dtype=jnp.float32,
                              mf=MFTechniqueConfig(mode="cim_sim",
                                                   cim=cim))
    params = T.lm_init(jax.random.PRNGKey(0), cfg)
    return ServeEngine(params, cfg, slots=2, max_len=16,
                       batched_prefill=False)


class TestSanitizerEngine:
    def test_clean_kernel_engine_passes_shadow_check(self, monkeypatch):
        from repro.serve.engine import Request
        eng = _kernel_engine(monkeypatch)
        assert eng._sanitizer is not None
        done = eng.run([Request(prompt=[1, 2], max_new_tokens=2)
                        for _ in range(2)])
        assert all(len(r.out) == 2 for r in done)
        assert eng._sanitizer.checked_steps >= 3

    def test_injected_kernel_mismatch_is_caught(self, monkeypatch):
        from repro.analysis.sanitize import SanitizeError
        from repro.core import programmed as P
        from repro.serve.engine import Request
        orig = P.cim_kernel_partials

        def corrupted(x2, ks, cfg, sx, dac_gains=None):
            # one ADC-code quantum of divergence on the fused path only
            parts = orig(x2, ks, cfg, sx, dac_gains)
            return parts._replace(s1c=parts.s1c.at[0, 0].add(1.0))

        monkeypatch.setattr(P, "cim_kernel_partials", corrupted)
        eng = _kernel_engine(monkeypatch)
        with pytest.raises(SanitizeError, match="fused/einsum divergence"):
            eng.run([Request(prompt=[1, 2], max_new_tokens=2)])

    def test_sanitize_off_attaches_nothing(self, monkeypatch):
        from repro.configs.base import MFTechniqueConfig
        from repro.configs.qwen3_0_6b import SMOKE
        from repro.core.cim import CimConfig
        from repro.models import transformer as T
        from repro.serve.engine import ServeEngine
        monkeypatch.delenv("REPRO_SANITIZE", raising=False)
        cim = CimConfig(w_bits=8, x_bits=8, adc_bits=5, m_columns=31)
        cfg = dataclasses.replace(SMOKE, dtype=jnp.float32,
                                  mf=MFTechniqueConfig(mode="cim_sim",
                                                       cim=cim))
        params = T.lm_init(jax.random.PRNGKey(0), cfg)
        eng = ServeEngine(params, cfg, slots=1, max_len=8,
                          batched_prefill=False)
        assert eng._sanitizer is None
