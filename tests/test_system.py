"""End-to-end system behaviour: train/resume determinism, fault tolerance,
serving engine, data pipeline."""

import dataclasses
import os
import pathlib
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import (ModelConfig, ParallelConfig,
                                TrainConfig)
from repro.data.synthetic import DataConfig, image_batch, lm_batch
from repro.models import transformer as T
from repro.train import checkpoint as ckpt
from repro.train import train_loop as TL
from repro.train.ft import PreemptionHandler, StepWatchdog, run_with_restarts

CFG = ModelConfig(name="sys-tiny", family="lm", n_layers=2, d_model=32,
                  n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=64,
                  dtype=jnp.float32)
TCFG = TrainConfig(lr=3e-3, warmup_steps=2, total_steps=50)
DCFG = DataConfig(vocab_size=64, seq_len=32, global_batch=8, task="copy")


def _run_steps(state, step_fn, start, n):
    m = None
    for i in range(start, start + n):
        batch = jax.tree.map(jnp.asarray, lm_batch(DCFG, i))
        state, m = step_fn(state, batch)
    return state, m


class TestTrainResume:
    def test_checkpoint_resume_is_bitexact(self):
        """10 straight steps == 5 steps + save/restore + 5 steps."""
        step_fn = jax.jit(TL.make_train_step(CFG, ParallelConfig(
            remat="none"), TCFG))
        s0 = TL.init_state(jax.random.PRNGKey(0), CFG, TCFG)
        sA, _ = _run_steps(s0, step_fn, 0, 10)

        sB, _ = _run_steps(TL.init_state(jax.random.PRNGKey(0), CFG, TCFG),
                           step_fn, 0, 5)
        with tempfile.TemporaryDirectory() as d:
            ckpt.save(d, 5, sB)
            sB2 = ckpt.restore(d, jax.eval_shape(lambda: sB))
        sB3, _ = _run_steps(sB2, step_fn, 5, 5)

        for a, b in zip(jax.tree.leaves(sA.params),
                        jax.tree.leaves(sB3.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)

    def test_data_pipeline_stateless_and_host_sharded(self):
        b_all = lm_batch(DCFG, 7)
        b_again = lm_batch(DCFG, 7)
        np.testing.assert_array_equal(b_all["tokens"], b_again["tokens"])
        h0 = lm_batch(dataclasses.replace(DCFG, host_index=0,
                                          host_count=2), 7)
        h1 = lm_batch(dataclasses.replace(DCFG, host_index=1,
                                          host_count=2), 7)
        np.testing.assert_array_equal(
            np.concatenate([h0["tokens"], h1["tokens"]]), b_all["tokens"])

    def test_copy_task_has_learnable_structure(self):
        b = lm_batch(DCFG, 0)
        t = b["tokens"][0]
        half = (DCFG.seq_len + 1) // 2 + 1
        assert np.array_equal(t[half:], t[:DCFG.seq_len - half])


class TestFaultTolerance:
    def test_preemption_flag(self):
        h = PreemptionHandler()
        assert not h.preempted()
        h.trigger()
        assert h.preempted()

    def test_watchdog_flags_straggler(self):
        import time
        w = StepWatchdog(straggler_factor=5.0, stall_timeout_s=60)
        for i in range(12):
            time.sleep(0.005)
            w.tick(i)
        time.sleep(0.2)
        w.tick(99)
        assert any(s == 99 for s, _, _ in w.straggler_events)
        assert not w.stalled()

    def test_run_with_restarts_recovers(self):
        calls = []

        def loop(start):
            calls.append(start)
            if len(calls) < 3:
                raise RuntimeError("simulated node failure")
            return 123

        assert run_with_restarts(loop, max_restarts=5) == 123
        assert len(calls) == 3

    def test_checkpoint_atomic_commit_marker(self):
        with tempfile.TemporaryDirectory() as d:
            tree = {"a": jnp.arange(4.0)}
            ckpt.save(d, 1, tree)
            assert os.path.exists(os.path.join(
                d, "step_000000000001.COMMITTED"))
            # uncommitted dirs are invisible to latest_step
            os.makedirs(os.path.join(d, "step_000000000999"))
            assert ckpt.latest_step(d) == 1

    def test_checkpoint_retention_gc(self):
        with tempfile.TemporaryDirectory() as d:
            tree = {"a": jnp.arange(4.0)}
            for s in (1, 2, 3, 4):
                ckpt.save(d, s, tree)
            ckpt.gc_old(d, keep=2)
            assert ckpt.latest_step(d) == 4
            with pytest.raises(FileNotFoundError):
                ckpt.restore(d, tree, step=1)

    def test_elastic_restore_to_new_sharding(self):
        # restore with explicit shardings — the reshard path used when the
        # mesh changes between runs (elastic scaling)
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = jax.make_mesh((1,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        tree = {"w": jnp.arange(8.0).reshape(2, 4)}
        with tempfile.TemporaryDirectory() as d:
            ckpt.save(d, 3, tree)
            sh = {"w": NamedSharding(mesh, P("data", None))}
            out = ckpt.restore(d, tree, shardings=sh)
            np.testing.assert_array_equal(np.asarray(out["w"]),
                                          np.asarray(tree["w"]))
            assert out["w"].sharding == sh["w"]


class TestServeEngine:
    def test_continuous_batching_completes_all(self):
        from repro.serve.engine import Request, ServeEngine
        params = T.lm_init(jax.random.PRNGKey(0), CFG)
        eng = ServeEngine(params, CFG, slots=2, max_len=32)
        reqs = [Request(prompt=[1, 2, 3], max_new_tokens=4)
                for _ in range(5)]
        done = eng.run(reqs)
        assert len(done) == 5
        assert all(len(r.out) == 4 for r in done)

    def test_greedy_decode_deterministic(self):
        from repro.serve.engine import Request, ServeEngine
        params = T.lm_init(jax.random.PRNGKey(0), CFG)
        outs = []
        for _ in range(2):
            eng = ServeEngine(params, CFG, slots=1, max_len=32)
            done = eng.run([Request(prompt=[5, 6], max_new_tokens=6)])
            outs.append(done[0].out)
        assert outs[0] == outs[1]


class TestImageData:
    def test_class_blobs_deterministic_and_separable(self):
        x, y = image_batch(64, 10, 8, 1, 0)
        x2, y2 = image_batch(64, 10, 8, 1, 0)
        np.testing.assert_array_equal(x, x2)
        np.testing.assert_array_equal(y, y2)
        same = [float(np.corrcoef(x[i].ravel(), x[j].ravel())[0, 1])
                for i in range(16) for j in range(16)
                if i != j and y[i] == y[j]]
        diff = [float(np.corrcoef(x[i].ravel(), x[j].ravel())[0, 1])
                for i in range(16) for j in range(16) if y[i] != y[j]]
        assert np.mean(same or [1.0]) > np.mean(diff or [0.0])


class TestCompileCache:
    """``enable_compile_cache``: the environment's directory when set,
    else the repo's fixed ``.jax_cache``."""

    @pytest.fixture(autouse=True)
    def _restore(self):
        before = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", before)

    def test_env_dir_is_the_only_cache(self, monkeypatch, tmp_path):
        from repro.launch.compile_cache import enable_compile_cache
        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_is_the_repo_dir(self, monkeypatch):
        from repro.launch.compile_cache import (REPO_CACHE_DIR,
                                                enable_compile_cache)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert REPO_CACHE_DIR == pathlib.Path(root) / ".jax_cache"
        assert enable_compile_cache() == str(REPO_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE_DIR)
