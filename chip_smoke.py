#!/usr/bin/env python3
"""On-chip smoke test: programmed CIM serving of qwen3-0.6b on a TPU.

Drives the serving engine (``repro.serve.engine.ServeEngine``) at qwen3-0.6b's
published widths (28 layers, d_model 1024, 16/8 GQA heads, head_dim 128,
d_ff 3072, vocab 151936, tied embeddings) with every MF projection mapped to
the paper's 8x62 CIM design point (W_P=8, X_P=8, A_P=5, M=31), in float32,
with random weights from ``--seed``. One process holds the chip throughout.

    python chip_smoke.py            # one chip: phases A, B, C
    python chip_smoke.py --chips 4  # four chips: the sharded path only

Phase A  pinned fleet, lossless collapse: 8 greedy requests through batched
         prefill and decode, the runtime sanitizer asserting the programmed
         partials equal the plane-level einsum reference bitwise on the
         first ticks, and prefill / first-decode logits held to a float
         reference run under HIGHEST matmul precision.
Phase B  the same model on the fused Pallas MAV kernel (sigma = 0): the
         sanitizer holds the kernel's partials to the plane einsum's, and
         its greedy tokens equal Phase A's exactly.
Phase C  a sampled SA-ADC fleet (sigma > 0, thermal dither on) on the fused
         silicon kernel, depth cut to what its float32 cap-folded state lets
         one chip hold: the sanitizer asserts the fused codes equal the
         einsum reference's bitwise.
--chips 4  the Phase A engine sharded over a (data=2, fleet=2) serve mesh
         against the same engine unsharded on device 0: greedy tokens
         identical.

Set-up and throughput lines are informational. The last line of stdout is
``{"ok": true, "device": {...}}``; a failed phase raises and the script exits
non-zero. Without a TPU it exits non-zero before any phase runs.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compiler.tiling import Fleet  # noqa: E402
from repro.configs.base import MFTechniqueConfig, ModelConfig  # noqa: E402
from repro.configs.qwen3_0_6b import CONFIG  # noqa: E402
from repro.core.cim import CimConfig  # noqa: E402
from repro.core.programmed import (iter_projections,  # noqa: E402
                                   programmed_bytes)
from repro.models import transformer as T  # noqa: E402
from repro.serve.engine import (Request, ServeEngine,  # noqa: E402
                                fleet_schedule)
from repro.silicon import SiliconConfig  # noqa: E402

# The paper's 8x62 design point (exactly lossless: 2^5 - 1 == 31).
CIM = CimConfig(w_bits=8, x_bits=8, adc_bits=5, m_columns=31)

# Phase A's float reference is the same program under HIGHEST matmul
# precision. The CIM projections are exact under either precision (their
# partials are integers); what moves is the float model around them
# (attention, norms, the tied LM head), which a TPU runs as one bf16 pass
# at default precision, plus the activation-quantiser steps that error
# shifts. Bound: max |delta logit| <= LOGIT_RTOL * max |reference logit|.
LOGIT_RTOL = 0.1

# Phase C's sigma > 0 silicon: SiliconConfig's default cap-DAC mismatch and
# comparator offset, plus a per-conversion thermal floor so the fused
# kernel's dither operand runs too.
SILICON = SiliconConfig(thermal_sigma_v=0.002)

# Share of the chip's HBM Phase C's steady state may take. The rest is
# headroom for program-time transients, the KV cache and the prefill
# wave, whose per-conversion dither tensors (tokens x N x Pw x C, f32)
# take ~6.5 GB for one d_ff projection at 8 x 128 tokens (XLA's memory
# analysis of the prefill program compiled for a v5e).
SILICON_HBM_SHARE = 0.4


@dataclasses.dataclass(frozen=True)
class Sizes:
    slots: int = 8
    max_len: int = 256
    n_requests: int = 8
    prompt_min: int = 32
    prompt_max: int = 128
    new_tokens: int = 32
    silicon_new_tokens: int = 8
    checked_ticks: int = 8      # sanitized decode ticks; the rest are timed


FULL = Sizes()


def log(msg: str) -> None:
    print(msg, flush=True)


def serve_config(base: ModelConfig, use_kernel: bool = False,
                 n_layers: int | None = None) -> ModelConfig:
    cim = dataclasses.replace(CIM, use_kernel=use_kernel)
    return dataclasses.replace(
        base, dtype=jnp.float32, n_layers=n_layers or base.n_layers,
        mf=MFTechniqueConfig(mode="cim_sim", cim=cim))


def make_requests(cfg: ModelConfig, sizes: Sizes, seed: int,
                  new_tokens: int) -> list[Request]:
    rng = np.random.default_rng(seed)
    lens = rng.integers(sizes.prompt_min, sizes.prompt_max + 1,
                        sizes.n_requests)
    return [Request(prompt=rng.integers(1, cfg.vocab_size, n).tolist(),
                    max_new_tokens=new_tokens) for n in lens]


def pinned_fleet(params, cim: CimConfig, slots: int) -> Fleet:
    """The smallest fleet that pins the model, sized from the engine's own
    schedule (as ``benchmarks/serve_bench.py`` sizes its pinned fleet)."""
    probe = Fleet(n_macros=1, cfg=cim)
    sched, _ = fleet_schedule(params, probe, slots)
    return Fleet(n_macros=-(-sched.total_tiles // probe.halves_per_macro),
                 cfg=cim)


@contextlib.contextmanager
def sanitizer():
    """``REPRO_SANITIZE=1`` for the engines built (and traced) inside."""
    prev = os.environ.get("REPRO_SANITIZE")
    os.environ["REPRO_SANITIZE"] = "1"
    try:
        yield
    finally:
        if prev is None:
            del os.environ["REPRO_SANITIZE"]
        else:
            os.environ["REPRO_SANITIZE"] = prev


def admit(engine: ServeEngine, reqs: list[Request]) -> float:
    """Admit ``reqs`` in one wave (batched prefill); returns its seconds."""
    assert len(reqs) <= engine.slots
    t0 = time.perf_counter()
    assert engine.submit_many(reqs) == len(reqs)
    jax.block_until_ready(engine.cache)
    return time.perf_counter() - t0


def decode(engine: ServeEngine, reqs: list[Request],
           checked_ticks: int = 0) -> dict:
    """Decode admitted ``reqs`` to completion and check every request
    finished with its token budget. The first ``checked_ticks`` ticks run
    under the engine's sanitizer (if it has one), which is then detached
    so the remaining ticks time the served program alone."""
    san = engine._sanitizer
    t0 = time.perf_counter()
    engine.step()                       # compiles the decode step
    t1 = time.perf_counter()
    while engine.occupied_slots and \
            engine.counters()["decode_steps"] < checked_ticks:
        engine.step()
    engine._sanitizer = None
    steady = engine.counters()["decode_tokens"]
    t2 = time.perf_counter()
    while engine.occupied_slots:
        engine.step()
    t3 = time.perf_counter()
    for r in reqs:
        assert r.done and not r.timed_out and not r.evicted, r
        assert len(r.out) == r.max_new_tokens, (len(r.out), r.max_new_tokens)
    tokens = engine.counters()["decode_tokens"] - steady
    checked = san.checked_steps if san is not None else 0
    assert checked == (min(checked_ticks, engine.counters()["decode_steps"])
                       if san is not None else 0), checked
    return {"first_tick_s": t1 - t0, "checked_ticks": checked,
            "decode_tok_s": tokens / (t3 - t2) if tokens else float("nan")}


def same_tokens(got: list[list[int]], want: list[list[int]]) -> str:
    """Asserts two runs' greedy tokens are identical; returns a summary
    line (positions that agree, first differing position per request)."""
    same = sum(a == b for g, w in zip(got, want) for a, b in zip(g, w))
    total = sum(len(w) for w in want)
    first = [next((i for i, (a, b) in enumerate(zip(g, w)) if a != b), None)
             for g, w in zip(got, want)]
    line = (f"{same}/{total} positions agree (first differing position per "
            f"request: {first})")
    assert got == want, f"tokens diverged: {line}"
    return line


def _max_delta(got, ref) -> tuple[float, float]:
    got, ref = np.asarray(got), np.asarray(ref)
    assert np.all(np.isfinite(got)) and np.all(np.isfinite(ref))
    return float(np.max(np.abs(got - ref))), float(np.max(np.abs(ref)))


def check_against_highest(engine: ServeEngine, cfg: ModelConfig,
                          reqs: list[Request], prompt_len: int) -> None:
    """Prefill and first-decode logits vs the same programs under HIGHEST
    matmul precision. Call after admission, before the first decode tick."""
    prefill = jax.jit(lambda p, t: T.serve_prefill(p, {"tokens": t}, cfg))
    toks = jnp.asarray([r.prompt[:prompt_len] for r in reqs], jnp.int32)
    feed = jnp.asarray(
        [r.prompt[-1] for r in reqs]
        + [0] * (engine.slots - len(reqs)), jnp.int32)
    key, step = jax.random.PRNGKey(0), jnp.int32(engine.stream_index)

    def logits():
        _, dec, _ = engine.step_fn(engine._exec_params, engine.cache, feed,
                                   key, step)
        return prefill(engine._exec_params, toks), dec[:len(reqs)]

    served = logits()       # the engine's own decode program, as compiled
    with jax.default_matmul_precision("highest"):
        ref = logits()
    for i, what in enumerate(("prefill", "first-decode")):
        delta, scale = _max_delta(served[i], ref[i])
        log(f"phase A: {what} logits max|delta| vs HIGHEST = {delta:.6g} "
            f"(max|ref| = {scale:.6g}, bound {LOGIT_RTOL} * max|ref|)")
        assert delta <= LOGIT_RTOL * scale, (what, delta, scale)


def phase_a(cfg: ModelConfig, params, fleet: Fleet, sizes: Sizes,
            seed: int) -> list[list[int]]:
    """Pinned-fleet lossless serving, sanitized for the first ticks;
    returns the greedy tokens of every request."""
    reqs = make_requests(cfg, sizes, seed, sizes.new_tokens)
    with sanitizer():
        t0 = time.perf_counter()
        engine = ServeEngine(params, cfg, slots=sizes.slots,
                             max_len=sizes.max_len, fleet=fleet)
        setup_s = time.perf_counter() - t0
        assert engine.schedule.pinned and engine._sanitizer is not None
        progs = [n["prog"] for _, n, _ in iter_projections(
            engine._exec_params)]
        assert progs and all(p.lossless is not None for p in progs)
        prefill_s = admit(engine, reqs)
        check_against_highest(engine, cfg, reqs, sizes.prompt_min)
        stats = decode(engine, reqs, sizes.checked_ticks)
    log(f"phase A: set-up {setup_s:.1f}s (program_weights + sanitizer "
        f"shadow, {fleet.tile_slots} tile slots pinned), prefill wave "
        f"{prefill_s:.1f}s and first tick {stats['first_tick_s']:.1f}s "
        f"incl. compile, decode ~{stats['decode_tok_s']:.1f} tok/s "
        f"(informational)")
    log(f"phase A: ok — {len(reqs)} requests x {sizes.new_tokens} tokens, "
        f"lossless partials == plane-einsum reference bitwise on "
        f"{stats['checked_ticks']} ticks")
    return [list(r.out) for r in reqs]


def phase_b(cfg: ModelConfig, params, fleet: Fleet, sizes: Sizes, seed: int,
            expect: list[list[int]]) -> None:
    """The fused MAV kernel at sigma = 0, sanitized for the first ticks;
    greedy tokens equal Phase A's (``expect``)."""
    kcfg = serve_config(cfg, use_kernel=True)
    reqs = make_requests(cfg, sizes, seed, sizes.new_tokens)
    with sanitizer():
        t0 = time.perf_counter()
        engine = ServeEngine(params, kcfg, slots=sizes.slots,
                             max_len=sizes.max_len, fleet=fleet)
        setup_s = time.perf_counter() - t0
        kernels = [n["prog"].kernel for _, n, _ in iter_projections(
            engine._exec_params)]
        assert kernels and all(k.wp_packed.dtype == jnp.int8
                               and k.gw_packed.dtype == jnp.int8
                               for k in kernels)
        prefill_s = admit(engine, reqs)
        stats = decode(engine, reqs, sizes.checked_ticks)
    log(f"phase B: set-up {setup_s:.1f}s (incl. sanitizer shadow), int8 "
        f"kernel state {programmed_bytes(engine._exec_params) / 1e9:.2f} "
        f"GB, prefill wave {prefill_s:.1f}s and first tick "
        f"{stats['first_tick_s']:.1f}s incl. compile, decode "
        f"~{stats['decode_tok_s']:.1f} tok/s (informational)")
    log(f"phase B: tokens vs phase A: "
        f"{same_tokens([list(r.out) for r in reqs], expect)}")
    log(f"phase B: ok — fused partials == plane-einsum reference bitwise "
        f"on {stats['checked_ticks']} ticks")


def silicon_bytes_per_layer(cfg: ModelConfig) -> tuple[int, int]:
    """(device bytes one layer adds in Phase C, bytes independent of depth).

    Per (K, N) projection, with C = ceil(K/M) chunks and Kp = C rounded to
    4 chunks of 32 lanes: int8 kernel planes + gates (Pw+1)*Kp*N, their f32
    cap fold 4*(Pw+1)*Kp*N, the f32 per-tile cap views of the engine and
    of the sanitizer shadow 2*4*C*M*N, the shadow's packed plane cells
    C*M*N, and the f32 weight 4*K*N. Depth-independent: the f32 embedding.
    """
    shapes = jax.eval_shape(lambda: T.lm_init(jax.random.PRNGKey(0), cfg))
    m, pw = cfg.mf.cim.m_columns, cfg.mf.cim.w_planes
    per_layer = 0
    for _, node, _ in iter_projections(shapes):
        k, n = node["w"].shape[-2:]
        c = -(-k // m)
        kp = -(-c // 4) * 4 * 32
        per_layer += n * (5 * (pw + 1) * kp + 9 * c * m) + 4 * k * n
    fixed = 4 * cfg.vocab_size * cfg.d_model
    return per_layer, fixed


def silicon_depth(cfg: ModelConfig, hbm_bytes: float) -> int:
    """Layers of ``cfg`` whose Phase C state fits SILICON_HBM_SHARE of
    ``hbm_bytes`` (at least 1, at most the published depth)."""
    per_layer, fixed = silicon_bytes_per_layer(cfg)
    fit = int((SILICON_HBM_SHARE * hbm_bytes - fixed) // per_layer)
    return max(1, min(cfg.n_layers, fit))


def phase_c(cfg: ModelConfig, sizes: Sizes, seed: int, layers: int) -> None:
    """Sampled SA-ADC silicon on the fused kernel, ``layers`` deep: the
    sanitizer asserts fused == einsum-reference codes every tick."""
    ccfg = serve_config(cfg, use_kernel=True, n_layers=layers)
    params = T.lm_init(jax.random.PRNGKey(seed), ccfg)
    fleet = pinned_fleet(params, ccfg.mf.cim, sizes.slots)
    reqs = make_requests(cfg, sizes, seed, sizes.silicon_new_tokens)
    with sanitizer():
        t0 = time.perf_counter()
        engine = ServeEngine(params, ccfg, slots=sizes.slots,
                             max_len=sizes.max_len, fleet=fleet,
                             silicon=SILICON)
        setup_s = time.perf_counter() - t0
        nodes = [n for _, n, _ in iter_projections(engine._exec_params)]
        assert nodes and all("silk" in n for n in nodes)
        prefill_s = admit(engine, reqs)
        stats = decode(engine, reqs, sizes.silicon_new_tokens)
    log(f"phase C: set-up {setup_s:.1f}s (program + silicon fold + "
        f"sanitizer shadow), prefill wave {prefill_s:.1f}s incl. compile")
    log(f"phase C: ok — {len(reqs)} requests x {sizes.silicon_new_tokens} "
        f"tokens, fused silicon codes == einsum reference bitwise on "
        f"{stats['checked_ticks']} ticks")


def phase_mesh(cfg: ModelConfig, params, fleet: Fleet, sizes: Sizes,
               seed: int, devices) -> None:
    """Phase A's engine sharded over a (data=2, fleet=2) serve mesh vs the
    same engine unsharded on the default device: greedy tokens identical."""
    from repro.launch.mesh import make_serve_mesh
    from repro.traffic import shard_engine
    outs = []
    for mesh in (None, make_serve_mesh(data=2, fleet=2,
                                       devices=devices[:4])):
        reqs = make_requests(cfg, sizes, seed, sizes.new_tokens)
        engine = ServeEngine(params, cfg, slots=sizes.slots,
                             max_len=sizes.max_len, fleet=fleet)
        info = shard_engine(engine, mesh) if mesh is not None else None
        prefill_s = admit(engine, reqs)
        decode(engine, reqs)
        outs.append([list(r.out) for r in reqs])
        log(f"mesh: {'sharded ' + json.dumps(info) if info else 'unsharded'}"
            f" — prefill wave {prefill_s:.1f}s incl. compile")
        del engine
    line = same_tokens(outs[1], outs[0])
    log(f"mesh: ok — sharded tokens equal unsharded, {line}")


def hbm_peak(dev) -> str:
    st = dev.memory_stats()
    return (f"HBM peak so far {st['peak_bytes_in_use'] / 1e9:.2f} GB of "
            f"{st['bytes_limit'] / 1e9:.2f} GB")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-vs-unsharded mesh path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__} "
        f"compile_cache={cache_dir}")
    if dev.platform != "tpu":
        print("chip_smoke: no TPU found — this script measures nothing "
              "on other backends", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1

    cfg = serve_config(CONFIG)
    params = T.lm_init(jax.random.PRNGKey(args.seed), cfg)
    fleet = pinned_fleet(params, cfg.mf.cim, FULL.slots)
    if args.chips == 4:
        phase_mesh(cfg, params, fleet, FULL, args.seed, devices)
    else:
        tokens = phase_a(cfg, params, fleet, FULL, args.seed)
        log(f"phase A: {hbm_peak(dev)}")
        gc.collect()
        phase_b(cfg, params, fleet, FULL, args.seed, tokens)
        log(f"phase B: {hbm_peak(dev)}")
        del params
        gc.collect()
        hbm = dev.memory_stats()["bytes_limit"]
        layers = silicon_depth(cfg, hbm)
        per_layer, _ = silicon_bytes_per_layer(cfg)
        log(f"phase C: depth cut to {layers} of {cfg.n_layers} layers — "
            f"{per_layer / 1e9:.2f} GB/layer of f32 cap-folded silicon "
            f"state, {SILICON_HBM_SHARE:.0%} of {hbm / 1e9:.1f} GB HBM")
        phase_c(cfg, FULL, args.seed, layers)
        log(f"phase C: {hbm_peak(dev)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
