"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST set the fake device count before ANY other import — jax locks the
device count on first init.
"""

import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

# ruff: noqa: E402
import argparse
import dataclasses
import json
import time
import traceback
from functools import partial

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import (LM_SHAPES, ModelConfig, ParallelConfig,
                                ShapeConfig, TrainConfig)
from repro.configs.registry import (ARCH_IDS, get_config, input_specs,
                                    shape_applicability)
from repro.launch.mesh import make_production_mesh
from repro.models import encdec as encdec_mod
from repro.models import transformer as T
from repro.parallel import sharding as shd
from repro.roofline import analysis as roofline
from repro.train import train_loop as TL

# The chip the dry-run projects its compiled cells onto: the programs are
# compiled for forced host devices, and their roofline terms are read
# against this kind's published peaks (repro.roofline.analysis.PEAKS).
TARGET_DEVICE_KIND = "TPU v5 lite"


def dryrun_parallel_config(arch: str, shape: ShapeConfig, multi_pod: bool,
                           overrides: dict | None = None) -> ParallelConfig:
    dp = ("pod", "data") if multi_pod else ("data",)
    kw = dict(dp_axes=dp, tp_axis="model", fsdp=True, use_ep=True,
              ep_axes=("model",), remat="block", microbatches=1)
    if arch == "deepseek-v3-671b":
        kw["ep_axes"] = ("data", "model")      # wide EP: 1 expert/chip
    if shape.kind == "decode":
        kw["remat"] = "none"
        # small models serve with replicated params (no per-layer gather)
        if arch in ("qwen3-0.6b", "recurrentgemma-2b", "xlstm-350m",
                    "whisper-base"):
            kw["fsdp"] = False
    if overrides:
        kw.update(overrides)
    return ParallelConfig(**kw)


def dryrun_train_config(arch: str) -> TrainConfig:
    # Adafactor for the 671B (Adam moments would not fit 256x16GB even
    # fully sharded); AdamW elsewhere.
    opt = "adafactor" if arch == "deepseek-v3-671b" else "adamw"
    return TrainConfig(optimizer=opt)


def _shardings(tree_pspecs, mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree_pspecs,
        is_leaf=lambda x: isinstance(x, P))


def _apply_overrides(cfg: ModelConfig, mf_overrides: dict | None
                     ) -> ModelConfig:
    if not mf_overrides:
        return cfg
    return dataclasses.replace(
        cfg, mf=dataclasses.replace(cfg.mf, **mf_overrides))


def build_cell(arch: str, shape: ShapeConfig, multi_pod: bool,
               pcfg_overrides: dict | None = None,
               tcfg: TrainConfig | None = None,
               cfg: ModelConfig | None = None,
               mf_overrides: dict | None = None):
    """Returns (fn, example_args_structs, in_shardings, donate) for a cell."""
    cfg = _apply_overrides(cfg or get_config(arch), mf_overrides)
    mesh = make_production_mesh(multi_pod=multi_pod)
    pcfg = dryrun_parallel_config(arch, shape, multi_pod, pcfg_overrides)
    tcfg = tcfg or dryrun_train_config(arch)
    pctx = T.ParallelContext(mesh=mesh, cfg=pcfg)
    specs = input_specs(cfg, shape)

    if shape.kind == "train":
        train_step = TL.make_train_step(cfg, pcfg, tcfg, pctx)
        state_struct = jax.eval_shape(
            lambda: TL.init_state(jax.random.PRNGKey(0), cfg, tcfg))
        pspecs = shd.params_pspecs(state_struct.params, pcfg, mesh)
        opt_pspecs = shd.opt_state_pspecs(state_struct.params, pspecs, tcfg)
        state_shardings = TL.TrainState(
            params=_shardings(pspecs, mesh),
            opt_state=_shardings(opt_pspecs, mesh),
            step=NamedSharding(mesh, P()), ef_error=None)
        batch_p = shd.batch_pspecs(specs, pcfg, mesh, seq_shard=True,
                                   cfg=cfg)
        batch_shardings = _shardings(batch_p, mesh)
        return (train_step, (state_struct, specs),
                (state_shardings, batch_shardings), (0,), mesh, cfg, pcfg)

    params_struct = jax.eval_shape(
        lambda: (encdec_mod.encdec_init(jax.random.PRNGKey(0), cfg)
                 if cfg.family == "encdec"
                 else T.lm_init(jax.random.PRNGKey(0), cfg)))
    pspecs = shd.params_pspecs(params_struct, pcfg, mesh)
    param_shardings = _shardings(pspecs, mesh)

    if shape.kind == "prefill":
        if cfg.family == "encdec":
            fn = partial(encdec_mod.encode, cfg=cfg, pctx=pctx)
            args = (params_struct, specs["frames"])
            in_sh = (param_shardings,
                     NamedSharding(mesh, P(shd.dp_spec(pcfg), "model",
                                           None)))
            return fn, args, in_sh, (), mesh, cfg, pcfg
        fn = partial(T.serve_prefill, cfg=cfg, pctx=pctx)
        batch_p = shd.batch_pspecs(specs, pcfg, mesh, seq_shard=True,
                                   cfg=cfg)
        return (fn, (params_struct, specs),
                (param_shardings, _shardings(batch_p, mesh)), (), mesh,
                cfg, pcfg)

    # decode
    if cfg.family == "encdec":
        fn = partial(encdec_mod.encdec_decode_step, cfg=cfg, pctx=pctx)
    else:
        fn = partial(T.lm_decode_step, cfg=cfg, pctx=pctx)
    cache_struct = specs["cache"]
    batch_p = shd.batch_pspecs(specs, pcfg, mesh, cfg=cfg)
    cache_sh = _shardings(batch_p["cache"], mesh)
    tok_sh = NamedSharding(mesh, batch_p["tokens"])
    return (fn, (params_struct, cache_struct, specs["tokens"]),
            (param_shardings, cache_sh, tok_sh), (1,), mesh, cfg, pcfg)


def _measure_variant(arch: str, shape: ShapeConfig, multi_pod: bool,
                     n_units: int, pcfg_overrides: dict | None,
                     mf_overrides: dict | None = None) -> dict:
    """Compile a shallow FULL-WIDTH variant with the layer scan unrolled.

    XLA's cost_analysis counts a while-loop body once regardless of trip
    count, so per-cell FLOPs/bytes/collectives are extrapolated from two
    unrolled variants: total = f(1) + (units - 1) * (f(2) - f(1)).
    """
    cfg = get_config(arch)
    plen = len(cfg.pattern)
    enc_ratio = (cfg.encoder_layers / cfg.n_layers
                 if cfg.family == "encdec" else 0)
    mini = dataclasses.replace(
        cfg, n_layers=plen * n_units,
        encoder_layers=int(round(enc_ratio * plen * n_units)))
    ov = dict(pcfg_overrides or {})
    ov["scan_unroll"] = True
    fn, args, in_sh, donate, mesh, _, _ = build_cell(
        arch, shape, multi_pod, ov, cfg=mini, mf_overrides=mf_overrides)
    with mesh:
        compiled = jax.jit(fn, in_shardings=in_sh,
                           donate_argnums=donate).lower(*args).compile()
        terms = roofline.terms_from_compiled(compiled, mesh.devices.size,
                                             TARGET_DEVICE_KIND)
    return {"flops": terms.flops, "hbm_bytes": terms.hbm_bytes,
            "coll_bytes": terms.coll_bytes,
            "coll_breakdown": terms.coll_breakdown}


def extrapolated_terms(arch: str, shape: ShapeConfig, multi_pod: bool,
                       chips: int, pcfg_overrides: dict | None = None,
                       mf_overrides: dict | None = None
                       ) -> tuple[roofline.RooflineTerms, dict]:
    cfg = get_config(arch)
    plen = len(cfg.pattern)
    units = cfg.n_layers / plen          # fractional tails interpolate
    u1, u2 = 1, max(2, min(4, int(units)))
    f1 = _measure_variant(arch, shape, multi_pod, u1, pcfg_overrides,
                          mf_overrides)
    f2 = _measure_variant(arch, shape, multi_pod, u2, pcfg_overrides,
                          mf_overrides)

    def ex(a, b):
        # per-unit delta clamped >= 0: XLA's global fusion choices differ
        # slightly between compiles; a layer can't have negative cost.
        per = max((b - a) / (u2 - u1), 0.0)
        return a + (units - u1) * per

    coll = {kind: ex(f1["coll_breakdown"][kind],
                     f2["coll_breakdown"][kind])
            for kind in f1["coll_breakdown"]}
    terms = roofline.RooflineTerms(
        flops=ex(f1["flops"], f2["flops"]),
        hbm_bytes=ex(f1["hbm_bytes"], f2["hbm_bytes"]),
        coll_bytes=float(sum(coll.values())), chips=chips,
        device_kind=TARGET_DEVICE_KIND, coll_breakdown=coll)
    return terms, {"unit1": f1, "unit2": f2, "units": units,
                   "u1": u1, "u2": u2}


def run_cell(arch: str, shape: ShapeConfig, multi_pod: bool, out_dir: str,
             pcfg_overrides: dict | None = None, tag: str = "",
             mf_overrides: dict | None = None) -> dict:
    mesh_name = "2x16x16" if multi_pod else "16x16"
    cell_id = f"{arch}__{shape.name}__{mesh_name}" + (f"__{tag}" if tag
                                                      else "")
    record: dict = {"arch": arch, "shape": shape.name, "mesh": mesh_name,
                    "kind": shape.kind, "tag": tag}
    t0 = time.time()
    try:
        fn, args, in_sh, donate, mesh, cfg, pcfg = build_cell(
            arch, shape, multi_pod, pcfg_overrides,
            mf_overrides=mf_overrides)
        chips = mesh.devices.size
        with mesh:
            jitted = jax.jit(fn, in_shardings=in_sh,
                             donate_argnums=donate)
            lowered = jitted.lower(*args)
            record["lower_s"] = time.time() - t0
            t1 = time.time()
            compiled = lowered.compile()
            record["compile_s"] = time.time() - t1
            try:
                ma = compiled.memory_analysis()
                record["memory_analysis"] = {
                    k: int(getattr(ma, k)) for k in dir(ma)
                    if k.endswith("_bytes") or k.endswith("in_bytes")
                } if ma is not None else None
            except Exception as e:  # noqa: BLE001
                record["memory_analysis"] = f"unavailable: {e!r}"
            raw_terms = roofline.terms_from_compiled(compiled, chips,
                                                     TARGET_DEVICE_KIND)
            record["roofline_raw"] = raw_terms.as_dict()
        # Scan-corrected costs: extrapolate from two unrolled shallow
        # variants (XLA cost_analysis counts a loop body once).
        terms, measure = extrapolated_terms(arch, shape, multi_pod, chips,
                                            pcfg_overrides, mf_overrides)
        record["roofline"] = terms.as_dict()
        record["measurement"] = measure

        # useful-work reference
        params_tree = args[0].params if shape.kind == "train" else args[0]
        frac = 1.0
        if cfg.moe is not None:
            frac = (cfg.moe.top_k + cfg.moe.n_shared) / (
                cfg.moe.n_experts + cfg.moe.n_shared)
        counts = roofline.count_params(params_tree, frac)
        tokens = (shape.global_batch * shape.seq_len
                  if shape.kind in ("train", "prefill")
                  else shape.global_batch)
        mf = roofline.model_flops(counts["active"], tokens, shape.kind)
        record["model_params"] = counts
        record["model_flops_total"] = mf
        record["model_flops_per_chip"] = mf / chips
        hlo_flops = record["roofline"]["flops"]
        record["useful_flops_ratio"] = (mf / chips / hlo_flops
                                        if hlo_flops else None)
        record["status"] = "ok"
    except Exception as e:  # noqa: BLE001
        record["status"] = "error"
        record["error"] = repr(e)
        record["traceback"] = traceback.format_exc()
    record["total_s"] = time.time() - t0
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, cell_id + ".json"), "w") as f:
        json.dump(record, f, indent=1, default=str)
    status = record["status"]
    extra = ("" if status == "ok" else
             " :: " + record.get("error", "")[:160])
    print(f"[dryrun] {cell_id}: {status} ({record['total_s']:.1f}s){extra}",
          flush=True)
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all", help="arch id or 'all'")
    ap.add_argument("--shape", default="all", help="shape name or 'all'")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    archs = ARCH_IDS if args.arch == "all" else (args.arch,)
    meshes = {"single": (False,), "multi": (True,),
              "both": (False, True)}[args.mesh]
    n_ok = n_err = n_skip = 0
    for arch in archs:
        cfg = get_config(arch)
        for shape in LM_SHAPES:
            if args.shape != "all" and shape.name != args.shape:
                continue
            ok, reason = shape_applicability(cfg, shape)
            if not ok:
                print(f"[dryrun] {arch}__{shape.name}: SKIP ({reason})",
                      flush=True)
                n_skip += 1
                continue
            for mp in meshes:
                mesh_name = "2x16x16" if mp else "16x16"
                path = os.path.join(
                    args.out, f"{arch}__{shape.name}__{mesh_name}.json")
                if args.skip_existing and os.path.exists(path):
                    with open(path) as f:
                        if json.load(f).get("status") == "ok":
                            n_ok += 1
                            continue
                rec = run_cell(arch, shape, mp, args.out)
                if rec["status"] == "ok":
                    n_ok += 1
                else:
                    n_err += 1
    print(f"[dryrun] done: {n_ok} ok, {n_err} errors, {n_skip} skipped",
          flush=True)


if __name__ == "__main__":
    main()
