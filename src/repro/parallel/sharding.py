"""Parameter/activation sharding rules (DP + FSDP + TP + EP + SP).

Storage shardings are assigned per-leaf by path suffix + rank heuristics,
following the Megatron pattern: column-parallel in-projections (out dim on
the `model` axis), row-parallel out-projections (in dim on `model`), FSDP
(ZeRO-3) over the `data` axis, experts over `model` (EP), embedding over
(vocab=`model`, d=`data`). Every proposed axis is divisibility-guarded —
a dim that doesn't divide the axis size stays unsharded (e.g. whisper's
51865 vocab).

Compute-level correctness is GSPMD's job; these specs set the resident
layout the compiler propagates from.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ParallelConfig


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


# suffix-name -> (role) where role determines the last-dims template
_COLUMN = {"q", "k", "v", "up", "gate", "in_x", "in_gate", "uq", "uk", "uv",
           "dq", "dkv", "wz", "wi", "wf", "wo", "vision_proj"}
_ROW = {"o", "down", "out"}


def _guard(dim: int, axis: Optional[str], axis_sizes: dict) -> Optional[str]:
    if axis is None:
        return None
    size = axis_sizes.get(axis, 1) if isinstance(axis, str) else int(
        np.prod([axis_sizes.get(a, 1) for a in axis]))
    return axis if size > 1 and dim % size == 0 else None


def spec_for_param(path, leaf, pcfg: ParallelConfig, axis_sizes: dict) -> P:
    name = _path_str(path)
    parts = name.split("/")
    shape = leaf.shape
    rank = len(shape)
    tp = pcfg.tp_axis
    fsdp = "data" if pcfg.fsdp else None

    def tail(*axes):
        """Spec with ``axes`` on the trailing dims, None on leading dims."""
        axes = [_guard(shape[rank - len(axes) + i], a, axis_sizes)
                for i, a in enumerate(axes)]
        return P(*([None] * (rank - len(axes)) + axes))

    # --- special families, most specific first ---------------------------
    if "router" in parts:
        return P()
    if "experts" in parts:
        ep = pcfg.ep_axes if len(pcfg.ep_axes) > 1 else pcfg.ep_axes[0]
        # FSDP on inner dims only if 'data' isn't already consumed by EP.
        efsdp = fsdp if (fsdp not in pcfg.ep_axes) else None
        leafname = parts[-1]
        if leafname in ("up", "gate"):        # (..., E, d, f)
            return tail(ep, efsdp, None)
        if leafname == "down":                # (..., E, f, d)
            return tail(ep, None, efsdp)
        if leafname.startswith("alpha"):      # (..., E, f)
            return tail(ep, None)
        return tail(ep) if rank >= 1 else P()
    if parts[-1] == "table":                  # embedding (V, d)
        return tail(tp, fsdp)
    if "lm_head" in parts and parts[-1] == "w":   # (d, V)
        return tail(fsdp, tp)
    if parts[-1] in ("conv_w",):              # (W, width)
        return tail(None, tp)
    if parts[-1] in ("w_a", "w_x"):           # (width, width) gate kernels
        return tail(None, tp)
    if parts[-1] in ("lam", "b_a", "b_x"):
        return tail(tp)
    if parts[-1] in ("rz", "ri", "rf", "ro"):  # sLSTM block-diag recurrents
        return P()

    owner = parts[-2] if len(parts) >= 2 else ""
    leafname = parts[-1]
    if pcfg.serve_tp_megaaxis and leafname == "w" and (
            owner in _COLUMN or owner in _ROW):
        mega = ("data", tp)

        def first_fit(dim, *cands):
            for c in cands:
                g = _guard(dim, c, axis_sizes)
                if g is not None:
                    return g
            return None

        if owner in _COLUMN:                  # (..., in, out): shard OUT
            out_axis = first_fit(shape[-1], mega, tp, "data")
            return P(*([None] * (rank - 1) + [out_axis]))
        # row: shard the contraction (IN) dim — partial sums reduce over
        # it with an activation-sized all-reduce, never a weight gather.
        in_axis = first_fit(shape[-2], mega, tp, "data")
        return P(*([None] * (rank - 2) + [in_axis, None]))
    if leafname == "w":
        if owner in _COLUMN:
            return tail(fsdp, tp)
        if owner in _ROW:
            return tail(tp, fsdp)
        if owner in ("igate", "fgate"):       # (d_inner, H) tiny
            return tail(fsdp, None)
        return tail(fsdp, None) if rank >= 2 else P()
    if leafname in ("alpha", "b"):
        if owner in _COLUMN:
            if pcfg.serve_tp_megaaxis:
                mega = ("data", tp)
                g = _guard(shape[-1], mega, axis_sizes) or _guard(
                    shape[-1], tp, axis_sizes)
                return P(*([None] * (rank - 1) + [g]))
            return tail(tp)
        return tail(None)
    # norms, scalars, everything else: replicated (leading dims unsharded)
    return P()


def params_pspecs(params, pcfg: ParallelConfig, mesh: Mesh):
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: spec_for_param(path, leaf, pcfg, axis_sizes),
        params)


def params_shardings(params, pcfg: ParallelConfig, mesh: Mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        params_pspecs(params, pcfg, mesh))


def opt_state_pspecs(params_struct, pspecs, tcfg):
    """Optimizer-state specs derived from parameter specs.

    adamw/sgdm moments mirror the parameter layout; adafactor's factored
    moments drop the reduced dim from the parameter spec. int8-quantised
    moments (blocked layout) are replicated — use adafactor for the
    memory-bound giants instead.
    """
    if tcfg.optimizer == "adamw":
        if tcfg.opt_state_dtype == "int8":
            rep = jax.tree.map(lambda _: P(), params_struct)
            blk = {"q": P(), "s": P()}
            rep = jax.tree.map(lambda _: dict(blk), params_struct)
            return {"m": rep, "v": rep}
        return {"m": pspecs, "v": pspecs}
    if tcfg.optimizer == "sgdm":
        return {"m": pspecs}
    if tcfg.optimizer == "adafactor":
        def fac(p, spec):
            axes = tuple(spec)
            axes = axes + (None,) * (p.ndim - len(axes))
            if p.ndim >= 2:
                return {"vr": P(*axes[:-1]),
                        "vc": P(*(axes[:-2] + axes[-1:]))}
            return {"v": P(*axes)}
        return {"f": jax.tree.map(fac, params_struct, pspecs)}
    raise ValueError(tcfg.optimizer)


# ---------------------------------------------------------------------------
# Batch/cache shardings
# ---------------------------------------------------------------------------

def dp_spec(pcfg: ParallelConfig) -> Any:
    return pcfg.dp_axes if len(pcfg.dp_axes) > 1 else pcfg.dp_axes[0]


def batch_pspecs(batch_specs: dict, pcfg: ParallelConfig, mesh: Mesh,
                 seq_shard: bool = False, cfg=None) -> dict:
    """Input-batch specs: batch dim over DP; optionally seq over TP (SP).

    Cache specs (decode cells) come from the authoritative per-family
    builders that mirror the cache constructors: attention cache sequence
    dims go on the `model` axis (flash-decode SP — valid for any kv-head
    count), recurrent state widths on `model`, batch on DP.
    """
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp = dp_spec(pcfg)
    out = {}
    for k, v in batch_specs.items():
        if k == "cache":
            assert cfg is not None, "cache specs need the model config"
            if cfg.family == "encdec":
                from repro.models import encdec as E
                out[k] = E.encdec_cache_pspecs(cfg, v, pcfg, axis_sizes)
            else:
                from repro.models import transformer as T
                out[k] = T.lm_cache_pspecs(cfg, v, pcfg, axis_sizes)
            continue
        rank = len(v.shape)
        if rank == 1:
            out[k] = P(dp)
        elif rank >= 2:
            seq_axis = pcfg.tp_axis if (
                seq_shard and pcfg.tp_axis in axis_sizes
                and v.shape[1] % axis_sizes[pcfg.tp_axis] == 0) else None
            out[k] = P(*([dp, seq_axis] + [None] * (rank - 2)))
    return sanitize_pspecs(out, batch_specs, axis_sizes)


def tree_shardings(tree_pspecs, mesh: Mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_pspecs,
                        is_leaf=lambda x: isinstance(x, P))


def sanitize_pspecs(pspecs, structs, axis_sizes: dict):
    """Null out any spec axis that does not divide its dim (e.g. batch=1
    decode cells can't shard batch over data)."""
    def size_of(ax) -> int:
        axes = ax if isinstance(ax, tuple) else (ax,)
        n = 1
        for a in axes:
            n *= axis_sizes.get(a, 1)
        return n

    def fix(spec, leaf):
        dims = list(spec) + [None] * (len(leaf.shape) - len(spec))
        out = [ax if ax is None or leaf.shape[i] % size_of(ax) == 0
               else None for i, ax in enumerate(dims)]
        return P(*out)

    return jax.tree.map(fix, pspecs, structs,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Serving-mesh shardings (repro.traffic.shard)
# ---------------------------------------------------------------------------

def serve_cache_pspecs(cfg, cache, axis_sizes: dict,
                       data_axis: str = "data"):
    """Decode-cache specs for the serving mesh: the slot (batch) dim over
    ``data_axis``, everything else replicated.

    Reuses the authoritative per-family cache builders
    (``T.lm_cache_pspecs``), then strips every axis the serving mesh does
    not have (the builders propose training axes like ``model`` for
    flash-decode SP) and every axis that does not divide its dim — so the
    result is always placeable on a ``("data", "fleet")`` mesh.
    """
    from repro.models import transformer as T
    from repro.configs.base import ParallelConfig as PC
    pcfg = PC(dp_axes=(data_axis,))
    specs = T.lm_cache_pspecs(cfg, cache, pcfg, axis_sizes)

    def size_of(axes) -> int:
        n = 1
        for a in axes:
            n *= axis_sizes.get(a, 1)
        return n

    def fix(spec, leaf):
        dims = list(spec) + [None] * (len(leaf.shape) - len(spec))
        out = []
        for i, ax in enumerate(dims):
            if ax is None:
                out.append(None)
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            ok = (all(a in axis_sizes for a in axes)
                  and size_of(axes) > 1
                  and leaf.shape[i] % size_of(axes) == 0)
            out.append(ax if ok else None)
        return P(*out)

    return jax.tree.map(fix, specs, cache,
                        is_leaf=lambda x: isinstance(x, P))


def exec_param_pspecs(params, axis_sizes: dict, fleet_axis: str = "fleet"):
    """Sharding specs for a ``ServeEngine`` exec tree.

    Programmed macro state is the serving working set: each
    :class:`~repro.core.programmed.ProgrammedMacro`'s weight-plane /
    lossless state and digital residue shard their output-channel (N)
    dim — the macro-placement axis: device d of the ``fleet`` axis holds
    a contiguous slice of every projection's µArray banks, mirroring how
    a multi-die fleet splits a projection's tiles by output channel.
    Scales, swapped macros (scales only), silicon views and every float
    parameter stay replicated — divisibility-guarded like everything
    else, so a fleet axis that doesn't divide some projection's N simply
    leaves that projection replicated.
    """
    from repro.core.programmed import (CimLosslessState, CimPackedPlanes,
                                       ProgrammedMacro, _is_prog_key)

    def rep(sub):
        return jax.tree.map(lambda _: P(), sub)

    def last_dim(leaf) -> P:
        if getattr(leaf, "ndim", 0) < 1:
            return P()
        ax = _guard(leaf.shape[-1], fleet_axis, axis_sizes)
        return P(*([None] * (leaf.ndim - 1) + [ax]))

    def prog_spec(pm: ProgrammedMacro) -> ProgrammedMacro:
        return ProgrammedMacro(
            sw=rep(pm.sw), sx=rep(pm.sx), r_w=last_dim(pm.r_w),
            state=None if pm.state is None else CimPackedPlanes(
                packed=last_dim(pm.state.packed),
                r_w=last_dim(pm.state.r_w)),
            # Kernel state replicates: the Pallas path is not a
            # traffic-lab target.
            kernel=None if pm.kernel is None else rep(pm.kernel),
            lossless=None if pm.lossless is None else CimLosslessState(
                packed=last_dim(pm.lossless.packed)))

    def walk(node):
        if isinstance(node, dict):
            return {k: prog_spec(v)
                    if _is_prog_key(k) and isinstance(v, ProgrammedMacro)
                    else walk(v) for k, v in node.items()}
        if type(node) in (list, tuple):
            return type(node)(walk(v) for v in node)
        return rep(node)

    return walk(params)
