"""Mesh sharding of a serving engine's state.

``shard_engine`` places a constructed :class:`~repro.serve.engine
.ServeEngine`'s working state on a jax device mesh built by
:func:`repro.launch.mesh.make_serve_mesh`:

  * the decode cache's slot (batch) dim shards over the ``data`` axis —
    device d of the data axis serves a contiguous block of cache slots,
    classic data parallelism over concurrent streams;
  * the programmed fleet state (bit-packed µArray planes, lossless
    bytes, digital residues) shards its output-channel dim over the
    ``fleet`` axis — macro placement across dies
    (:func:`repro.parallel.sharding.exec_param_pspecs`);
  * everything else (scales, silicon views, float params) replicates.

No re-jit is needed: the engine's existing ``step_fn``/``_prefill_fn``
retrace against the committed shardings and GSPMD partitions the step —
which is exactly why a SINGLE-device mesh is bitwise identical to the
unsharded path (same program, same device, shardings are no-ops). The
engine's exec-refresh hook keeps re-built trees (drift refresh,
recalibration) on the mesh.
"""

from __future__ import annotations

import jax

from repro.parallel.sharding import (exec_param_pspecs, serve_cache_pspecs,
                                     tree_shardings)


def _count_sharded(spec_tree) -> int:
    from jax.sharding import PartitionSpec as P
    n = [0]

    def visit(s):
        if isinstance(s, P) and any(ax is not None for ax in s):
            n[0] += 1

    jax.tree.map(visit, spec_tree, is_leaf=lambda x: isinstance(x, P))
    return n[0]


def shard_engine(engine, mesh) -> dict:
    """Place ``engine``'s cache and exec tree on ``mesh`` (in place).

    Returns a placement summary ``{"data": ..., "fleet": ...,
    "cache_sharded_leaves": ..., "param_sharded_leaves": ...}`` the
    traffic benchmark records. Raises when the engine's slot count does
    not divide the data axis (a ragged slot split would silently
    replicate the cache instead).
    """
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    data = axis_sizes.get("data", 1)
    if engine.slots % data:
        raise ValueError(
            f"engine slots ({engine.slots}) must divide the data axis "
            f"({data}) — ragged slot blocks cannot be placed")
    cache_specs = serve_cache_pspecs(engine.cfg, engine.cache, axis_sizes)
    param_specs = exec_param_pspecs(engine._exec_params, axis_sizes)
    cache_sh = tree_shardings(cache_specs, mesh)
    param_sh = tree_shardings(param_specs, mesh)
    engine.cache = jax.device_put(engine.cache, cache_sh)
    engine._exec_params = jax.device_put(engine._exec_params, param_sh)
    engine.mesh = mesh

    def under_mesh(fn):
        """``fn`` traced and run with ``mesh`` as JAX's current mesh, which
        is what makes programmed projections gather their fleet-sharded
        outputs (``repro.core.mf.apply_projection``)."""
        def call(*args):
            with jax.set_mesh(mesh):
                return fn(*args)
        return call

    engine.step_fn = under_mesh(engine.step_fn)
    engine._prefill_fn = under_mesh(engine._prefill_fn)

    def _reput(eng):
        """Exec-refresh hook: a re-attached/re-programmed tree is born on
        the default device — put it back on the mesh. The tree STRUCTURE
        is invariant across refreshes (same programmed layout), so the
        shardings are reusable as-is."""
        eng._exec_params = jax.device_put(eng._exec_params, param_sh)

    engine.exec_refresh_hooks.append(_reput)
    return {
        "data": data, "fleet": axis_sizes.get("fleet", 1),
        "cache_sharded_leaves": _count_sharded(cache_specs),
        "param_sharded_leaves": _count_sharded(param_specs),
    }
