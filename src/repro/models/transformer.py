"""Unified decoder-only LM covering all assigned architectures.

A model is a stack of pattern periods (cfg.block_pattern); homogeneous
models are the 1-element pattern ('attn',). Parameters for each pattern
position are stacked over periods and executed with `jax.lax.scan` (one
traced copy of each distinct block kind — compile time and HLO size stay
flat in depth). Remainder layers (depth not divisible by the pattern) run
unrolled as the tail.

Block kinds: 'attn' (GQA or MLA per cfg.attn_type, + MLP or MoE),
'local_attn' (sliding window), 'rglru' (Griffin recurrent block + MLP),
'mlstm'/'slstm' (xLSTM, self-contained).

MF-Net integration: `resolve_modes` maps the config's MFTechniqueConfig
to an ExecMode per projection group — the paper's mixed mapping. Embeds,
routers, gates and the LM head are always the typical operator.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ParallelConfig
from repro.core.mf import ExecMode
from repro.models import attention, blocks, mla as mla_mod, moe as moe_mod
from repro.models import rglru as rglru_mod, xlstm as xlstm_mod


@dataclasses.dataclass(frozen=True)
class ParallelContext:
    """Runtime distribution context; None mesh -> single-process paths."""

    mesh: Any = None
    cfg: ParallelConfig = dataclasses.field(default_factory=ParallelConfig)

    @property
    def active(self) -> bool:
        return self.mesh is not None


def resolve_modes(cfg: ModelConfig) -> dict[str, ExecMode]:
    """Projection-group -> ExecMode under the mixed-mapping policy."""
    if not cfg.mf.enabled:
        off = ExecMode.REGULAR
        return {"attn": off, "mlp": off, "expert": off}
    m = ExecMode(cfg.mf.mode)
    return {
        "attn": m if cfg.mf.attn_qkv else ExecMode.REGULAR,
        "mlp": m if cfg.mf.mlp else ExecMode.REGULAR,
        "expert": m if cfg.mf.experts else ExecMode.REGULAR,
    }


def _mf_kw(cfg: ModelConfig) -> dict:
    kw = {"delta_sigma": cfg.mf.delta_sigma, "delta_coeff": cfg.mf.delta_coeff}
    if cfg.mf.mode == "cim_sim":
        kw["cim_cfg"] = cfg.mf.cim
    return kw


# ---------------------------------------------------------------------------
# Block init/apply dispatch
# ---------------------------------------------------------------------------

def _block_init(key: jax.Array, cfg: ModelConfig, kind: str) -> dict:
    dtype = cfg.dtype
    k1, k2, k3 = jax.random.split(key, 3)
    use_mf = cfg.mf.enabled
    if kind in ("attn", "local_attn"):
        p = {"ln1": blocks.norm_init(cfg.norm_type, cfg.d_model, dtype)}
        if cfg.attn_type == "mla" and kind == "attn":
            p["attn"] = mla_mod.mla_init(k1, cfg.d_model, cfg.n_heads,
                                         cfg.mla, mf=use_mf and cfg.mf.attn_qkv,
                                         dtype=dtype)
        else:
            p["attn"] = attention.attn_init(
                k1, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                cfg.resolved_head_dim, qkv_bias=cfg.qkv_bias,
                qk_norm=cfg.qk_norm, mf=use_mf and cfg.mf.attn_qkv,
                dtype=dtype)
        p["ln2"] = blocks.norm_init(cfg.norm_type, cfg.d_model, dtype)
        if cfg.moe is not None:
            p["moe"] = moe_mod.moe_init(
                k2, cfg.d_model, cfg.moe.d_ff_expert or cfg.d_ff,
                cfg.moe.n_experts, cfg.moe.n_shared, cfg.moe.top_k,
                mf=use_mf and cfg.mf.experts, dtype=dtype)
        else:
            p["mlp"] = blocks.mlp_init(k2, cfg.d_model, cfg.d_ff,
                                       cfg.mlp_type,
                                       mf=use_mf and cfg.mf.mlp, dtype=dtype)
        return p
    if kind == "rglru":
        return {
            "ln1": blocks.norm_init(cfg.norm_type, cfg.d_model, dtype),
            "rec": rglru_mod.rglru_init(
                k1, cfg.d_model, cfg.lru_width or cfg.d_model,
                cfg.conv_width, mf=use_mf and cfg.mf.attn_qkv, dtype=dtype),
            "ln2": blocks.norm_init(cfg.norm_type, cfg.d_model, dtype),
            "mlp": blocks.mlp_init(k2, cfg.d_model, cfg.d_ff, cfg.mlp_type,
                                   mf=use_mf and cfg.mf.mlp, dtype=dtype),
        }
    if kind == "mlstm":
        return {"block": xlstm_mod.mlstm_init(
            k1, cfg.d_model, cfg.n_heads, mf=use_mf and cfg.mf.mlp,
            conv_width=cfg.conv_width, dtype=dtype)}
    if kind == "slstm":
        return {"block": xlstm_mod.slstm_init(
            k1, cfg.d_model, cfg.n_heads, mf=use_mf and cfg.mf.mlp,
            dtype=dtype)}
    raise ValueError(kind)  # pragma: no cover


def _moe_apply(p: dict, x: jax.Array, cfg: ModelConfig, pctx: ParallelContext,
               mode: ExecMode, **kw) -> tuple[jax.Array, jax.Array]:
    mcfg = cfg.moe
    if not (pctx.active and pctx.cfg.use_ep):
        return moe_mod.moe_apply_dense(p, x, top_k=mcfg.top_k, mode=mode,
                                       **kw)
    from jax.sharding import PartitionSpec as P
    pcfg = pctx.cfg
    dp = pcfg.dp_axes if len(pcfg.dp_axes) > 1 else pcfg.dp_axes[0]
    tp = pcfg.tp_axis
    ep = pcfg.ep_axes if len(pcfg.ep_axes) > 1 else pcfg.ep_axes[0]
    b, t, d = x.shape
    mesh_sizes = dict(zip(pctx.mesh.axis_names, pctx.mesh.devices.shape))
    tp_size = mesh_sizes.get(tp, 1)
    seq_shardable = t % tp_size == 0 and t >= tp_size
    all_axes = tuple(pcfg.dp_axes) + (tp,)

    if seq_shardable:
        # Training/prefill: tokens distinct per (dp, tp) shard — sequence-
        # parallel region around the MoE (DeepSeek pattern).
        def ep_fn(pp, xx):
            s = xx.shape[0] * xx.shape[1]
            y, aux = moe_mod.moe_apply_ep(
                pp, xx.reshape(s, d), top_k=mcfg.top_k, ep_axis=ep,
                capacity_factor=mcfg.capacity_factor,
                expert_capacity_factor=mcfg.expert_capacity_factor,
                mode=mode,
                fuse_single_expert=pcfg.moe_fuse_single_expert, **kw)
            return y.reshape(xx.shape), jax.lax.pmean(aux, all_axes)

        x_spec = P(dp, tp, None)
        out_spec = P(dp, tp, None)
    else:
        # Decode (t == 1): tokens replicated over tp inside the region;
        # each tp shard takes its batch slice, runs EP, and the slices are
        # reassembled with an all_gather — no duplicate expert sends.
        def ep_fn(pp, xx):
            bl = xx.shape[0]
            chunk = -(-bl // tp_size)
            pad = chunk * tp_size - bl
            xp = jnp.pad(xx.reshape(bl, d), ((0, pad), (0, 0)))
            mine = jax.lax.dynamic_slice_in_dim(
                xp, jax.lax.axis_index(tp) * chunk, chunk, axis=0)
            y, aux = moe_mod.moe_apply_ep(
                pp, mine, top_k=mcfg.top_k, ep_axis=ep,
                capacity_factor=mcfg.capacity_factor,
                expert_capacity_factor=mcfg.expert_capacity_factor,
                mode=mode,
                fuse_single_expert=pcfg.moe_fuse_single_expert, **kw)
            y_full = jax.lax.all_gather(y, tp, axis=0, tiled=True)[:bl]
            return (y_full.reshape(xx.shape),
                    jax.lax.pmean(aux, all_axes))

        x_spec = P(dp, None, None)
        out_spec = P(dp, None, None)

    expert_specs = jax.tree.map(lambda _: P(ep), p["experts"])
    pspecs = {"router": jax.tree.map(lambda _: P(), p["router"]),
              "experts": expert_specs}
    if "shared" in p:
        pspecs["shared"] = jax.tree.map(lambda _: P(), p["shared"])
    return jax.shard_map(
        ep_fn, mesh=pctx.mesh,
        in_specs=(pspecs, x_spec),
        out_specs=(out_spec, P()),
        check_vma=False,
    )(p, x)


def _block_apply(p: dict, x: jax.Array, kind: str, cfg: ModelConfig,
                 modes: dict, positions: jax.Array, pctx: ParallelContext,
                 cache: Optional[dict] = None,
                 prefill_valid: Optional[jax.Array] = None
                 ) -> tuple[jax.Array, Optional[dict], jax.Array]:
    """Returns (x_out, new_cache, aux_loss)."""
    kw = _mf_kw(cfg)
    aux = jnp.zeros((), jnp.float32)
    new_cache: Optional[dict] = None
    if prefill_valid is not None and not (
            kind in ("attn", "local_attn") and cfg.attn_type != "mla"):
        raise ValueError(
            f"batched prefill is implemented for GQA attention caches "
            f"only; block kind {kind!r} (attn_type={cfg.attn_type}) must "
            f"ingest prompts through the decode step")
    if kind in ("attn", "local_attn"):
        window = cfg.window if kind == "local_attn" else (
            cfg.window if cfg.block_pattern is None else None)
        h = blocks.norm_apply(cfg.norm_type, p["ln1"], x)
        attn_cache = None if cache is None else cache.get("attn")
        if cfg.attn_type == "mla" and kind == "attn":
            a, attn_cache = mla_mod.mla_apply(
                p["attn"], h, n_heads=cfg.n_heads, mla=cfg.mla,
                rope_theta=cfg.rope_theta, positions=positions,
                mode=modes["attn"], cache=attn_cache,
                attn_block=cfg.attn_block,
                attn_block_skip=cfg.attn_block_skip, **kw)
        else:
            a, attn_cache = attention.gqa_apply(
                p["attn"], h, n_heads=cfg.n_heads,
                n_kv_heads=cfg.n_kv_heads, head_dim=cfg.resolved_head_dim,
                rope_theta=cfg.rope_theta, positions=positions,
                mode=modes["attn"], qk_norm=cfg.qk_norm, causal=True,
                window=window, cache=attn_cache,
                attn_block=cfg.attn_block,
                attn_block_skip=cfg.attn_block_skip, pctx=pctx,
                prefill_valid=prefill_valid, **kw)
        x = x + a
        h = blocks.norm_apply(cfg.norm_type, p["ln2"], x)
        if cfg.moe is not None:
            f, aux = _moe_apply(p["moe"], h, cfg, pctx, modes["expert"], **kw)
            # named for the 'save_moe' remat policy: saving the MoE output
            # keeps backward from recomputing the expert all_to_alls.
            from jax.ad_checkpoint import checkpoint_name
            f = checkpoint_name(f, "moe_out")
        else:
            f = blocks.mlp_apply(p["mlp"], h, cfg.mlp_type, modes["mlp"],
                                 **kw)
        x = x + f
        if cache is not None:
            new_cache = {"attn": attn_cache}
        return x, new_cache, aux
    if kind == "rglru":
        h = blocks.norm_apply(cfg.norm_type, p["ln1"], x)
        rec_state = None if cache is None else cache.get("rec")
        r, rec_state = rglru_mod.rglru_block_apply(
            p["rec"], h, mode=modes["attn"], state=rec_state, **kw)
        x = x + r
        h = blocks.norm_apply(cfg.norm_type, p["ln2"], x)
        x = x + blocks.mlp_apply(p["mlp"], h, cfg.mlp_type, modes["mlp"],
                                 **kw)
        if cache is not None:
            new_cache = {"rec": rec_state}
        return x, new_cache, aux
    if kind == "mlstm":
        state = None if cache is None else cache.get("cell")
        y, state = xlstm_mod.mlstm_apply(p["block"], x, cfg.n_heads,
                                         mode=modes["mlp"], state=state, **kw)
        if cache is not None:
            new_cache = {"cell": state}
        return x + y, new_cache, aux
    if kind == "slstm":
        state = None if cache is None else cache.get("cell")
        y, state = xlstm_mod.slstm_apply(p["block"], x, cfg.n_heads,
                                         mode=modes["mlp"], state=state, **kw)
        if cache is not None:
            new_cache = {"cell": state}
        return x + y, new_cache, aux
    raise ValueError(kind)  # pragma: no cover


def _block_init_cache(cfg: ModelConfig, kind: str, batch: int,
                      max_len: int) -> dict:
    if kind in ("attn", "local_attn"):
        window = cfg.window if kind == "local_attn" else None
        # Local attention keeps a ring buffer of the window only — this is
        # what makes long_500k decode O(window) instead of O(T) memory.
        size = min(max_len, window) if window else max_len
        if cfg.attn_type == "mla" and kind == "attn":
            return {"attn": mla_mod.mla_init_cache(batch, max_len, cfg.mla,
                                                   dtype=cfg.dtype)}
        return {"attn": attention.init_kv_cache(
            batch, size, cfg.n_kv_heads, cfg.resolved_head_dim,
            dtype=cfg.dtype)}
    if kind == "rglru":
        return {"rec": rglru_mod.rglru_init_state(
            batch, cfg.lru_width or cfg.d_model, cfg.conv_width,
            dtype=cfg.dtype)}
    if kind == "mlstm":
        return {"cell": xlstm_mod.mlstm_init_state(batch, cfg.d_model,
                                                   cfg.n_heads,
                                                   cfg.conv_width)}
    if kind == "slstm":
        return {"cell": xlstm_mod.slstm_init_state(batch, cfg.d_model)}
    raise ValueError(kind)  # pragma: no cover


# ---------------------------------------------------------------------------
# Model init / apply
# ---------------------------------------------------------------------------

def _periods(cfg: ModelConfig) -> tuple[int, int]:
    plen = len(cfg.pattern)
    return cfg.n_layers // plen, cfg.n_layers % plen


def lm_init(key: jax.Array, cfg: ModelConfig) -> dict:
    n_periods, tail = _periods(cfg)
    keys = jax.random.split(key, 4)
    params: dict = {"embed": blocks.embed_init(keys[0], cfg.vocab_size,
                                               cfg.d_model, cfg.dtype)}
    if cfg.vision_tokens:
        params["vision_proj"] = blocks.proj_init(
            jax.random.fold_in(keys[0], 1), cfg.vision_embed_dim,
            cfg.d_model, bias=True, mf=False, dtype=cfg.dtype)
    stacked = []
    for pos, kind in enumerate(cfg.pattern):
        pk = jax.random.split(jax.random.fold_in(keys[1], pos), n_periods)
        stacked.append(jax.vmap(lambda k: _block_init(k, cfg, kind))(pk))
    params["layers"] = tuple(stacked)
    params["tail"] = tuple(
        _block_init(jax.random.fold_in(keys[2], i), cfg, cfg.pattern[i])
        for i in range(tail))
    params["final_norm"] = blocks.norm_init(cfg.norm_type, cfg.d_model,
                                            cfg.dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = blocks.proj_init(keys[3], cfg.d_model,
                                             cfg.vocab_size, bias=False,
                                             mf=False, dtype=cfg.dtype)
    return params


def _embed_inputs(params: dict, cfg: ModelConfig, batch: dict) -> jax.Array:
    x = blocks.embed_apply(params["embed"], batch["tokens"])
    if cfg.vision_tokens and "vision_embeds" in batch:
        v = blocks.proj_apply(params["vision_proj"], batch["vision_embeds"])
        x = jnp.concatenate([v.astype(x.dtype), x], axis=1)
    return x


def lm_forward(params: dict, batch: dict, cfg: ModelConfig,
               pctx: ParallelContext = ParallelContext()
               ) -> tuple[jax.Array, jax.Array]:
    """Training/prefill forward. batch['tokens']: (B,T). -> (logits, aux)."""
    modes = resolve_modes(cfg)
    x = _embed_inputs(params, cfg, batch)
    b, t, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    n_periods, tail = _periods(cfg)

    def period_body(carry, period_params):
        h, aux = carry
        for pos, kind in enumerate(cfg.pattern):
            h, _, a = _block_apply(period_params[pos], h, kind, cfg, modes,
                                   positions, pctx)
            aux = aux + a
        return (h, aux), None

    body = period_body
    if pctx.cfg.remat == "block":
        body = jax.checkpoint(period_body, prevent_cse=False)
    elif pctx.cfg.remat == "save_moe":
        body = jax.checkpoint(
            period_body, prevent_cse=False,
            policy=jax.checkpoint_policies.save_only_these_names("moe_out"))
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                               params["layers"],
                               unroll=pctx.cfg.scan_unroll)
    for i, p in enumerate(params["tail"]):
        x, _, a = _block_apply(p, x, cfg.pattern[i], cfg, modes, positions,
                               pctx)
        aux = aux + a
    x = blocks.norm_apply(cfg.norm_type, params["final_norm"], x)
    if cfg.tie_embeddings:
        logits = blocks.lm_head_apply(None, x,
                                      tied_table=params["embed"]["table"])
    else:
        logits = blocks.lm_head_apply(params["lm_head"], x)
    return logits, aux / max(cfg.n_layers, 1)


def serve_prefill(params: dict, batch: dict, cfg: ModelConfig,
                  pctx: ParallelContext = ParallelContext()) -> jax.Array:
    """Prefill forward returning only the last-position logits (the
    full (B, T, vocab) logits tensor is never materialised — XLA DCEs
    the other positions' head matmul)."""
    modes = resolve_modes(cfg)
    x = _embed_inputs(params, cfg, batch)
    b, t, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    n_periods, tail = _periods(cfg)

    def period_body(carry, period_params):
        h = carry
        for pos, kind in enumerate(cfg.pattern):
            h, _, _ = _block_apply(period_params[pos], h, kind, cfg, modes,
                                   positions, pctx)
        return h, None

    body = period_body
    if pctx.cfg.remat == "block":
        body = jax.checkpoint(period_body, prevent_cse=False)
    x, _ = jax.lax.scan(body, x, params["layers"],
                        unroll=pctx.cfg.scan_unroll)
    for i, p in enumerate(params["tail"]):
        x, _, _ = _block_apply(p, x, cfg.pattern[i], cfg, modes, positions,
                               pctx)
    x = blocks.norm_apply(cfg.norm_type, params["final_norm"], x[:, -1:])
    if cfg.tie_embeddings:
        logits = blocks.lm_head_apply(None, x,
                                      tied_table=params["embed"]["table"])
    else:
        logits = blocks.lm_head_apply(params["lm_head"], x)
    return logits[:, 0]


def lm_init_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    n_periods, tail = _periods(cfg)
    stacked = []
    for kind in cfg.pattern:
        one = _block_init_cache(cfg, kind, batch, max_len)
        stacked.append(jax.tree.map(
            lambda v: jnp.broadcast_to(v, (n_periods,) + v.shape).copy() if
            n_periods else v[None][:0], one))
    return {
        "layers": tuple(stacked),
        "tail": tuple(_block_init_cache(cfg, cfg.pattern[i], batch, max_len)
                      for i in range(tail)),
        "pos": jnp.zeros((batch,), jnp.int32),
    }


def _block_cache_pspec(cfg: ModelConfig, kind: str, pcfg, axis_sizes: dict
                       ) -> dict:
    """PartitionSpec tree mirroring `_block_init_cache` for one block.

    Attention caches: batch over DP, sequence over the `model` axis
    (flash-decode SP — works for any kv-head count). Recurrent states:
    batch over DP, channel width over `model` when divisible.
    """
    from jax.sharding import PartitionSpec as P
    dp = pcfg.dp_axes if len(pcfg.dp_axes) > 1 else pcfg.dp_axes[0]
    tp = pcfg.tp_axis
    tps = axis_sizes.get(tp, 1)

    def g(dim):  # guard divisibility
        return tp if tps > 1 and dim % tps == 0 else None

    d = cfg.d_model
    if kind in ("attn", "local_attn"):
        if cfg.attn_type == "mla" and kind == "attn":
            return {"attn": {"ckv": P(dp, tp, None), "kr": P(dp, tp, None),
                             "len": P(dp)}}
        return {"attn": {"k": P(dp, tp, None, None),
                         "v": P(dp, tp, None, None), "len": P(dp)}}
    if kind == "rglru":
        w = cfg.lru_width or d
        return {"rec": {"conv": P(dp, None, g(w)), "h": P(dp, g(w))}}
    if kind == "mlstm":
        return {"cell": {"conv": P(dp, None, g(2 * d)),
                         "cell": {"c": P(dp, None, None, None),
                                  "n": P(dp, None, None),
                                  "m": P(dp, None)}}}
    if kind == "slstm":
        return {"cell": {"cell": {"h": P(dp, g(d)), "c": P(dp, g(d)),
                                  "n": P(dp, g(d)), "m": P(dp, g(d))}}}
    raise ValueError(kind)  # pragma: no cover


def lm_cache_pspecs(cfg: ModelConfig, cache_tree, pcfg, axis_sizes: dict):
    """Spec tree matching `lm_init_cache` (stacked periods get a leading
    None dim)."""
    from jax.sharding import PartitionSpec as P
    n_periods, tail = _periods(cfg)
    dp = pcfg.dp_axes if len(pcfg.dp_axes) > 1 else pcfg.dp_axes[0]
    stacked = []
    for kind in cfg.pattern:
        one = _block_cache_pspec(cfg, kind, pcfg, axis_sizes)
        stacked.append(jax.tree.map(
            lambda s: P(*((None,) + tuple(s))), one,
            is_leaf=lambda x: isinstance(x, P)))
    return {
        "layers": tuple(stacked),
        "tail": tuple(_block_cache_pspec(cfg, cfg.pattern[i], pcfg,
                                         axis_sizes) for i in range(tail)),
        "pos": P(dp),
    }


def lm_decode_step(params: dict, cache: dict, tokens: jax.Array,
                   cfg: ModelConfig,
                   pctx: ParallelContext = ParallelContext()
                   ) -> tuple[jax.Array, dict]:
    """One decode step. tokens: (B,) -> (logits (B,V), new cache)."""
    modes = resolve_modes(cfg)
    x = blocks.embed_apply(params["embed"], tokens[:, None])
    positions = cache["pos"][:, None]

    def period_body(h, inp):
        period_params, period_cache = inp
        new_caches = []
        for pos, kind in enumerate(cfg.pattern):
            h, nc, _ = _block_apply(period_params[pos], h, kind, cfg, modes,
                                    positions, pctx, cache=period_cache[pos])
            new_caches.append(nc)
        return h, tuple(new_caches)

    x, new_layer_caches = jax.lax.scan(
        period_body, x, (params["layers"], cache["layers"]),
        unroll=pctx.cfg.scan_unroll)
    new_tail = []
    for i, p in enumerate(params["tail"]):
        x, nc, _ = _block_apply(p, x, cfg.pattern[i], cfg, modes, positions,
                                pctx, cache=cache["tail"][i])
        new_tail.append(nc)
    x = blocks.norm_apply(cfg.norm_type, params["final_norm"], x)
    if cfg.tie_embeddings:
        logits = blocks.lm_head_apply(None, x,
                                      tied_table=params["embed"]["table"])
    else:
        logits = blocks.lm_head_apply(params["lm_head"], x)
    new_cache = {"layers": new_layer_caches, "tail": tuple(new_tail),
                 "pos": cache["pos"] + 1}
    return logits[:, 0], new_cache


def prefill_supported(cfg: ModelConfig) -> bool:
    """True when ``lm_prefill_cache`` can ingest prompts for this config:
    every block is a GQA attention block with a non-ring (full-length) KV
    cache. Recurrent mixers (rgLRU/xLSTM) and MLA caches fall back to
    prefill-as-decode in the serve engine."""
    kinds_ok = all(k in ("attn", "local_attn") for k in cfg.pattern)
    return kinds_ok and cfg.attn_type != "mla" and cfg.window is None


def lm_prefill_cache(params: dict, cache: dict, tokens: jax.Array,
                     valid: jax.Array, cfg: ModelConfig,
                     pctx: ParallelContext = ParallelContext()) -> dict:
    """Batched prompt ingestion: fold a (B, T) prompt slab into the cache.

    The T > 1 prompt axis rides the same collapsed step-time matmuls as
    decode (every CIM projection reshapes (..., K) -> (B*T, K), so
    programmed/swapped macro execution is identical per position) while
    attention runs causally over the slab — prompt ingestion stops paying
    one decode step per token. ``valid`` gives each slot's real prompt
    length within the slab (0 = slot not participating: its cache rows,
    length and position are left untouched, so mid-decode neighbours in a
    serving batch are safe). Participating slots must be fresh
    (``cache['pos'] == 0``). Returns the new cache only — sampling the
    first output token happens in the ordinary decode step that feeds the
    last prompt token.
    """
    if not prefill_supported(cfg):
        raise ValueError(
            f"{cfg.name}: batched prefill needs an all-GQA-attention "
            f"pattern with a full-length KV cache")
    modes = resolve_modes(cfg)
    x = blocks.embed_apply(params["embed"], tokens)
    b, t, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(t)[None], (b, t))

    def period_body(h, inp):
        period_params, period_cache = inp
        new_caches = []
        for pos, kind in enumerate(cfg.pattern):
            h, nc, _ = _block_apply(period_params[pos], h, kind, cfg, modes,
                                    positions, pctx,
                                    cache=period_cache[pos],
                                    prefill_valid=valid)
            new_caches.append(nc)
        return h, tuple(new_caches)

    x, new_layer_caches = jax.lax.scan(
        period_body, x, (params["layers"], cache["layers"]),
        unroll=pctx.cfg.scan_unroll)
    new_tail = []
    for i, p in enumerate(params["tail"]):
        x, nc, _ = _block_apply(p, x, cfg.pattern[i], cfg, modes, positions,
                                pctx, cache=cache["tail"][i],
                                prefill_valid=valid)
        new_tail.append(nc)
    # No final norm / LM head: prefill produces cache state, not logits.
    return {"layers": new_layer_caches, "tail": tuple(new_tail),
            "pos": cache["pos"] + valid.astype(cache["pos"].dtype)}


def lm_loss(params: dict, batch: dict, cfg: ModelConfig,
            pctx: ParallelContext = ParallelContext(),
            aux_weight: float = 0.01) -> tuple[jax.Array, dict]:
    logits, aux = lm_forward(params, batch, cfg, pctx)
    targets = batch["targets"]
    if cfg.vision_tokens and "vision_embeds" in batch:
        logits = logits[:, -targets.shape[1]:]
    valid = (targets >= 0)
    tgt = jnp.maximum(targets, 0)
    # One-hot CE: elementwise mask-and-reduce keeps the (B,T,V) logits
    # sharded on the vocab axis under GSPMD (take_along_axis would force an
    # all-gather of the full logits — fatal at 152k vocab x 1M tokens).
    loss = _sharded_ce(logits, tgt, valid)
    total = loss + aux_weight * aux
    return total, {"loss": loss, "aux": aux}


def _sharded_ce(logits: jax.Array, tgt: jax.Array, valid: jax.Array
                ) -> jax.Array:
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    v = logits.shape[-1]
    onehot = (jnp.arange(v, dtype=jnp.int32)[None, None, :]
              == tgt[..., None])
    tl = jnp.sum(jnp.where(onehot, lf, 0.0), axis=-1)
    nll = lse - tl
    return jnp.sum(nll * valid) / jnp.maximum(jnp.sum(valid), 1)
