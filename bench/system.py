"""The system under test: the program's serving engine and batcher, built
from a configuration file. The only module of the benchmark that imports
the program.

Everything the builder needs is data in the configuration file:

* ``model``: the published sizes, under their published keys;
* ``program.model``: the program's ``ModelConfig`` fields that the
  published keys do not give (``family``, ``qk_norm``, ``mlp_type``, ...);
  any field named there wins over the one derived from ``model``. A dict
  given for a field whose type is one of the program's dataclasses
  (``moe``, ``mla``) is built into it, and a list becomes a tuple;
* ``program.mf_mode``: how the projections run (``cim_sim``: on CIM
  macros);
* ``cim``: the design point (``CimConfig`` fields) and the static
  activation full scale ``act_amax``;
* ``program.fleet``: ``"pinned"`` (the smallest fleet that pins every
  tile) or ``{"n_macros": n}`` (a fixed fleet; below the model's tiles the
  engine swaps weights in rounds);
* ``program.silicon``: ``null`` (nominal macros) or the ``SiliconConfig``
  fields of sampled silicon, keyed by the run's seed;
* ``activation_dtype``, ``matmul_precision`` and ``serving`` (slots,
  max_len).
"""

from __future__ import annotations

import dataclasses
import typing

import jax
import jax.numpy as jnp

# Published keys -> the program's ModelConfig fields.
PUBLISHED = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
             "num_attention_heads": "n_heads",
             "num_key_value_heads": "n_kv_heads",
             "intermediate_size": "d_ff", "vocab_size": "vocab_size",
             "head_dim": "head_dim", "tie_word_embeddings": "tie_embeddings",
             "rope_theta": "rope_theta", "attention_bias": "qkv_bias"}


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import MFTechniqueConfig, ModelConfig
    from repro.core.cim import CimConfig
    prog = conf["program"]
    fields = {f.name for f in dataclasses.fields(CimConfig)}
    cim = CimConfig(**{k: v for k, v in conf["cim"].items() if k in fields})
    kw = {PUBLISHED[k]: v for k, v in conf["model"].items()
          if k in PUBLISHED}
    if "rope_theta" in kw:
        kw["rope_theta"] = float(kw["rope_theta"])
    hints = typing.get_type_hints(ModelConfig)
    kw.update({k: field_value(hints[k], v)
               for k, v in prog["model"].items()})
    return ModelConfig(name=conf["name"],
                       dtype=jnp.dtype(conf["activation_dtype"]),
                       mf=MFTechniqueConfig(mode=prog["mf_mode"], cim=cim),
                       **kw)


def field_value(hint, value):
    """``value`` as a field of type ``hint`` takes it: a dict for a
    dataclass (or an optional one) becomes that dataclass, a list a
    tuple."""
    if isinstance(value, dict):
        for t in (hint, *typing.get_args(hint)):
            if dataclasses.is_dataclass(t):
                return t(**value)
    if isinstance(value, list):
        return tuple(value)
    return value


def check_program_defaults(conf: dict) -> None:
    """The configuration states what the program fixes itself: the static
    activation full scale and the RMSNorm epsilon. Fail before a run
    whose reference would silently differ."""
    from repro.core.programmed import DEFAULT_ACT_AMAX
    if conf["cim"]["act_amax"] != DEFAULT_ACT_AMAX:
        raise ValueError(f"config act_amax {conf['cim']['act_amax']} != the "
                         f"program's static scale {DEFAULT_ACT_AMAX}")
    if conf["model"].get("rms_norm_eps", 1e-6) != 1e-6:
        raise ValueError("the program's RMSNorm epsilon is 1e-6")


def pinned_fleet(params, cfg, slots: int):
    """The smallest fleet that pins every projection tile, sized from the
    engine's own schedule."""
    from repro.compiler.tiling import Fleet
    from repro.serve.engine import fleet_schedule
    probe = Fleet(n_macros=1, cfg=cfg.mf.cim)
    sched, _ = fleet_schedule(params, probe, slots)
    return Fleet(n_macros=-(-sched.total_tiles // probe.halves_per_macro),
                 cfg=cfg.mf.cim)


def seed_key(seed: int) -> jax.Array:
    """A threefry key from any seed below 2**64."""
    seed %= 2**64
    return jax.random.wrap_key_data(
        jnp.asarray([seed >> 32, seed & 0xFFFFFFFF], jnp.uint32))


def build_engine(params, conf: dict, seed: int):
    """The program's ``ServeEngine`` as the configuration states it."""
    from repro.compiler.tiling import Fleet
    from repro.serve.engine import ServeEngine
    from repro.silicon.instance import SiliconConfig
    cfg = model_config(conf)
    check_program_defaults(conf)
    prog, sv = conf["program"], conf["serving"]
    if prog["fleet"] == "pinned":
        fleet = pinned_fleet(params, cfg, sv["slots"])
    else:
        fleet = Fleet(n_macros=prog["fleet"]["n_macros"], cfg=cfg.mf.cim)
    kw = {}
    if prog.get("silicon") is not None:
        kw = {"silicon": SiliconConfig(**prog["silicon"]),
              "silicon_key": seed_key(seed)}
    engine = ServeEngine(params, cfg, slots=sv["slots"],
                         max_len=sv["max_len"], fleet=fleet, **kw)
    if prog["fleet"] == "pinned" and not engine.schedule.pinned:
        raise RuntimeError("the pinned fleet does not pin the model")
    return engine


def warm_up(engine, buckets: list[int]) -> None:
    """Compile every program the window drives: the slot reset, one
    prefill wave per slab length in ``buckets`` and the decode step. The
    slots are empty again afterwards."""
    from repro.serve.engine import Request
    for b in buckets:
        # len - 1 = b // 2 + 1 tokens bucket to exactly b.
        engine.submit_many([Request(prompt=[1] * (b // 2 + 2),
                                    max_new_tokens=1)])
        engine.step()
    if not buckets:
        engine.submit_many([Request(prompt=[1], max_new_tokens=1)])
        engine.step()
    for slot in engine.occupied_slots:
        engine.evict(slot)
    jax.block_until_ready(engine.cache)


def batcher(engine, clock, n_requests: int):
    """The program's continuous batcher in front of ``engine``, admitting
    everything: nothing is shed or evicted."""
    from repro.traffic.batching import AdmissionConfig, ContinuousBatcher
    return ContinuousBatcher(engine, clock, AdmissionConfig(
        max_queue=n_requests + 1, drop_late=False,
        evict_past_deadline=False))


def traffic_request(spec):
    from repro.traffic.workload import TrafficRequest
    inf = float("inf")
    return TrafficRequest(rid=spec.rid, t_arrival_s=spec.due_s,
                          prompt=list(spec.prompt),
                          max_new_tokens=spec.max_new_tokens,
                          ttft_deadline_s=inf, deadline_s=inf)


def fault(step_fn, kind: str, vocab: int):
    """``step_fn`` (the engine's decode program) broken underneath the
    harness, for the checks that show ``correct`` comes out false:
    ``stale_state`` returns the cache as it was before the step;
    ``altered_token`` replaces each sampled token where the step produces
    it."""
    if kind == "stale_state":
        def broken(params, cache, *a):
            nxt, logits, _ = step_fn(params, cache, *a)
            return nxt, logits, cache
    elif kind == "altered_token":
        def broken(params, cache, *a):
            nxt, logits, new = step_fn(params, cache, *a)
            return (nxt + 1) % vocab, logits, new
    else:
        raise ValueError(kind)
    return broken


def install_spans(engine) -> None:
    """Wrap the engine's entry points in host spans that the profiler
    writes into its trace (``bench.step``, ``bench.admit``)."""
    from jax.profiler import TraceAnnotation
    step, submit = engine.step, engine.submit_many

    def traced_step():
        with TraceAnnotation("bench.step"):
            step()

    def traced_submit(reqs):
        with TraceAnnotation("bench.admit"):
            return submit(reqs)

    engine.step, engine.submit_many = traced_step, traced_submit
