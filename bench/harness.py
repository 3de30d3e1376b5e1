"""One run of one cell: set-up, the measured window, the trace reduction,
the metrics by name, and the check that decides ``correct``.

Everything specific to a configuration, a traffic mix or a metric is found
by name under ``bench/``: ``configs/<config>.json`` (with the plain
reference module it names beside it), ``traffic/<mix>.json`` and
``metrics/<metric>.py`` (one file may serve ``<metric>.<split>``).

The reference module gives ``init_params(seed, sizes)``, ``row_logits``
and ``kv_rows`` (see ``compare``), and may give ``token_ops(sizes,
cache_len)`` (the model's operations for one output token, which
``RunData.token_ops`` hands to the readers) and ``KV_ENTRY`` (the path of
the cache entry ``kv_rows`` stands for; ``("layers", 0, "attn")``).
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import importlib.util
import json
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import generator, stats, system
from bench import trace as tr
from bench.peaks import Peaks

BENCH = Path(__file__).resolve().parent

# Arrivals generated past the window's end, so that load stays as offered
# while the requests due in the window finish (open loop).
TAIL_S = 10.0
# How long past the window's close a request due inside it may take to
# produce its first token before it counts as failed.
LATE_LIMIT_S = 60.0
# Part of the window the traced run records: its last third, at most this
# long (about 12 MB of trace per second of the long-decode cell), so that
# it holds a few dozen prefill waves.
TRACE_SPAN_S = 8.0
# Correctness sample: the longest finished request plus others drawn from
# the seed, up to this many served tokens, in this many reference rows.
CHECK_TOKENS = 1200
CHECK_ROWS = 16
# Logits every decode step keeps for the check, at this many vocabulary
# ids drawn from the seed.
LOGIT_IDS = 512
# A cached row of a leaf (k, v; a latent) disagrees with the reference
# where its relative error is above this (float32 rounding reads about
# 1e-7; bfloat16 storage about 1e-3).
KV_TOL = 1e-4
# The cache entry the check reads, where the reference names none: the
# first layer's attention (period 0 of the layer scan).
KV_ENTRY = ("layers", 0, "attn")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def freeze(value):
    """A configuration value as a static argument can take it: a dict
    becomes the tuple of its sorted items, a list a tuple, all the way
    down."""
    if isinstance(value, dict):
        return tuple(sorted((k, freeze(v)) for k, v in value.items()))
    if isinstance(value, list):
        return tuple(freeze(v) for v in value)
    return value


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    conf: dict
    mix: dict
    reference: object
    metrics: list          # BENCHMARK.json metric entries this run reports


def load_cell(bench: dict, name: str, traced: bool,
              root: Path = BENCH) -> Cell:
    """The cell ``name`` of a parsed BENCHMARK.json, with its files."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} (known: {sorted(cells)})")
    w = cells[name]
    conf = load_json(root / "configs" / f"{w['config']}.json")
    mix = load_json(root / "traffic" / f"{w['traffic']}.json")
    ref = load_module(root / "configs" / f"{conf['reference']}.py")
    entries = bench["per_layer"] if traced else bench["end_to_end"]
    mine = [m for m in entries if name in m.get("workloads", [name])]
    return Cell(name, w["chips"], conf, mix, ref, mine)


def reader(name: str, root: Path = BENCH):
    """The ``read(run)`` function of metric ``name``: from
    ``metrics/<name>.py``, or for a metric split by the end-to-end metric
    it moves (``prefill_wave_ms.chat``), from the file of the name before
    its first dot."""
    path = root / "metrics" / f"{name}.py"
    if not path.exists():
        path = root / "metrics" / f"{name.split('.')[0]}.py"
    return load_module(path).read


@dataclasses.dataclass
class RunData:
    """What a metric reader gets: the window's requests, its program's
    counters (deltas over the window of ``engine.metrics``), the trace of
    a traced run, and the configuration's ``token_ops`` (None where its
    reference gives none)."""

    records: list
    window_s: float
    setup_s: float
    sizes: dict
    peaks: Optional[Peaks]
    trace: Optional[tr.Trace] = None
    counters: dict = dataclasses.field(default_factory=dict)
    token_ops: Optional[Callable] = None


# ---------------------------------------------------------------------------
# The measured window
# ---------------------------------------------------------------------------

class WindowClosed(Exception):
    """Raised through the batcher's loop when the window is over."""


class Clock:
    """The benchmark's clock for the program's batcher (its 4-method
    protocol: ``now``, ``on_decode``, ``on_prefill``, ``fast_forward``).

    It stamps every output token on the host as the engine's step returns
    with it, and closes the window: a closed loop at ``window_s``; an open
    loop once every request due inside the window has its first token (or
    ``LATE_LIMIT_S`` past the close)."""

    def __init__(self, records: dict, window_s: float, open_loop: bool,
                 tracer=None):
        self.records = records          # id(prompt list) -> stats.Record
        self.window_s = window_s
        self.open_loop = open_loop
        self.tracer = tracer
        self.live: list = []            # (engine Request, Record)
        self.t0 = time.perf_counter()

    @property
    def now(self) -> float:
        return time.perf_counter() - self.t0

    def start(self) -> None:
        self.t0 = time.perf_counter()

    def on_admit(self, wave) -> None:
        now = self.now
        for _, req in wave:
            rec = self.records[id(req.prompt)]
            rec.admit_s = now
            self.live.append((req, rec))

    def on_prefill(self) -> None:
        pass

    def on_decode(self) -> None:
        now = self.now
        keep = []
        for req, rec in self.live:
            for tok in req.out[len(rec.tokens):]:
                rec.tokens.append(int(tok))
                rec.token_s.append(now)
            if req.done:
                rec.done = True
            else:
                keep.append((req, rec))
        self.live = keep
        if self.tracer is not None:
            self.tracer.tick(now)
        self._maybe_close(now)

    def fast_forward(self, t: float) -> None:
        self._maybe_close(max(t, self.now))
        delta = t - self.now
        if delta > 0:
            time.sleep(delta)

    def _maybe_close(self, now: float) -> None:
        if now < self.window_s:
            return
        if not self.open_loop or now > self.window_s + LATE_LIMIT_S:
            raise WindowClosed
        if all(r.token_s for r in self.records.values()
               if r.due_s < self.window_s):
            raise WindowClosed


class Tracer:
    """Runs ``jax.profiler`` from ``t_start`` of the window until it
    closes, opening a ``bench.tick`` span per batcher tick. The trace is
    written out after the window (``close``), so the writing stalls no
    request."""

    def __init__(self, out_dir: str, t_start: float):
        self.out_dir, self.t_start = out_dir, t_start
        self.on = False
        self._tick = None

    def tick(self, now: float) -> None:
        from jax.profiler import TraceAnnotation
        if self._tick is not None:
            self._tick.__exit__(None, None, None)
        elif now >= self.t_start:
            jax.profiler.start_trace(self.out_dir)
            self.on = True
        if self.on:
            self._tick = TraceAnnotation("bench.tick")
            self._tick.__enter__()

    def close(self) -> None:
        if self._tick is not None:
            self._tick.__exit__(None, None, None)
            self._tick = None
        if self.on:
            jax.profiler.stop_trace()
            self.on = False


def serve_window(engine, specs, window_s: float, open_loop: bool,
                 tracer: Optional[Tracer] = None) -> list:
    """Drive the program's batcher over ``specs`` for ``window_s`` seconds
    of the benchmark's clock; returns the per-request records."""
    trs = [system.traffic_request(s) for s in specs]
    records = {id(t.prompt): stats.Record(s.rid, s.due_s, s.prompt,
                                          s.max_new_tokens, key=id(t.prompt))
               for s, t in zip(specs, trs)}
    clock = Clock(records, window_s, open_loop, tracer)
    engine.admission_hooks.append(clock.on_admit)
    b = system.batcher(engine, clock, len(trs))
    clock.start()
    try:
        b.run(trs, max_ticks=2**62)
    except WindowClosed:
        pass
    finally:
        if tracer is not None:
            tracer.close()
        engine.admission_hooks.remove(clock.on_admit)
    out = sorted(records.values(), key=lambda r: r.rid)
    if not open_loop and out[-1].admit_s is not None:
        raise RuntimeError(
            "the closed loop's queue ran dry inside the window")
    return out




# ---------------------------------------------------------------------------
# What the timed path produced, kept for the check
# ---------------------------------------------------------------------------

@jax.jit
def _pick(logits, ids):
    return logits[:, ids]


class Capture:
    """Wraps the engine's decode program (``engine.step_fn``): after each
    step it keeps, on the device, the logits the step produced at ``ids``
    for every slot, and on the host which request and which of its output
    tokens each slot served (so also how many steps fed each request).
    ``inner`` is the program it calls."""

    def __init__(self, engine, ids: np.ndarray):
        self.engine, self.inner = engine, engine.step_fn
        self.ids = jnp.asarray(ids, jnp.int32)
        self.rows: list = []
        self.owners: list = []
        engine.step_fn = self

    def __call__(self, params, cache, *a):
        nxt, logits, new = self.inner(params, cache, *a)
        self.rows.append(_pick(logits, self.ids))
        self.owners.append([None if r is None else (id(r.prompt), len(r.out))
                            for r in self.engine.requests])
        return nxt, logits, new

    def clear(self) -> None:
        self.rows, self.owners = [], []

    def steps_of(self) -> collections.Counter:
        """key -> decode steps that fed the request in its slot."""
        return collections.Counter(own[0] for owners in self.owners
                                   for own in owners if own is not None)

    def logits_of(self, keys: set) -> dict:
        """key -> (outputs, ids) logits the program produced for each
        request whose key is in ``keys``."""
        rows = np.stack(jax.device_get(self.rows)) if self.rows else None
        got: dict = {k: {} for k in keys}
        for step, owners in enumerate(self.owners):
            for slot, own in enumerate(owners):
                if own is not None and own[0] in got:
                    got[own[0]][own[1]] = rows[step, slot]
        return {k: np.stack([v[j] for j in range(len(v))]) if v else None
                for k, v in got.items()}


def cached_rows(engine, records: dict, steps: dict,
                entry: tuple = KV_ENTRY) -> dict:
    """The leaves of the cache entry at ``entry`` (period 0) that the
    engine holds for every request in flight (``rows``: leaf -> (slots,
    positions, ...)), its own count of written positions (``len``), and
    the tokens the cache was made from (``seqs``): the prompt tokens a
    prefill wave ingested, then one token for each decode step that fed
    the request (``steps``, by request key)."""
    slots, seqs = [], []
    for s, req in enumerate(engine.requests):
        if req is None:
            continue
        rec = records[id(req.prompt)]
        waved = len(rec.prompt) - 1 if engine.batched_prefill else 0
        n = waved + steps.get(rec.key, 0)
        slots.append(s)
        seqs.append((list(rec.prompt) + list(req.out))[:n])
    node = engine.cache
    for key in entry:
        node = node[key]
    # Every slot's rows (in-flight ones first), a fixed shape.
    idx = jnp.asarray(slots + [s for s in range(engine.slots)
                               if s not in slots], jnp.int32)
    rows, n_prog = jax.device_get((
        {k: v[0, idx] for k, v in node.items() if k != "len"},
        node["len"][0, idx]))
    return {"seqs": seqs, "rows": rows, "len": n_prog}


# ---------------------------------------------------------------------------
# Correctness: what the window served against the plain reference
# ---------------------------------------------------------------------------

def check_sample(records, seed: int) -> list:
    """Finished requests to check: the longest, then others drawn from the
    seed, up to ``CHECK_TOKENS`` served tokens in ``CHECK_ROWS`` rows."""
    done = [r for r in records if r.done]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.tokens), -r.rid))
    rest = [r for r in done if r is not longest]
    order = generator.rng_for(seed, stream=1).permutation(len(rest))
    pick, n = [longest], len(longest.tokens)
    for i in order:
        if n >= CHECK_TOKENS or len(pick) == CHECK_ROWS:
            break
        pick.append(rest[i])
        n += len(rest[i].tokens)
    return pick


def logit_ids(seed: int, vocab: int) -> np.ndarray:
    return np.sort(generator.rng_for(seed, stream=2).choice(
        vocab, size=min(LOGIT_IDS, vocab), replace=False)).astype(np.int32)


def reference_rows(sample, max_len: int):
    """(tokens, served, mask): each row is the prompt and its served
    tokens but the last; ``served[b, t]`` is the token served after
    position t, and ``mask`` marks the positions that served one."""
    tokens = np.zeros((CHECK_ROWS, max_len), np.int32)
    served = np.zeros((CHECK_ROWS, max_len), np.int32)
    mask = np.zeros((CHECK_ROWS, max_len), bool)
    for b, r in enumerate(sample):
        seq = list(r.prompt) + r.tokens[:-1]
        tokens[b, :len(seq)] = seq
        p = len(r.prompt) - 1
        served[b, p:p + len(r.tokens)] = r.tokens
        mask[b, p:p + len(r.tokens)] = True
    return tokens, served, mask


def _rel(got, ref, axes):
    """||got - ref|| / ||ref|| over ``axes``."""
    num = np.sqrt(np.sum(np.square(got - ref), axis=axes))
    return num / np.maximum(np.sqrt(np.sum(np.square(ref), axis=axes)),
                            1e-30)


def compare(cell: Cell, sv, control: str = "") -> dict:
    """The numbers the check reads of what the window served (the
    configuration's ``correct`` names those it limits):

    * ``served_logit_gap_max``, ``served_not_first_share``: how far each
      served token of the sample lies below the reference's best logit at
      its position, the widest gap and the share not put first;
    * ``logit_rel_err_median`` (and ``_max``): per served position, the
      relative distance of the logits the program's step produced (at the
      seed's ids) from the reference's, the median (largest) over the
      sample;
    * ``kv_mismatch_share``: of the positions the cache holds for the
      requests in flight at the close, the share at which any leaf of the
      cache entry the reference's ``kv_rows`` stands for (a dict: leaf
      name -> (B, T, ...) rows) lies off the reference's by more than
      ``KV_TOL`` over all its axes after (slot, position), or that the
      cache does not count as written (``kv_rel_err_median``: the median
      over positions of the largest relative distance of any leaf).

    The reference gets the configuration's ``model`` and ``cim`` sections
    as static arguments (``freeze``). With ``control`` (a lower precision
    of the reference): the same of the reference at that precision put in
    the program's place, reading the tokens it puts first and its own
    logits and cache rows."""
    ref, conf = cell.reference, cell.conf
    items = (freeze(conf["model"]), freeze(conf["cim"]))
    out: dict = {}
    if sv.sample:
        tokens, served, mask = reference_rows(sv.sample,
                                              conf["serving"]["max_len"])
        ids = np.broadcast_to(sv.ids, tokens.shape + sv.ids.shape)
        if control:
            _, first, got = jax.device_get(ref.row_logits(
                sv.params, jnp.asarray(tokens), jnp.asarray(ids), *items,
                control))
            picks = np.concatenate([first[..., None], ids], axis=-1)
        else:
            got = sv.logits
            picks = np.concatenate([served[..., None], ids], axis=-1)
        best, _, at = jax.device_get(ref.row_logits(
            sv.params, jnp.asarray(tokens), jnp.asarray(picks), *items))
        gaps = (best - at[..., 0])[mask]
        rel = _rel(got, at[..., 1:], -1)[mask]
        if np.all(np.isfinite(gaps)) and np.all(np.isfinite(rel)):
            out["served_logit_gap_max"] = float(gaps.max())
            out["served_not_first_share"] = float(np.mean(gaps > 0))
            out["logit_rel_err_median"] = float(np.median(rel))
            out["logit_rel_err_max"] = float(rel.max())
    kv = sv.kv
    if kv["seqs"]:
        # Fixed shapes (every slot, the whole cache), so the reference
        # compiles once per cell.
        some = next(iter(kv["rows"].values()))
        tokens = np.zeros(some.shape[:2], np.int32)
        for b, q in enumerate(kv["seqs"]):
            tokens[b, :len(q)] = q
        want = jax.device_get(ref.kv_rows(sv.params, jnp.asarray(tokens),
                                          *items))
        if control:
            got = jax.device_get(ref.kv_rows(
                sv.params, jnp.asarray(tokens), *items, control))
            n_prog = [len(q) for q in kv["seqs"]]
        else:
            got, n_prog = kv["rows"], kv["len"]
        err = np.maximum.reduce([
            _rel(got[leaf], w, tuple(range(2, w.ndim)))
            for leaf, w in sorted(want.items())])
        errs, bad = [], 0
        for b, q in enumerate(kv["seqs"]):
            e = err[b, :len(q)]
            off = ~(e <= KV_TOL) | (np.arange(len(q)) >= int(n_prog[b]))
            bad += int(off.sum())
            errs.append(e)
        out["kv_mismatch_share"] = bad / sum(len(q) for q in kv["seqs"])
        out["kv_rel_err_median"] = float(np.median(np.concatenate(errs)))
    return out


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def device_info(devices) -> dict:
    dev = devices[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


@contextlib.contextmanager
def compile_counter():
    """Counts backend compilations (persistent-cache hits included)."""
    count = [0]

    def listener(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            count[0] += 1

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        yield count
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)


@dataclasses.dataclass
class Served:
    """One served window, with what the check and the readers need: the
    weights, the check's sample of finished requests with the logits the
    program produced for them (``logits``, at ``ids``), the cache rows
    of the requests in flight at the close (``kv``), and the program's
    counters over the window (``counters``)."""

    params: dict
    records: list
    window_s: float
    setup_s: float
    memory_peak_bytes: int
    attempted: int
    failed: int
    trace: Optional[tr.Trace]
    sample: list
    ids: np.ndarray
    logits: Optional[np.ndarray]
    kv: dict
    counters: dict


def serve(cell: Cell, seed: int, seconds: float, traced: bool,
          t_process: float, fault: Optional[str] = None) -> Served:
    """Set up, warm up and serve the window; the program's state is freed
    on return, the weights and what the check reads kept."""
    conf, mix = cell.conf, cell.mix
    sizes, sv = conf["model"], conf["serving"]
    devices = jax.local_devices()[:cell.chips]
    open_loop = mix["loop"] == "open"
    specs = generator.generate(
        mix, seed, generator.request_count(mix, seconds, TAIL_S),
        sizes["vocab_size"])
    ids = logit_ids(seed, sizes["vocab_size"])
    with jax.default_matmul_precision(conf["matmul_precision"]):
        t0 = time.perf_counter()
        params = cell.reference.init_params(seed, sizes)
        jax.block_until_ready(params)
        t1 = time.perf_counter()
        engine = system.build_engine(params, conf, seed)
        t2 = time.perf_counter()
        capture = Capture(engine, ids)
        buckets = generator.prefill_buckets(mix, sv["max_len"])
        system.warm_up(engine, buckets)
        capture.clear()
        t3 = time.perf_counter()
        log(f"bench: set-up: imports {t0 - t_process:.3f}s, weights "
            f"{t1 - t0:.3f}s, engine {t2 - t1:.3f}s, warm-up of prefill "
            f"buckets {buckets} and decode {t3 - t2:.3f}s")
        if fault is not None:
            capture.inner = system.fault(capture.inner, fault,
                                         engine.cfg.vocab_size)
        tmp = tempfile.TemporaryDirectory() if traced else None
        tracer = None
        if traced:
            system.install_spans(engine)
            tracer = Tracer(tmp.name,
                            seconds - min(TRACE_SPAN_S, seconds / 3))
        setup_s = time.perf_counter() - t_process
        before = engine.metrics.snapshot()
        with compile_counter() as compiles:
            records = serve_window(engine, specs, seconds, open_loop,
                                   tracer)
        counters = engine.metrics.delta(before)
    mem = memory_peak(devices)
    log(f"bench: window {seconds}s, {compiles[0]} compilations inside it")
    kv = cached_rows(engine, {r.key: r for r in records},
                     capture.steps_of(),
                     getattr(cell.reference, "KV_ENTRY", KV_ENTRY))
    sample = check_sample(records, seed)
    got = capture.logits_of({r.key for r in sample})
    logits = None
    if sample:
        logits = np.zeros((CHECK_ROWS, sv["max_len"], len(ids)), np.float32)
        for b, r in enumerate(sample):
            rows = got[r.key]
            p = len(r.prompt) - 1
            if rows is None or len(rows) != len(r.tokens):
                logits[b, p:p + len(r.tokens)] = np.nan
            else:
                logits[b, p:p + len(r.tokens)] = rows
    trace = None
    if traced:
        trace = tr.load(tmp.name)
        tmp.cleanup()
    due = stats.due_in_window(records, seconds)
    if open_loop:
        lag = [r.admit_s - r.due_s for r in due if r.admit_s is not None]
        log(f"bench: generator ran late by at most "
            f"{max(lag, default=0.0):.6f}s (due to admission, "
            f"{len(due)} requests due in the window)")
        attempted = len(due)
        failed = sum(1 for r in due if not r.token_s)
    else:
        attempted = sum(1 for r in records
                        if r.admit_s is not None and r.admit_s <= seconds)
        failed = 0
    del engine, capture
    gc.collect()
    return Served(params, records, seconds, setup_s, mem, attempted, failed,
                  trace, sample, ids, logits, kv, counters)


def judge(cell: Cell, numbers: dict, failed: int) -> dict:
    """Each number of ``compare`` that the configuration limits, and the
    requests due in the window that never produced a token, with its
    limit."""
    compared = {k: {"value": numbers.get(k), "limit": limit}
                for k, limit in cell.conf["correct"].items()}
    compared["failed_requests"] = {"value": failed, "limit": 0}
    return compared


def is_correct(compared: dict) -> bool:
    return all(v["value"] is not None and v["value"] <= v["limit"]
               for v in compared.values())


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             t_process: float, peaks: Optional[Peaks],
             fault: Optional[str] = None, control: str = "") -> dict:
    """Set up, warm up, measure, check; returns the result line's dict.
    ``fault`` breaks the timed path (``system.fault``); ``control`` puts
    the reference at that lower precision in the program's place for the
    check."""
    devices = jax.local_devices()[:cell.chips]
    sv = serve(cell, seed, seconds, traced, t_process, fault)
    numbers = compare(cell, sv, control)
    compared = judge(cell, numbers, sv.failed)
    correct = is_correct(compared)
    run = RunData(sv.records, seconds, sv.setup_s, cell.conf["model"],
                  peaks, sv.trace, sv.counters,
                  getattr(cell.reference, "token_ops", None))
    metrics = {}
    for m in cell.metrics:
        value = reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = device_info(devices) | {
        "memory_peak_bytes": sv.memory_peak_bytes}
    result = {"correct": correct, "attempted": sv.attempted,
              "failed": sv.failed, "metrics": metrics, "device": device}
    if sv.trace is not None:
        device["busy_s"] = tr.mean_busy_s(sv.trace)
        device["window_s"] = sv.trace.window_s
        result["breakdown"] = {"device_ops": tr.top_ops(sv.trace),
                               "idle_gaps": tr.idle_gaps(sv.trace)}
        for what, parts, scale in (
                ("decode step ms by scope path",
                 tr.scope_times(sv.trace, "jit_serve_step"), 1e3),
                ("device idle s by innermost program span",
                 tr.idle_by_span(sv.trace), 1.0)):
            top = sorted(parts.items(), key=lambda kv: -kv[1])[:8]
            log(f"bench: {what}: " + ", ".join(
                f"{k or '(none)'} {v * scale:.6f}" for k, v in top))
    log(f"bench: checked {sum(len(r.tokens) for r in sv.sample)} served "
        f"tokens of {len(sv.sample)} finished requests and "
        f"{sum(len(q) for q in sv.kv['seqs'])} cached positions of "
        f"{len(sv.kv['seqs'])} requests in flight against the reference")
    log("bench: read, not limited: " + ", ".join(
        f"{k} = {v!r}" for k, v in numbers.items() if k not in compared))
    for k, v in compared.items():
        log(f"compared: {k} = {v['value']!r} (limit {v['limit']!r})")
    result["compared"] = compared
    return result
