"""Continuous batching with SLO-aware admission control.

:class:`ContinuousBatcher` replaces the engine's submit-everything
``run()`` loop with a scheduler that sees TIME: requests arrive on a
clock (virtual or wall), wait in a priority-FIFO queue, are wave-filled
into free cache slots as soon as slots open, and are shed or evicted
when their SLO can no longer be met.

Invariants (pinned by ``tests/test_traffic.py``):

  * no cache-slot overflow — in-flight requests never exceed the
    engine's ``slots``; oversized requests are *rejected*, never raised;
  * FIFO within priority — among equal-priority queued requests,
    admission follows arrival order;
  * deadline eviction frees slots — an in-flight request past its
    completion deadline is evicted via ``engine.evict`` and its slot is
    reusable in the same tick's admission wave.

Clocks: the :class:`VirtualClock` advances by a fixed measured per-tick
cost (one decode step = ``tick_s``, one batched-prefill wave =
``prefill_s``), making a whole offered-load sweep deterministic and
machine-independent; the :class:`WallClock` reads ``perf_counter`` for
live measurement. Both expose the same 4-method protocol.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional

from repro.obs import trace as obs_trace
from repro.obs.metrics import LATENCY_EDGES_S
from repro.serve.engine import Request
from repro.traffic.workload import TrafficRequest


@dataclasses.dataclass(frozen=True)
class AdmissionConfig:
    """Admission-control policy of one batcher."""

    max_queue: int = 256          # arrivals beyond this depth are shed
    drop_late: bool = True        # shed queued requests past TTFT SLO
    evict_past_deadline: bool = True  # reclaim slots from late streams


class VirtualClock:
    """Deterministic simulation clock: decode ticks and prefill waves
    cost a fixed, measured amount of virtual time."""

    def __init__(self, tick_s: float, prefill_s: Optional[float] = None):
        if tick_s <= 0:
            raise ValueError(f"tick_s must be > 0, got {tick_s}")
        self.tick_s = tick_s
        self.prefill_s = tick_s if prefill_s is None else prefill_s
        self.now = 0.0

    def on_decode(self) -> None:
        self.now += self.tick_s

    def on_prefill(self) -> None:
        self.now += self.prefill_s

    def fast_forward(self, t: float) -> None:
        """Jump an idle engine to the next arrival (never backwards)."""
        if t > self.now:
            self.now = t


class WallClock:
    """Live wall-clock: decode/prefill advance time by themselves."""

    def __init__(self):
        self._t0 = time.perf_counter()

    @property
    def now(self) -> float:
        return time.perf_counter() - self._t0

    def on_decode(self) -> None:
        pass

    def on_prefill(self) -> None:
        pass

    def fast_forward(self, t: float) -> None:
        delta = t - self.now
        if delta > 0:
            time.sleep(delta)


@dataclasses.dataclass
class TrafficRunLog:
    """Everything one batcher run observed (feeds ``report.from_run``)."""

    requests: list[TrafficRequest]
    ticks: int
    queue_depth: list[int]        # sampled once per decode tick
    occupied: list[int]           # occupied slots, sampled per tick
    elapsed_s: float              # clock time (virtual or wall)
    wall_s: float                 # host wall time regardless of clock
    serve_report: object          # ServeReport of the run window
    out_of_ticks: bool = False


class ContinuousBatcher:
    """SLO-aware continuous batching in front of one ``ServeEngine``."""

    def __init__(self, engine, clock=None,
                 admission: AdmissionConfig = AdmissionConfig()):
        self.engine = engine
        self.clock = clock if clock is not None else WallClock()
        self.admission = admission
        self._slot_map: dict[int, TrafficRequest] = {}
        self._by_serve: dict[int, TrafficRequest] = {}
        engine.admission_hooks.append(self._on_wave)
        # Scheduler-side telemetry rides the ENGINE's metrics registry —
        # one registry per serving process, one Prometheus exposition.
        # Latencies are in clock seconds (virtual or wall, whichever
        # clock drives this batcher).
        m = engine.metrics
        self._g_queue = m.gauge(
            "traffic_queue_depth", "queued requests NOW (level)")
        self._c_shed = m.counter(
            "traffic_shed_total", "requests rejected or shed")
        self._c_completed = m.counter(
            "traffic_completed_total", "requests served to completion")
        self._h_ttft = m.histogram(
            "traffic_ttft_s", LATENCY_EDGES_S,
            "time to first token, completed requests (clock seconds)")
        self._h_latency = m.histogram(
            "traffic_latency_s", LATENCY_EDGES_S,
            "arrival-to-done latency, completed requests (clock seconds)")

    # -- engine admission hook ------------------------------------------

    def _on_wave(self, wave: list[tuple[int, Request]]) -> None:
        for slot, sreq in wave:
            tr = self._by_serve.get(id(sreq))
            if tr is not None:
                self._slot_map[slot] = tr

    # -- queue policy ---------------------------------------------------

    def _reject(self, tr: TrafficRequest, now: float,
                reason: str = "inadmissible") -> None:
        tr.state = "rejected"
        tr.t_done_s = now
        self._c_shed.inc()
        obs_trace.emit("shed", rid=tr.rid, engine=self.engine.trace_tag,
                       reason=reason)

    def _admissible(self, tr: TrafficRequest) -> bool:
        """Cache-fit check — rejection, not an exception: under open-loop
        traffic a malformed request must not take the scheduler down."""
        return (len(tr.prompt) >= 1
                and len(tr.prompt) + tr.max_new_tokens
                <= self.engine.max_len)

    # -- main loop ------------------------------------------------------

    def run(self, requests: list[TrafficRequest],
            max_ticks: int = 100_000) -> TrafficRunLog:
        """Serve one workload trace to completion (or ``max_ticks``)."""
        eng, clock, adm = self.engine, self.clock, self.admission
        arrivals = sorted(requests,
                          key=lambda r: (r.t_arrival_s, r.rid))
        queue: list[TrafficRequest] = []
        queue_depth: list[int] = []
        occupied: list[int] = []
        i = 0
        ticks = 0
        wall0 = time.perf_counter()
        t_start = clock.now
        counters0 = eng.counters()

        while i < len(arrivals) or queue or self._slot_map:
            if ticks >= max_ticks:
                break
            with obs_trace.span("batcher.tick"):
                with obs_trace.span("batcher.queue"):
                    now = clock.now
                    # 1) pull arrivals whose timestamp has passed
                    while i < len(arrivals) and \
                            arrivals[i].t_arrival_s <= now:
                        tr = arrivals[i]
                        i += 1
                        if len(queue) >= adm.max_queue or \
                                not self._admissible(tr):
                            self._reject(tr, now,
                                         "queue_full"
                                         if len(queue) >= adm.max_queue
                                         else "inadmissible")
                            continue
                        tr.state = "queued"
                        queue.append(tr)
                    # (steps 3-5 are no-ops for an idle engine)
                    idle = (not queue and not self._slot_map
                            and i < len(arrivals))
                    # 3) shed queued requests that already missed their
                    #    TTFT SLO
                    if adm.drop_late:
                        late = [t for t in queue if now > t.ttft_deadline_s]
                        for tr in late:
                            queue.remove(tr)
                            self._reject(tr, now, "ttft_slo")
                    # 4) evict in-flight requests past their completion
                    #    deadline
                    if adm.evict_past_deadline:
                        for slot, tr in list(self._slot_map.items()):
                            if now > tr.deadline_s and not tr.serve.done:
                                # engine.evict emits the slot-side "evict"
                                # event (freed tokens); this one joins it
                                # to the rid.
                                eng.evict(slot)
                                obs_trace.emit("evict_sched", rid=tr.rid,
                                               slot=slot,
                                               engine=eng.trace_tag,
                                               reason="deadline")
                                del self._slot_map[slot]
                                tr.state = "evicted"
                                tr.t_done_s = now
                    # 5) wave-fill free slots: priority first, FIFO within
                    wave = []
                    if queue and eng.free_slots:
                        queue.sort(key=lambda t: (t.priority,
                                                  t.t_arrival_s, t.rid))
                        n = min(len(queue), len(eng.free_slots))
                        wave, queue = queue[:n], queue[n:]
                if idle:
                    # 2) idle engine, empty queue: jump to the next
                    #    arrival instead of burning empty decode ticks
                    with obs_trace.span("batcher.clock"):
                        clock.fast_forward(arrivals[i].t_arrival_s)
                    continue
                if wave:
                    rids = " ".join(str(tr.rid) for tr in wave)
                    with obs_trace.span("batcher.admit", rids=rids):
                        sreqs = []
                        for tr in wave:
                            tr.serve = Request(
                                prompt=tr.prompt,
                                max_new_tokens=tr.max_new_tokens)
                            self._by_serve[id(tr.serve)] = tr
                            tr.state = "running"
                            tr.t_admit_s = now
                            sreqs.append(tr.serve)
                        admitted = eng.submit_many(sreqs)
                        assert admitted == len(sreqs), \
                            "wave sized to free_slots must admit fully"
                    if eng.batched_prefill and \
                            any(len(r.prompt) > 1 for r in sreqs):
                        with obs_trace.span("batcher.clock"):
                            clock.on_prefill()
                # 6) one decode tick for every occupied slot
                occupied.append(len(self._slot_map))
                queue_depth.append(len(queue))
                self._g_queue.set(len(queue))
                eng.step()
                with obs_trace.span("batcher.clock"):
                    clock.on_decode()
                ticks += 1
                with obs_trace.span("batcher.observe"):
                    # 7) observe first tokens and completions
                    now = clock.now
                    for slot, tr in list(self._slot_map.items()):
                        if tr.t_first_token_s is None and tr.serve.out:
                            tr.t_first_token_s = now
                        if tr.serve.done:
                            tr.state = "completed"
                            tr.t_done_s = now
                            self._c_completed.inc()
                            if tr.ttft_s is not None:
                                self._h_ttft.observe(tr.ttft_s)
                            self._h_latency.observe(tr.latency_s)
                            del self._slot_map[slot]

        # drain bookkeeping for anything still alive at the tick budget
        out_of_ticks = bool(queue or self._slot_map
                            or i < len(arrivals))
        now = clock.now
        for slot, tr in list(self._slot_map.items()):
            eng.evict(slot)
            obs_trace.emit("evict_sched", rid=tr.rid, slot=slot,
                           engine=eng.trace_tag, reason="out_of_ticks")
            tr.state = "evicted"
            tr.t_done_s = now
        self._slot_map.clear()
        for tr in queue:
            self._reject(tr, now, "out_of_ticks")
        for tr in arrivals[i:]:
            self._reject(tr, now, "out_of_ticks")
        self._by_serve.clear()
        elapsed = clock.now - t_start
        report = eng.report_since(counters0, elapsed)
        return TrafficRunLog(
            requests=list(requests), ticks=ticks,
            queue_depth=queue_depth, occupied=occupied,
            elapsed_s=elapsed, wall_s=time.perf_counter() - wall0,
            serve_report=report, out_of_ticks=out_of_ticks)
