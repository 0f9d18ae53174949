"""CNNServer: the serving loop tying registry + batcher + engine together.

One `step()` forms at most one batch (dynamic batcher policy), fetches the
model's resident plan (registry, LRU), stacks the requests into an NHWC
batch (on the host and sent in one transfer, unless a request's image is
already a device array), runs it through the whole-model jitted pipeline
(engine.forward_jit) — the entire layer chain against the resident DKV
imprint in ONE XLA dispatch — and splits the outputs back to their
requests.  With a ``dispatcher`` (serve/dispatch.py) the batch is instead
sharded *concurrently* across the fleet's simulated accelerator
instances, bitwise-identically — surviving injected crashes, stragglers
and stuck reconfigurations via the dispatcher's retry/quarantine loop.
Wall-clock and modeled-hardware telemetry is recorded per batch — per
shard and instance operating point when sharded (telemetry.py); pipeline
compile stalls are counted per (plan, batch bucket) in
``pipeline_compiles``; fleet health and admission counters surface in
``telemetry.summary()["fleet"]``.

SLO-aware admission control (``slo=ServeSLO(...)``): every ``submit``
estimates time-to-completion from the queue depth ahead, the measured
per-frame service rate (EMA over served batches), and the *surviving*
fleet capacity; a request the degraded fleet cannot plausibly serve
inside the deadline is shed at the door with a typed
``AdmissionRejected`` instead of being queued to blow the p99.  When
quarantined instances probe back in, the capacity estimate recovers and
admission resumes — graceful degradation, then graceful recovery.

The clock is injectable (``time_fn``) so tests and trace replays can drive
a virtual clock; by default everything is wall time.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import engine
from ..obs.tracer import NOOP_TRACER, Tracer
from .batcher import (BATCH, ContinuousBatcher, DynamicBatcher,
                      INTERACTIVE)
from .brownout import BrownoutController, RungTransition
from .dispatch import ShardedDispatcher
from .faults import (AdmissionRejected, BrownoutShed,
                     CorruptionBudgetExceeded, QueueOverflow,
                     RequestExpired, ServingFault)
from .registry import PlanRegistry
from ..core.operating_point import OperatingPoint
from .telemetry import DEFAULT_HW_POINTS, TelemetryLog


@dataclasses.dataclass(frozen=True)
class ServeSLO:
    """The serving contract admission control defends.

    ``deadline_s``   — target submit-to-result completion time.
    ``flush_fraction`` — force-dispatch a queue once its oldest request
                       has burned this fraction of the deadline waiting
                       (don't let batching eat the whole budget).
    ``min_observations`` — batches to observe before shedding anything
                       (the rate estimate needs data; admit until then).
    ``max_corrupted_frame_rate`` — integrity budget: the tolerated EMA of
                       detected-corrupted frames per served frame.  While
                       the fleet's corruption rate exceeds it, ``submit``
                       sheds with ``CorruptionBudgetExceeded``; the EMA
                       decays as clean batches are served, so admission
                       resumes once the datapath heals.  ``None`` (the
                       default) disables integrity shedding.
    ``corruption_halflife_s`` — the corrupted-frame-rate EMA also ages on
                       the server clock with this half-life, so integrity
                       shedding is a circuit breaker, not a latch: once
                       the corrupting instance is quarantined, admission
                       resumes even if no traffic is being served to
                       decay the rate.
    """
    deadline_s: float
    flush_fraction: float = 0.5
    min_observations: int = 1
    max_corrupted_frame_rate: Optional[float] = None
    corruption_halflife_s: float = 0.5

    def __post_init__(self) -> None:
        if self.deadline_s <= 0:
            raise ValueError(
                f"deadline_s must be > 0, got {self.deadline_s}")
        if not 0 < self.flush_fraction <= 1:
            raise ValueError(
                f"flush_fraction must be in (0, 1], got "
                f"{self.flush_fraction}")
        if (self.max_corrupted_frame_rate is not None
                and not 0 < self.max_corrupted_frame_rate <= 1):
            raise ValueError(
                f"max_corrupted_frame_rate must be in (0, 1], got "
                f"{self.max_corrupted_frame_rate}")
        if self.corruption_halflife_s <= 0:
            raise ValueError(
                f"corruption_halflife_s must be > 0, got "
                f"{self.corruption_halflife_s}")


def _timed_transfers(xs, put, span) -> list:
    """``put`` of each of ``xs``, each call timed: the traced path of
    ``CNNServer.step``'s ``h2d`` span, whose counters are ``transfers``,
    ``bytes`` and ``max_ms`` (the slowest transfer).  ``xs`` is the
    host-stacked batch alone (one transfer) or, when a request's image is
    already a device array, the requests' images one by one."""
    out, slowest = [], 0.0
    for x in xs:
        t = time.perf_counter()
        out.append(put(x))
        slowest = max(slowest, time.perf_counter() - t)
    span.set(transfers=len(out), bytes=sum(x.nbytes for x in out),
             max_ms=1e3 * slowest)
    return out


def _as_f32(x) -> jax.Array:
    return jnp.asarray(x, jnp.float32)


class CNNServer:
    def __init__(self, registry: PlanRegistry, max_batch: int = 8,
                 max_wait_s: float = 0.005,
                 hw_points: Sequence[OperatingPoint] = DEFAULT_HW_POINTS,
                 interpret: Optional[bool] = None,
                 time_fn: Callable[[], float] = time.monotonic,
                 dispatcher: Optional[ShardedDispatcher] = None,
                 slo: Optional[ServeSLO] = None,
                 tracer: Optional[Tracer] = None,
                 continuous: bool = False,
                 max_queue: Optional[int] = None,
                 age_promote_s: Optional[float] = None,
                 brownout: Optional[BrownoutController] = None,
                 service_model: Optional[Callable[[str, int, OperatingPoint],
                                                  float]] = None):
        self.registry = registry
        batcher_cls = ContinuousBatcher if continuous else DynamicBatcher
        self.batcher = batcher_cls(max_batch=max_batch,
                                   max_wait_s=max_wait_s,
                                   max_queue=max_queue,
                                   age_promote_s=age_promote_s)
        self.telemetry = TelemetryLog(hw_points)
        self.interpret = interpret
        self.dispatcher = dispatcher
        self.slo = slo
        #: span tracer (obs.Tracer); defaults to the free no-op path, and
        #: is propagated to the dispatcher (and its fault injector) so
        #: request, batch, shard and fault events land in one ring
        self.tracer = tracer if tracer is not None else NOOP_TRACER
        self.batcher.metrics = self.telemetry.metrics
        if dispatcher is not None:
            # one scrape registry for the whole stack: batcher depth,
            # request latencies AND the dispatcher's SDC detection
            # latencies land in telemetry.metrics
            dispatcher.metrics = self.telemetry.metrics
        if dispatcher is not None and tracer is not None:
            dispatcher.tracer = self.tracer
        self._time = time_fn
        #: modeled service time, ``(model, batch_size, serving_point) ->
        #: seconds``; when set, the service-rate EMA, request latencies
        #: and telemetry exec_s all run in *modeled* time on the server's
        #: injectable clock — the virtual-clock determinism the overload
        #: harness replays on (wall time otherwise)
        self.service_model = service_model
        #: brownout ladder controller; observed at the top of every step,
        #: applied transitions stretch the batching window, gate
        #: batch-class admission, and downshift the operating point
        self.brownout = brownout
        self._base_max_wait_s = max_wait_s
        #: the operating point the device is currently retuned to; starts
        #: at the primary telemetry point and moves with brownout rungs
        #: (``set_operating_point``)
        self.serving_point: OperatingPoint = self.telemetry.points[0]
        self._base_point: OperatingPoint = self.serving_point
        self.results: Dict[int, np.ndarray] = {}
        #: typed per-request failures (rid -> ServingFault): expired
        #: requests land here instead of ``results``
        self.failures: Dict[int, ServingFault] = {}
        #: pipeline trace+compile stalls paid inside step() so far — one
        #: per (plan, batch-size bucket), like the registry's plan misses
        self.pipeline_compiles = 0
        #: admission-control state: shed/admitted counters + the EMA of
        #: measured per-frame service time the estimator runs on
        self.admission = {"admitted": 0, "shed": 0, "integrity_shed": 0,
                          "queue_shed": 0, "brownout_shed": 0, "expired": 0}
        self._frame_s_ema: Optional[float] = None
        self._observed_batches = 0
        #: EMA of detected-corrupted frames per served frame — the
        #: corrupted-frame-rate SLO (``slo.max_corrupted_frame_rate``)
        #: sheds against this; decays toward 0 over clean batches AND on
        #: the server clock (corruption_halflife_s), so shedding lifts
        #: after the corrupting instance is quarantined
        self._corruption_ema = 0.0
        self._corruption_t: Optional[float] = None
        if dispatcher is not None or slo is not None or brownout is not None:
            self.telemetry.attach_fleet(self._fleet_report)

    # -- fleet / admission reporting -------------------------------------

    def _fleet_report(self) -> Dict:
        """summary()["fleet"]: dispatcher health + admission counters."""
        out = (self.dispatcher.fleet_health()
               if self.dispatcher is not None else {})
        out["admission"] = dict(
            self.admission,
            slo_deadline_s=(self.slo.deadline_s if self.slo else None),
            est_frame_s=self._frame_s_ema)
        out["sdc"] = {
            "corrupted_frame_rate_ema": self._corruption_ema,
            "budget": (self.slo.max_corrupted_frame_rate
                       if self.slo else None),
        }
        if self.brownout is not None:
            out["brownout"] = self.brownout.report()
        return out

    def _now(self, now: Optional[float]) -> float:
        return self._time() if now is None else now

    def _decay_corruption(self, now: float) -> None:
        """Age the corrupted-frame-rate EMA on the server clock."""
        if self._corruption_t is not None and now > self._corruption_t:
            half = (self.slo.corruption_halflife_s
                    if self.slo is not None else 0.5)
            self._corruption_ema *= 0.5 ** (
                (now - self._corruption_t) / half)
        self._corruption_t = now

    # -- admission control ------------------------------------------------

    def _healthy_fraction(self) -> float:
        if self.dispatcher is None:
            return 1.0
        return self.dispatcher.healthy_capacity_fraction()

    def estimated_completion_s(self, priority: Optional[str] = None,
                               now: Optional[float] = None,
                               ) -> Optional[float]:
        """Expected submit-to-result time for a request arriving now.

        Queue depth ahead (plus this request) times the measured
        per-frame service time, inflated by the surviving fleet capacity
        — a 2-of-3 instance loss means a third of the throughput, three
        times the drain time.  ``None`` until enough batches have been
        observed to trust the rate.

        The depth is class-aware: an *interactive* arrival queues behind
        only the promoted backlog (selection orders promoted work first),
        so a deep batch-class backlog must not shed interactive traffic
        the priority system would in fact serve in time.  With
        ``priority`` omitted (or batch-class), the full depth counts.
        """
        if (self._frame_s_ema is None or self.slo is None
                or self._observed_batches < self.slo.min_observations):
            return None
        frac = self._healthy_fraction()
        if frac <= 0:
            return float("inf")
        if priority == INTERACTIVE:
            frames_ahead = self.batcher.pending_promoted(self._now(now)) + 1
        else:
            frames_ahead = self.batcher.pending() + 1
        return frames_ahead * self._frame_s_ema / frac

    def submit(self, model: str, x: Any,
               now: Optional[float] = None,
               priority: str = INTERACTIVE,
               deadline_s: Optional[float] = None) -> int:
        """Queue one image for ``model``; returns the request id.

        Shape is validated here, at the door: a malformed image must not
        reach a formed batch, where it would fail the whole batch's stack
        after its requests have already left the queue.  An unregistered
        model raises ``KeyError`` here too — never deep inside ``step()``
        after the request is already queued.  Under an SLO, admission
        control runs here as well: a request the surviving fleet cannot
        serve inside the deadline is shed with ``AdmissionRejected`` and
        nothing is queued.

        ``priority`` picks the class: interactive requests get
        completion-estimate admission control against ``deadline_s`` (or
        the SLO deadline); batch-class requests skip the estimate check
        unless they carry an explicit ``deadline_s`` — their backpressure
        is the bounded queue (typed ``QueueOverflow``) and, under
        brownout, door shedding (typed ``BrownoutShed``).  A request with
        ``deadline_s`` that is still queued when the deadline passes is
        cancelled by the next step's expiry sweep (typed
        ``RequestExpired`` in ``failures``).
        """
        if model not in self.registry.registered:
            raise KeyError(f"model {model!r} not registered "
                           f"(registered: {sorted(self.registry.registered)})")
        expect = self.registry.input_shape(model)
        got = np.shape(x)
        if got != expect:
            raise ValueError(f"model {model!r} expects input shape "
                             f"{expect}, got {got}")
        now = self._now(now)
        if (self.brownout is not None and priority == BATCH
                and not self.brownout.rung.admit_batch):
            self.admission["brownout_shed"] += 1
            rung = self.brownout.rung.name
            self.tracer.instant("admission.brownout_shed", cat="admission",
                                model=model, rung=rung)
            self.telemetry.metrics.counter(
                "serve_brownout_sheds_total",
                "batch-class requests shed by the brownout ladder",
                model=model).inc()
            raise BrownoutShed(model=model, rung=rung)
        if self.slo is not None and self.slo.max_corrupted_frame_rate:
            self._decay_corruption(now)
        if (self.slo is not None
                and self.slo.max_corrupted_frame_rate is not None
                and self._corruption_ema > self.slo.max_corrupted_frame_rate):
            self.admission["integrity_shed"] += 1
            self.tracer.instant(
                "admission.integrity_shed", cat="admission", model=model,
                rate=self._corruption_ema,
                budget=self.slo.max_corrupted_frame_rate)
            raise CorruptionBudgetExceeded(
                model=model, rate=self._corruption_ema,
                budget=self.slo.max_corrupted_frame_rate)
        # completion-estimate admission: always for interactive traffic,
        # for batch traffic only when it carries its own deadline (its
        # default backpressure is the queue bound, not an SLO estimate)
        checked_deadline = (deadline_s if deadline_s is not None
                            else (self.slo.deadline_s
                                  if self.slo is not None else None))
        if (checked_deadline is not None and self.slo is not None
                and (priority == INTERACTIVE or deadline_s is not None)):
            est = self.estimated_completion_s(priority=priority, now=now)
            if est is not None and est > checked_deadline:
                self.admission["shed"] += 1
                self.tracer.instant(
                    "admission.shed", cat="admission", model=model,
                    est_s=est, deadline_s=checked_deadline)
                raise AdmissionRejected(
                    model=model, est_s=est, deadline_s=checked_deadline,
                    healthy_fraction=self._healthy_fraction())
        try:
            rid = self.batcher.submit(model, x, now, priority=priority,
                                      deadline_s=deadline_s)
        except QueueOverflow:
            self.admission["queue_shed"] += 1
            self.tracer.instant("admission.queue_shed", cat="admission",
                                model=model)
            raise
        self.admission["admitted"] += 1
        self.tracer.async_begin("request", aid=rid, model=model)
        return rid

    def pending(self) -> int:
        return self.batcher.pending()

    def reset(self) -> None:
        """Drop the trace's accumulated state and release held resources.

        ``results``, ``failures`` and the telemetry records otherwise
        grow for the server's lifetime — callers running multiple traces
        against one server (or consuming results incrementally) should
        reset between traces, after harvesting what they need.  Admission
        counters are cleared with them (they are per-trace tallies), the
        dispatcher's lazily-created shard thread pool is shut down (it is
        recreated on the next sharded dispatch — no pool leaks across
        traces), and only the service-rate EMA survives: it describes the
        hardware, not the trace.
        """
        if self.batcher.pending():
            raise RuntimeError(
                f"{self.batcher.pending()} requests still queued; drain "
                f"before resetting")
        if self.dispatcher is not None:
            self.dispatcher.close()
        self.results.clear()
        self.failures.clear()
        for key in self.admission:
            self.admission[key] = 0
        self.telemetry.reset()

    # -- brownout / operating point ---------------------------------------

    def set_operating_point(self, point: OperatingPoint) -> None:
        """Retune the serving device to ``point`` (and replan if needed).

        The registry's planner recompiles resident plans against the new
        accelerator on their next fetch — bitwise-identical outputs, only
        packing geometry moves (``engine.plan_model``'s contract) — so a
        brownout downshift never changes what requesters receive.
        """
        if point == self.serving_point:
            return
        prev = self.serving_point
        self.serving_point = point
        self.registry.set_accelerator(point)
        self.telemetry.metrics.counter(
            "serve_point_switches_total",
            "serving operating-point retunes").inc()
        self.tracer.instant("serve.point_switch", cat="brownout",
                            src=prev.label, dst=point.label)

    def _apply_rung(self, tr: RungTransition) -> None:
        """Apply one ladder transition to the live serving policy."""
        rung = self.brownout.rung
        self.batcher.max_wait_s = self._base_max_wait_s * rung.max_wait_scale
        self.set_operating_point(rung.point if rung.point is not None
                                 else self._base_point)
        m = self.telemetry.metrics
        m.gauge("serve_brownout_rung",
                "current brownout ladder rung").set(self.brownout.rung_index)
        m.counter("serve_brownout_transitions_total",
                  "brownout rung transitions",
                  direction=tr.direction).inc()
        self.tracer.instant(
            "brownout.rung", cat="brownout", direction=tr.direction,
            src=self.brownout.rungs[tr.src].name, dst=rung.name,
            pressure=tr.pressure)

    def _observe_brownout(self, now: float) -> None:
        power = None
        if self.dispatcher is not None:
            health = self.dispatcher.fleet_health()
            power = health.get("admitted_power_w")
        tr = self.brownout.observe(
            now, depth=self.batcher.pending(),
            est_completion_s=self.estimated_completion_s(),
            deadline_s=(self.slo.deadline_s if self.slo is not None
                        else None),
            power_w=power)
        if tr is not None:
            self._apply_rung(tr)

    def _sweep_expired(self, now: float) -> None:
        """Cancel queued requests whose deadline passed (typed failures)."""
        for req in self.batcher.expire(now):
            fault = RequestExpired(
                model=req.model, rid=req.rid,
                deadline_s=req.deadline - req.t_submit,
                waited_s=now - req.t_submit)
            self.failures[req.rid] = fault
            self.admission["expired"] += 1
            self.telemetry.metrics.counter(
                "serve_requests_expired_total",
                "queued requests cancelled at their deadline",
                model=req.model).inc()
            self.tracer.async_end("request", aid=req.rid, model=req.model,
                                  expired=True)
            self.tracer.instant("request.expired", cat="admission",
                                model=req.model, rid=req.rid,
                                waited_s=fault.waited_s)

    def _slo_flush_due(self, now: float) -> bool:
        """Dispatch early once queue wait eats into the SLO deadline."""
        if self.slo is None:
            return False
        oldest = self.batcher.oldest_wait_s(now)
        return (oldest is not None
                and oldest >= self.slo.flush_fraction * self.slo.deadline_s)

    def step(self, now: Optional[float] = None, force: bool = False) -> int:
        """Serve at most one batch; returns the number of requests served.

        The batch runs through the whole-model jitted pipeline
        (``engine.forward_jit``): one XLA dispatch for the entire layer
        chain, batch size bucketed to the next power of two.  The recorded
        per-batch ``exec_s`` is full service time: plan fetch (a registry
        miss pays compile/LRU-reload here, where the requester actually
        waits), batch stacking, kernel execution — including any fault
        retries/re-apportionment when dispatched across a fleet — and,
        for the first batch in a (plan, bucket), the pipeline
        trace+compile stall, which ``pipeline_compiles`` counts.  Request
        latencies are taken on the server's own clock (``time_fn``), so a
        virtual-clock replay stays in one unit system; on the default
        wall clock they include the compile stall too.
        """
        now = self._now(now)
        self._sweep_expired(now)
        if self.brownout is not None:
            self._observe_brownout(now)
        fb = self.batcher.pop_batch(now,
                                    force=force or self._slo_flush_due(now))
        if fb is None:
            return 0
        tr = self.tracer
        waits = fb.queue_waits()
        bucket = engine.batch_bucket(fb.size)
        with tr.span("batch", cat="batch", model=fb.model, size=fb.size,
                     bucket=bucket) as bsp:
            if tr.enabled:
                bsp.set(queue_wait_s=sum(waits) / fb.size)
            t0 = time.perf_counter()
            with tr.span("plan.fetch", cat="batch", model=fb.model):
                entry = self.registry.get(fb.model)
            with tr.span("stack", cat="batch") as ssp:
                xs = [r.x for r in fb.requests]
                # host images are stacked on the host and sent in one
                # transfer; images already on the device stay there, as
                # pulling them back would add a device-to-host copy
                on_host = not any(isinstance(x, jax.Array) for x in xs)
                ssp.set(host_stacked=int(on_host))
                if on_host:
                    with tr.span("host_stack", cat="batch") as hsp:
                        xs = [np.stack([np.asarray(x, np.float32)
                                        for x in xs])]
                        hsp.set(bytes=xs[0].nbytes)
                put = jax.device_put if on_host else _as_f32
                with tr.span("h2d", cat="batch") as dsp:
                    if tr.enabled:
                        xs = _timed_transfers(xs, put, dsp)
                    else:
                        xs = [put(x) for x in xs]
                xb = xs[0] if on_host else jnp.stack(xs)
            compiles_before = engine.pipeline_cache_info()["compiles"]
            sdc_before = (self.dispatcher.counters["sdc_detections"]
                          if self.dispatcher is not None else 0)
            shard_info = ()
            with tr.span("exec", cat="batch", model=fb.model):
                if self.dispatcher is None:
                    with tr.span("dispatch", cat="batch", bucket=bucket):
                        out = engine.forward_jit(entry.plan, xb,
                                                 interpret=self.interpret)
                    with tr.span("device_wait", cat="batch"):
                        out = jax.block_until_ready(out)
                else:
                    # shard the batch across the fleet; outputs keep
                    # request order (sim_specs lets a hardware-paced fleet
                    # floor each shard at its instance's modeled device
                    # time)
                    out, runs = self.dispatcher.run(
                        entry.plan, xb, interpret=self.interpret,
                        sim_specs=entry.sim_specs)
                    shard_info = [(r.instance.name, r.batch_size,
                                   r.instance.hw, r.exec_s) for r in runs]
            compiled = (engine.pipeline_cache_info()["compiles"]
                        - compiles_before)
            self.pipeline_compiles += compiled
            if self.service_model is not None:
                # modeled service time on the injectable clock: the EMA,
                # latencies and telemetry all stay in one (virtual) unit
                # system, deterministic across hosts
                exec_s = self.service_model(fb.model, fb.size,
                                            self.serving_point)
            else:
                exec_s = time.perf_counter() - t0
            # service-rate EMA feeds admission control; fault retries
            # inflate exec_s, which is exactly the backpressure the
            # estimator needs
            per_frame = exec_s / fb.size
            self._frame_s_ema = (per_frame if self._frame_s_ema is None
                                 else 0.3 * per_frame
                                 + 0.7 * self._frame_s_ema)
            self._observed_batches += 1
            # corrupted-frame-rate EMA: detections this batch (integrity
            # checks flagged a shard; it was re-executed bitwise-clean)
            # attributed to the batch's frames pro-rata by shard count.
            # Clean batches decay the EMA, so integrity shedding lifts
            # once the datapath heals.
            detections = ((self.dispatcher.counters["sdc_detections"]
                           - sdc_before)
                          if self.dispatcher is not None else 0)
            corrupted_frames = 0
            if detections:
                shards = max(1, len(shard_info))
                corrupted_frames = min(
                    fb.size,
                    int(np.ceil(detections * fb.size / shards)))
            done = (now + exec_s if self.service_model is not None
                    else self._now(None))
            self._decay_corruption(done)
            rate = corrupted_frames / fb.size
            self._corruption_ema = (0.3 * rate
                                    + 0.7 * self._corruption_ema)
            if detections:
                self.telemetry.record_sdc(fb.model, detections,
                                          corrupted_frames)
            with tr.span("epilogue", cat="batch"):
                with tr.span("d2h", cat="batch", bytes=out.nbytes):
                    out_np = np.asarray(out)
                lats = []
                for i, req in enumerate(fb.requests):
                    self.results[req.rid] = out_np[i]
                    lat = done - req.t_submit
                    lats.append(lat)
                    tr.async_end("request", aid=req.rid, model=fb.model,
                                 latency_s=lat)
                with tr.span("telemetry", cat="batch"):
                    self.telemetry.record_batch(
                        model=fb.model, sim_specs=entry.sim_specs,
                        batch_size=fb.size, t_formed=now, exec_s=exec_s,
                        queue_waits_s=waits, latencies_s=lats,
                        shards=shard_info, exec_specs=entry.exec_specs,
                        op_points=entry.plan.layer_points,
                        reconfig_switches=entry.plan.reconfig_switches,
                        priorities=fb.priorities())
                    if tr.enabled and self.dispatcher is None:
                        # unsharded: the whole batch's modeled device time
                        # lands on one "local" hardware track (sharded
                        # batches annotate per-shard hardware time in the
                        # dispatcher instead)
                        cost = self.telemetry._hw_cost(
                            fb.model, entry.sim_specs, fb.size,
                            self.telemetry.points[0])
                        bsp.hw("local", cost.frame_latency_s * fb.size)
            bsp.set(compiles=compiled, exec_s=exec_s)
        return fb.size

    def run_until_drained(self, max_steps: int = 100_000,
                          ) -> Dict[int, np.ndarray]:
        """Serve everything queued (force-flushing ragged final batches).

        Returns ``self.results`` — the server's *cumulative* rid->output
        map, including requests served before this call; use ``reset()``
        between traces for per-trace results.
        """
        for _ in range(max_steps):
            if self.step(force=True) == 0 and self.batcher.pending() == 0:
                break
        return self.results
