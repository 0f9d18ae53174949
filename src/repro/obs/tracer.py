"""Bounded ring-buffer span tracer for the serving stack.

A request's life — submit, admission, queueing, batch formation, dispatch,
per-shard execution/retry/probe, epilogue — becomes a tree of spans with
structured attributes.  Design constraints, in order:

1. **Disabled must be free.**  ``NOOP_TRACER`` is a stateless singleton
   whose ``span``/``instant`` return a shared do-nothing context manager;
   the hot path when tracing is off is one attribute load and one call
   that does nothing.  The serving stack defaults to it.
2. **Enabled must be bounded.**  Finished spans land in a
   ``deque(maxlen=capacity)`` ring — oldest spans fall off, memory never
   grows with trace length.  Per-category sampling (``sample={"shard":
   0.25}``) deterministically keeps every ``round(1/rate)``-th span of a
   category, so repeated runs trace the same spans.
3. **Dual clocks.**  A span records host wall time (``time_fn``, default
   ``time.perf_counter``); calling ``span.hw(instance, seconds)`` attaches
   the *modeled photonic hardware* duration from ``core/simulator``, which
   :mod:`repro.obs.export` lays out on a second Perfetto process so host
   overhead and cycle-true device occupancy sit side by side.
4. **One clock with the device.**  An enabled, kept span is also a
   ``jax.profiler.TraceAnnotation`` carrying its scalar args, so a
   profile taken with a host tracer level of 1 or more holds it on the
   host plane, on the same clock as the device's operations.

Span nesting is tracked per thread: a span opened inside another becomes
its child (``parent_id``); worker-thread spans are roots on their own
track (``tid`` defaults to the thread name).
"""
from __future__ import annotations

import dataclasses
import itertools
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, Iterable, Optional, Tuple

from jax.profiler import TraceAnnotation


@dataclasses.dataclass(frozen=True)
class SpanRecord:
    """One finished trace event.

    ``ph`` follows the Chrome trace-event phase alphabet used by the
    exporter: ``"X"`` complete span, ``"i"`` instant, ``"b"``/``"e"``
    async begin/end (paired by ``aid``).  ``hw_instance``/``hw_s``, when
    set, place a mirror event of ``hw_s`` modeled seconds on that
    instance's hardware-clock track.
    """

    name: str
    cat: str
    ph: str
    t0: float
    dur: float
    tid: str
    span_id: int
    parent_id: Optional[int]
    args: Dict[str, Any]
    aid: Optional[int] = None
    hw_instance: Optional[str] = None
    hw_s: Optional[float] = None


class _NoopSpan:
    """Shared, stateless stand-in for a span when tracing is disabled."""

    __slots__ = ()

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **args: Any) -> None:
        pass

    def hw(self, instance: str, seconds: float) -> None:
        pass


_NOOP_SPAN = _NoopSpan()


class NoopTracer:
    """The disabled path: every operation is a constant-time no-op."""

    __slots__ = ()
    enabled = False

    def span(self, name: str, cat: str = "batch",
             tid: Optional[str] = None, **args: Any) -> _NoopSpan:
        return _NOOP_SPAN

    def instant(self, name: str, cat: str = "event",
                tid: Optional[str] = None, **args: Any) -> None:
        pass

    def async_begin(self, name: str, aid: int, cat: str = "request",
                    tid: Optional[str] = None, **args: Any) -> None:
        pass

    def async_end(self, name: str, aid: int, cat: str = "request",
                  tid: Optional[str] = None, **args: Any) -> None:
        pass

    def events(self) -> Tuple[SpanRecord, ...]:
        return ()

    def clear(self) -> None:
        pass

    def stats(self) -> Dict[str, Any]:
        return {"enabled": False, "emitted": 0, "retained": 0,
                "dropped_ring": 0, "sampled_out": 0}


#: module-level singleton; ``tracer or NOOP_TRACER`` is the idiom
NOOP_TRACER = NoopTracer()


class _Span:
    """Live span handle produced by :meth:`Tracer.span` (context manager)."""

    __slots__ = ("_tr", "name", "cat", "tid", "args", "t0", "span_id",
                 "parent_id", "hw_instance", "hw_s", "_sampled", "_ann")

    def __init__(self, tr: "Tracer", name: str, cat: str,
                 tid: Optional[str], args: Dict[str, Any], sampled: bool):
        self._tr = tr
        self.name = name
        self.cat = cat
        self.tid = tid
        self.args = args
        self.t0 = 0.0
        self.span_id = 0
        self.parent_id: Optional[int] = None
        self.hw_instance: Optional[str] = None
        self.hw_s: Optional[float] = None
        self._sampled = sampled

    def set(self, **args: Any) -> None:
        """Attach/overwrite structured attributes on the open span."""
        self.args.update(args)

    def hw(self, instance: str, seconds: float) -> None:
        """Mirror this span as ``seconds`` of modeled hardware time."""
        self.hw_instance = instance
        self.hw_s = float(seconds)

    def __enter__(self) -> "_Span":
        tr = self._tr
        self.span_id = next(tr._ids)
        if self._sampled:
            stack = tr._stack()
            self.parent_id = stack[-1] if stack else None
            stack.append(self.span_id)
            self._ann = TraceAnnotation(self.name)
            self._ann.__enter__()
        self.t0 = tr._time()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        tr = self._tr
        dur = tr._time() - self.t0
        if not self._sampled:
            return False
        stack = tr._stack()
        if stack and stack[-1] == self.span_id:
            stack.pop()
        if exc_type is not None:
            self.args["error"] = exc_type.__name__
        # args set inside the body are known only now
        self._ann.set_metadata(**{k: v for k, v in self.args.items()
                                  if isinstance(v, (int, float, str))})
        self._ann.__exit__(None, None, None)
        if self.tid is None:
            self.tid = threading.current_thread().name
        tr._emit(SpanRecord(
            name=self.name, cat=self.cat, ph="X", t0=self.t0, dur=dur,
            tid=self.tid, span_id=self.span_id, parent_id=self.parent_id,
            args=self.args, hw_instance=self.hw_instance, hw_s=self.hw_s))
        return False


class Tracer:
    """Span recorder with a bounded ring and per-category sampling.

    Parameters
    ----------
    capacity:
        Ring size; the newest ``capacity`` finished events are retained.
    sample:
        Optional ``{category: keep_rate}`` map (rate in (0, 1]); a
        category keeps every ``round(1/rate)``-th span, deterministically.
        Unlisted categories are always kept.
    time_fn:
        Host clock (monotonic seconds).  Injectable for tests.
    """

    enabled = True

    def __init__(self, capacity: int = 65536,
                 sample: Optional[Dict[str, float]] = None,
                 time_fn: Callable[[], float] = time.perf_counter):
        if capacity <= 0:
            raise ValueError(f"capacity must be positive, got {capacity}")
        self.capacity = capacity
        self._time = time_fn
        self._buf: deque = deque(maxlen=capacity)
        self._ids = itertools.count(1)
        self._tls = threading.local()
        self._lock = threading.Lock()
        self._emitted = 0
        self._sampled_out = 0
        self._periods: Dict[str, int] = {}
        self._cat_seen: Dict[str, int] = {}
        for cat, rate in (sample or {}).items():
            if not 0 < rate <= 1:
                raise ValueError(
                    f"sample rate for {cat!r} must be in (0, 1], got {rate}")
            self._periods[cat] = max(1, round(1.0 / rate))

    # -- internals --------------------------------------------------------

    def _stack(self) -> list:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def _keep(self, cat: str) -> bool:
        period = self._periods.get(cat)
        if period is None or period == 1:
            return True
        with self._lock:
            n = self._cat_seen.get(cat, 0)
            self._cat_seen[cat] = n + 1
        if n % period == 0:
            return True
        with self._lock:
            self._sampled_out += 1
        return False

    def _emit(self, rec: SpanRecord) -> None:
        with self._lock:
            self._emitted += 1
            self._buf.append(rec)

    # -- recording API ----------------------------------------------------

    def span(self, name: str, cat: str = "batch",
             tid: Optional[str] = None, **args: Any) -> _Span:
        """Open a span as a context manager; children nest via the
        per-thread stack.  Sampled-out spans still run their body but
        record nothing and don't claim children."""
        return _Span(self, name, cat, tid, dict(args), self._keep(cat))

    def instant(self, name: str, cat: str = "event",
                tid: Optional[str] = None, **args: Any) -> None:
        """Record a zero-duration point event (fault trips, sheds, …)."""
        if not self._keep(cat):
            return
        stack = self._stack()
        self._emit(SpanRecord(
            name=name, cat=cat, ph="i", t0=self._time(), dur=0.0,
            tid=tid or threading.current_thread().name,
            span_id=next(self._ids),
            parent_id=stack[-1] if stack else None, args=dict(args)))

    def async_begin(self, name: str, aid: int, cat: str = "request",
                    tid: Optional[str] = None, **args: Any) -> None:
        """Open one side of an async pair (e.g. a request's queue-to-reply
        life) matched to :meth:`async_end` by ``aid``."""
        self._emit(SpanRecord(
            name=name, cat=cat, ph="b", t0=self._time(), dur=0.0,
            tid=tid or "requests", span_id=next(self._ids),
            parent_id=None, args=dict(args), aid=aid))

    def async_end(self, name: str, aid: int, cat: str = "request",
                  tid: Optional[str] = None, **args: Any) -> None:
        self._emit(SpanRecord(
            name=name, cat=cat, ph="e", t0=self._time(), dur=0.0,
            tid=tid or "requests", span_id=next(self._ids),
            parent_id=None, args=dict(args), aid=aid))

    # -- reading API ------------------------------------------------------

    def events(self) -> Tuple[SpanRecord, ...]:
        """Snapshot of retained events, oldest first."""
        with self._lock:
            return tuple(self._buf)

    def events_by_cat(self, cat: str) -> Tuple[SpanRecord, ...]:
        return tuple(r for r in self.events() if r.cat == cat)

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()
            self._emitted = 0
            self._sampled_out = 0
            self._cat_seen.clear()

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            retained = len(self._buf)
            return {"enabled": True, "emitted": self._emitted,
                    "retained": retained,
                    "dropped_ring": self._emitted - retained,
                    "sampled_out": self._sampled_out}


def category_census(records: Iterable[SpanRecord]) -> Dict[str, int]:
    """Count events per category — the quick shape check for a trace."""
    out: Dict[str, int] = {}
    for r in records:
        out[r.cat] = out.get(r.cat, 0) + 1
    return dict(sorted(out.items()))
