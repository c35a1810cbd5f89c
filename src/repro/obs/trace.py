"""In-process instrumentation: profiler spans, span counters, request traces.

:func:`span` is the one primitive every layer boundary uses.  ``with
span("<layer>.<stage>")`` gives up to three outputs:

* a ``jax.profiler.TraceAnnotation`` named ``repro.<layer>.<stage>``, so
  host work lands in the profiler's trace beside the device ops, on
  their clock (it costs next to nothing while no profile is taken);
* with ``metrics=`` a :class:`~repro.runtime.metrics.MetricsRegistry`,
  three integer counters: ``<stage>_n``, ``<stage>_ns`` (wall,
  ``time.perf_counter_ns``) and ``<stage>_cpu_ns`` (this thread's CPU
  time, ``time.thread_time_ns``), so wall minus CPU is time the thread
  spent waiting;
* while a request :class:`Trace` is current on the thread (see
  :func:`use_span` / :func:`use_spans`), a child :class:`Span` named
  ``<stage>`` on the trace's own clock, so spans nest by call structure
  and stay exact under ``VirtualClock``.

One :class:`Trace` follows a request end to end: prepare (and its
sampling, induction, vertex-cut and padding stages) on the submitting
thread, admission in ``runtime.queue``, queue wait, batch close and
execute (stack, dispatch, fetch) on the worker, with the execute span
stamped with the :class:`~repro.exec.plan.SpmmPlan` attributes
(impl, precision, mesh width, block sizes) that served it.
Device time per layer comes from the profiler's trace, under the named
scopes of the compiled steps, not from these spans.

Design constraints, in order:

* **Clock-faithful.** Every trace timestamp comes from a
  :class:`~repro.runtime.clock.Clock` — under ``VirtualClock`` a trace
  is bit-for-bit deterministic, so tests assert exact span edges.
* **Cheap when off.** With no profile being taken, no registry passed
  and no trace current, a span costs one annotation object and a
  thread-local read: a microsecond or two.
* **No upward imports.** This module depends only on
  ``runtime.clock``; JAX's profiler and the ledger hookup are imported
  lazily, so ``runtime`` (which imports this module) stays free of JAX
  at import time and ``dist`` stays a leaf layer.

Span ids and trace ids are deterministic counters (no randomness, no
wall-clock salt) — resumable tests and virtual-clock runs stay exact.
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

from repro.runtime.clock import Clock, RealClock

__all__ = [
    "SpanEvent",
    "Span",
    "Trace",
    "Tracer",
    "span",
    "current_span",
    "current_spans",
    "use_span",
    "use_spans",
    "plan_attributes",
    "engine_batch_info",
    "install_ledger_listener",
]


class SpanEvent:
    """A point-in-time annotation on a span (e.g. one ledger record)."""

    __slots__ = ("name", "at", "attributes")

    def __init__(self, name: str, at: float, attributes: Dict[str, object]):
        self.name = name
        self.at = at
        self.attributes = attributes

    def to_dict(self) -> dict:
        return {"name": self.name, "at": self.at,
                "attributes": dict(self.attributes)}


class Span:
    """One timed operation inside a trace.

    ``finish`` is idempotent: the first call pins ``end``, later calls
    are no-ops — so a span finished on the failure path can't be
    re-stamped by a late success path.
    """

    __slots__ = ("trace", "span_id", "parent_id", "name", "start", "end",
                 "attributes", "events")

    def __init__(self, trace: "Trace", span_id: int, parent_id: Optional[int],
                 name: str, start: float, attributes: Dict[str, object]):
        self.trace = trace
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.start = start
        self.end: Optional[float] = None
        self.attributes = attributes
        self.events: List[SpanEvent] = []

    def set(self, **attrs: object) -> "Span":
        self.attributes.update(attrs)
        return self

    def event(self, name: str, at: Optional[float] = None,
              **attrs: object) -> SpanEvent:
        ev = SpanEvent(name, self.trace.clock.now() if at is None else at,
                       attrs)
        self.events.append(ev)
        return ev

    def finish(self, at: Optional[float] = None) -> "Span":
        if self.end is None:
            self.end = self.trace.clock.now() if at is None else at
        return self

    @property
    def duration(self) -> Optional[float]:
        return None if self.end is None else self.end - self.start

    def to_dict(self) -> dict:
        return {
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "name": self.name,
            "start": self.start,
            "end": self.end,
            "attributes": dict(self.attributes),
            "events": [ev.to_dict() for ev in self.events],
        }


class Trace:
    """A tree of spans for one request; ``spans[0]`` is the root.

    ``finish`` is first-wins: a trace shed by the scheduler keeps its
    ``shed_expired`` status even if a racing success path also tries
    to close it. Finishing notifies the owning tracer exactly once, so
    ``Tracer.drain`` sees each trace one time.
    """

    def __init__(self, trace_id: str, name: str, clock: Clock,
                 tracer: Optional["Tracer"] = None,
                 attributes: Optional[Dict[str, object]] = None):
        self.trace_id = trace_id
        self.clock = clock
        self.tracer = tracer
        self.status: Optional[str] = None
        self.spans: List[Span] = []
        self._lock = threading.Lock()
        self._ids = itertools.count()
        self._root = self._new_span(name, parent_id=None, start=None,
                                    attributes=dict(attributes or {}))

    def _new_span(self, name: str, parent_id: Optional[int],
                  start: Optional[float],
                  attributes: Dict[str, object]) -> Span:
        with self._lock:
            span = Span(self, next(self._ids), parent_id, name,
                        self.clock.now() if start is None else start,
                        attributes)
            self.spans.append(span)
        return span

    @property
    def root(self) -> Span:
        return self._root

    def span(self, name: str, *, parent: Optional[Span] = None,
             start: Optional[float] = None, **attrs: object) -> Span:
        """Open a child span (of ``parent``, default the root)."""
        pid = (parent or self._root).span_id
        return self._new_span(name, parent_id=pid, start=start,
                              attributes=dict(attrs))

    def find(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    @property
    def done(self) -> bool:
        return self.status is not None

    def finish(self, status: str = "ok", at: Optional[float] = None,
               **attrs: object) -> "Trace":
        with self._lock:
            if self.status is not None:
                return self
            self.status = status
        if attrs:
            self._root.set(**attrs)
        self._root.set(status=status)
        self._root.finish(at=at)
        if self.tracer is not None:
            self.tracer._complete(self)
        return self

    def __enter__(self) -> "Trace":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.finish(status="ok" if exc_type is None
                    else f"error:{exc_type.__name__}")

    def to_dict(self) -> dict:
        return {
            "trace_id": self.trace_id,
            "name": self._root.name,
            "status": self.status,
            "spans": [s.to_dict() for s in self.spans],
        }


class Tracer:
    """Factory + bounded buffer of completed traces.

    Hand one to ``ServeRuntime`` / ``FleetRuntime`` (``tracer=``) and
    every request yields a complete trace; call :meth:`drain` to pull
    finished traces for export. The buffer is a deque capped at
    ``max_traces`` (oldest evicted first) so an un-drained tracer in a
    long-lived server never grows without bound.
    """

    def __init__(self, clock: Optional[Clock] = None,
                 max_traces: int = 4096, ledger_events: bool = True):
        self.clock: Clock = clock if clock is not None else RealClock()
        self.max_traces = int(max_traces)
        self._completed: deque = deque(maxlen=self.max_traces)
        self._lock = threading.Lock()
        self._ids = itertools.count()
        self.started = 0
        self.completed = 0
        if ledger_events:
            install_ledger_listener()

    def trace(self, name: str, **attrs: object) -> Trace:
        with self._lock:
            tid = f"t{next(self._ids):06d}"
            self.started += 1
        return Trace(tid, name, self.clock, tracer=self, attributes=attrs)

    def _complete(self, trace: Trace) -> None:
        with self._lock:
            self._completed.append(trace)
            self.completed += 1

    def drain(self) -> List[Trace]:
        """Pop and return all completed traces (oldest first)."""
        with self._lock:
            out = list(self._completed)
            self._completed.clear()
        return out

    def __len__(self) -> int:
        return len(self._completed)


# --------------------------------------------------------------------------
# Thread-local current spans: let deep call sites (the span primitive,
# exec.dispatch, the ledger) attach children/events without threading a
# trace through every signature.  Each stack entry is a tuple of spans:
# one per request on the submitting thread, one per traced request of
# the batch on the worker, so a batch's stages land in every trace.

_tls = threading.local()


def _stack() -> list:
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = []
        _tls.stack = stack
    return stack


def _pop(entry: tuple) -> None:
    stack = _stack()
    if stack and stack[-1] is entry:
        stack.pop()
    elif entry in stack:  # pragma: no cover - unbalanced exit
        stack.remove(entry)


def current_spans() -> Tuple[Span, ...]:
    stack = _stack()
    return stack[-1] if stack else ()


def current_span() -> Optional[Span]:
    spans = current_spans()
    return spans[0] if spans else None


@contextlib.contextmanager
def use_spans(spans: Sequence[Span]):
    """Make ``spans`` the thread's current spans for the duration (no-op
    when empty): :func:`span` then opens a child under each of them."""
    entry = tuple(spans)
    if not entry:
        yield entry
        return
    _stack().append(entry)
    try:
        yield entry
    finally:
        _pop(entry)


@contextlib.contextmanager
def use_span(span: Span):
    """Make ``span`` the thread's current span for the duration."""
    with use_spans((span,)):
        yield span


_TraceAnnotation = None


def _annotation_cls():
    global _TraceAnnotation
    if _TraceAnnotation is None:
        from jax.profiler import TraceAnnotation

        _TraceAnnotation = TraceAnnotation
    return _TraceAnnotation


class span:
    """``with span("<layer>.<stage>", metrics=None) as s:`` — one layer
    boundary, written to the profiler, the registry and the current
    traces (module docstring).  ``s.set(**attrs)`` stamps attributes on
    the trace children it opened.  Counters and child spans are written
    whether the body returns or raises."""

    __slots__ = ("name", "metrics", "_ann", "_children", "_t0", "_c0")

    def __init__(self, name: str, metrics=None):
        self.name = name
        self.metrics = metrics

    def __enter__(self) -> "span":
        cls = _TraceAnnotation or _annotation_cls()
        self._ann = None
        if cls.is_enabled():          # a profile is being taken
            self._ann = cls("repro." + self.name)
            self._ann.__enter__()
        parents = current_spans()
        children = ()
        if parents:
            stage = self.name.rpartition(".")[2]
            children = tuple(p.trace.span(stage, parent=p) for p in parents)
            _stack().append(children)
        self._children = children
        if self.metrics is not None:
            self._c0 = time.thread_time_ns()
            self._t0 = time.perf_counter_ns()
        return self

    def set(self, **attrs: object) -> "span":
        for child in self._children:
            child.set(**attrs)
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self.metrics is not None:
            wall = time.perf_counter_ns() - self._t0
            cpu = time.thread_time_ns() - self._c0
            stage = self.name.rpartition(".")[2]
            self.metrics.inc_many({f"{stage}_n": 1, f"{stage}_ns": wall,
                                   f"{stage}_cpu_ns": cpu})
        if self._children:
            _pop(self._children)
            for child in self._children:
                child.finish()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)


# --------------------------------------------------------------------------
# Plan / engine introspection helpers shared by ServeRuntime and
# FleetRuntime instrumentation.


def plan_attributes(plan, **extra: object) -> Dict[str, object]:
    """The span attributes for one ``SpmmPlan`` (duck-typed)."""
    attrs: Dict[str, object] = {
        "impl": getattr(plan, "effective_impl", None)
        or getattr(plan, "impl", "?"),
        "precision": getattr(plan, "precision", "f32"),
        "mesh_width": int(getattr(plan, "n_shards", 1) or 1),
        "block_rows": getattr(plan, "block_rows", None),
        "block_k": getattr(plan, "block_k", None),
        "block_f": getattr(plan, "block_f", None),
    }
    attrs.update(extra)
    return attrs


def engine_batch_info(engine, bucket) -> dict:
    """Describe how ``engine`` serves ``bucket``: keys + plan attrs.

    Returns the dict ``RuntimeLoop`` consumes: ``bucket_key`` /
    ``plan_key`` (the :mod:`repro.obs.feedback` identities measured
    latency is filed under), ``attrs`` for the execute span, and one
    attribute dict per layer (the execute span's ``layers``). Plans
    are read from the batcher's caches, so this reflects the plans the
    compiled executable was actually built from.
    """
    import dataclasses as _dc

    from repro.obs.feedback import bucket_key as _bucket_key
    from repro.obs.feedback import plan_key_from_plan

    feature_dim = int(engine.features.shape[1])
    batcher = engine.batcher
    precision = batcher.precision_for_bucket(bucket)
    plan = batcher.plan_for_bucket(bucket, feature_dim)
    layer_plans = batcher.layer_plans_for_bucket(bucket, feature_dim)
    if precision != "f32":
        plan = _dc.replace(plan, precision=precision)
        layer_plans = [_dc.replace(p, precision=precision)
                       for p in layer_plans]
    return {
        "bucket_key": _bucket_key(bucket, feature_dim),
        "plan_key": plan_key_from_plan(plan),
        "attrs": plan_attributes(plan),
        "layers": [plan_attributes(p) for p in layer_plans],
    }


# --------------------------------------------------------------------------
# CollectiveLedger adoption: every LEDGER.record while a span is
# active becomes a span event, so modeled DRAM/collective bytes are
# attributed per request/layer.

_ledger_installed = False
_install_lock = threading.Lock()


def _on_ledger_record(kind: str, nbytes: float, n: int) -> None:
    span = current_span()
    if span is not None:
        span.event("ledger", kind=kind, bytes=float(nbytes), n=int(n))


def install_ledger_listener() -> bool:
    """Route ``LEDGER.record`` calls to the active span (idempotent)."""
    global _ledger_installed
    with _install_lock:
        if _ledger_installed:
            return False
        from repro.dist.collectives import LEDGER

        LEDGER.listeners.append(_on_ledger_record)
        _ledger_installed = True
        return True
