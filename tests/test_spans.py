"""Tests for the span primitive ``repro.obs.trace.span`` and the spans at
the serving path's layer boundaries.

One ``with span("<layer>.<stage>")`` writes a profiler annotation
``repro.<layer>.<stage>``, the registry counters ``<stage>_n``,
``<stage>_ns`` and ``<stage>_cpu_ns`` when a registry is passed, and a
child span in every request trace current on the thread.  Trace edges
run on the runtime clock, so the virtual-clock tests here assert them
exactly; the profiler test reads a real CPU trace back.
"""

import glob
import os
import time

import numpy as np
import pytest

from repro.obs import Tracer, use_span
from repro.obs.trace import current_spans, span, use_spans
from repro.runtime import MetricsRegistry, VirtualClock
from repro.runtime.metrics import COUNTERS


@pytest.fixture(autouse=True)
def _isolated_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_CACHE", str(tmp_path / "cache"))


# ---------------------------------------------------------------------------
# the primitive
# ---------------------------------------------------------------------------


def _burn(seconds):
    t_end = time.perf_counter() + seconds
    x = 0
    while time.perf_counter() < t_end:
        x += 1
    return x


def test_span_writes_counters_and_cpu_is_at_most_wall():
    reg = MetricsRegistry()
    for _ in range(3):
        with span("engine.prepare", metrics=reg):
            _burn(0.002)
    with span("engine.prepare", metrics=reg):
        time.sleep(0.02)            # waiting: wall, not CPU
    c = reg.snapshot()["counters"]
    assert c["prepare_n"] == 4
    assert c["prepare_ns"] >= 4 * 2_000_000
    # thread CPU time never exceeds wall time (1 ms for clock granularity)
    assert 0 <= c["prepare_cpu_ns"] <= c["prepare_ns"] + 1_000_000
    # the sleep shows up as wall time the thread did not spend on a CPU
    assert c["prepare_ns"] - c["prepare_cpu_ns"] >= 10_000_000
    assert isinstance(c["prepare_ns"], int)


def test_span_counts_a_body_that_raises():
    reg = MetricsRegistry()
    with pytest.raises(ValueError):
        with span("runtime.execute", metrics=reg):
            raise ValueError("boom")
    assert reg.count("execute_n") == 1
    assert current_spans() == ()


def test_span_without_registry_or_trace_writes_nothing():
    reg = MetricsRegistry()
    before = reg.snapshot()["counters"]
    with span("sampler.sample") as s:
        s.set(ignored=True)          # no trace current: a no-op
    assert reg.snapshot()["counters"] == before
    assert current_spans() == ()


def test_span_counters_are_in_every_snapshot():
    snap = MetricsRegistry().snapshot()["counters"]
    for stage in ("prepare", "execute"):
        for suffix in ("n", "ns", "cpu_ns"):
            name = f"{stage}_{suffix}"
            assert name in COUNTERS and snap[name] == 0


def test_span_nests_children_under_the_current_trace():
    clock = VirtualClock(start=5.0)
    trace = Tracer(clock=clock).trace("request")
    with use_span(trace.root):
        with span("engine.prepare") as prep:
            prep.set(bucket="b")
            clock.advance(1.0)
            with span("sampler.sample"):
                clock.advance(0.5)
            with span("batcher.pad"):
                clock.advance(0.25)
    [p] = trace.find("prepare")
    [s] = trace.find("sample")
    [b] = trace.find("pad")
    assert (p.start, p.end, p.attributes["bucket"]) == (5.0, 6.75, "b")
    assert (s.start, s.end, s.parent_id) == (6.0, 6.5, p.span_id)
    assert (b.start, b.end, b.parent_id) == (6.5, 6.75, p.span_id)
    assert p.parent_id == trace.root.span_id


def test_span_fans_out_to_every_current_trace():
    """On the worker one batch serves several traced requests: a stage
    opens a child in each of their traces."""
    clock = VirtualClock()
    tracer = Tracer(clock=clock)
    traces = [tracer.trace("request") for _ in range(3)]
    with use_spans([t.root for t in traces]):
        with span("runtime.execute") as ex:
            ex.set(batch_size=3)
            with span("batcher.dispatch"):
                clock.advance(2.0)
    for t in traces:
        [e] = t.find("execute")
        [d] = t.find("dispatch")
        assert e.attributes["batch_size"] == 3
        assert d.parent_id == e.span_id and (d.start, d.end) == (0.0, 2.0)
    assert current_spans() == ()


# ---------------------------------------------------------------------------
# the serving path
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def toy_parts():
    from repro.graphs.datasets import (
        DatasetSpec,
        gcn_normalize,
        synthesize_adjacency,
    )

    spec = DatasetSpec("toy", nodes=400, edges=1_600, feature_dim=32,
                       classes=5)
    adj_norm = gcn_normalize(synthesize_adjacency(spec, seed=7))
    feats = np.random.default_rng(7).standard_normal(
        (spec.nodes, spec.feature_dim)).astype(np.float32)
    return spec, adj_norm, feats


def _engine(toy_parts, **kw):
    from repro.models.gcn import GCNConfig
    from repro.serve import ServeEngine

    spec, adj_norm, feats = toy_parts
    impl = kw.pop("spmm_impl", "reference")
    cfg = GCNConfig(in_dim=spec.feature_dim, hidden_dim=8,
                    out_dim=spec.classes, spmm_impl=impl)
    base = dict(fanout=4, max_seeds=4, max_batch=4, base_bucket_nodes=64)
    base.update(kw)
    return ServeEngine(adj_norm, feats, cfg, **base)


def _drive(rt, rounds=64):
    for _ in range(rounds):
        rt.loop.step()
        nxt = rt.scheduler.next_close_time()
        if nxt is None:
            break
        if nxt > rt.clock.now():
            rt.clock.set_time(nxt)
    rt.loop.drain()


def test_runtime_counts_prepares_and_executed_batches(toy_parts):
    """Served in interpret mode (the Pallas kernels on the CPU): one
    prepare per request, one execute per closed batch."""
    engine = _engine(toy_parts, spmm_impl="pallas", interpret=True)
    engine.warmup()
    rt = engine.runtime(capacity=64, clock=VirtualClock(start=1.0))
    rng = np.random.default_rng(3)
    n = 7
    reqs = [rt.submit(rng.choice(400, size=2, replace=False))
            for _ in range(n)]
    _drive(rt)
    for r in reqs:
        r.future.result(timeout=0)
    c = rt.metrics.snapshot()["counters"]
    closed = c["batches_full"] + c["batches_deadline"] + c["batches_flush"]
    assert c["prepare_n"] == n
    assert c["execute_n"] == closed >= 1
    assert c["completed"] == n
    assert 0 < c["prepare_cpu_ns"] <= c["prepare_ns"] + 1_000_000 * n
    assert 0 < c["execute_cpu_ns"] <= c["execute_ns"] + 1_000_000 * closed
    rt.shutdown()


class _TickClock(VirtualClock):
    """A virtual clock that moves 1/1024 s forward on every read, so each
    span edge is a distinct, exactly representable instant."""

    def now(self) -> float:
        t = super().now()
        self.advance(1.0 / 1024)
        return t


def test_trace_edges_are_ordered_exactly_under_a_ticking_clock(toy_parts):
    engine = _engine(toy_parts)
    engine.warmup()
    clock = _TickClock(start=50.0)
    tracer = Tracer(clock=clock)
    rt = engine.runtime(capacity=64, clock=clock, tracer=tracer)
    rng = np.random.default_rng(5)
    reqs = [rt.submit(rng.choice(400, size=2, replace=False))
            for _ in range(5)]
    _drive(rt)
    for r in reqs:
        r.future.result(timeout=0)
    traces = tracer.drain()
    assert len(traces) == len(reqs)
    for r, trace in zip(reqs, traces):
        [prep] = trace.find("prepare")
        stages = [trace.find(n)[0]
                  for n in ("sample", "induce", "build", "pad")]
        edges = [prep.start]
        for st in stages:
            assert st.parent_id == prep.span_id
            assert st.start < st.end
            edges += [st.start, st.end]
        edges.append(prep.end)
        assert edges == sorted(edges) and len(set(edges)) == len(edges)
        # prep_s is the submit-side interval around the prepare span
        assert prep.end - prep.start <= r.prep_s
        [qw] = trace.find("queue_wait")
        [close] = trace.find("close")
        [ex] = trace.find("execute")
        assert prep.end < r.arrival == qw.start
        assert close.start <= qw.end <= close.end < ex.start < ex.end
        kids = [s for s in trace.spans if s.parent_id == ex.span_id]
        assert [s.name for s in kids] == ["stack", "dispatch", "fetch"]
        inner = [ex.start]
        for s in kids:
            inner += [s.start, s.end]
        inner.append(ex.end)
        assert inner == sorted(inner) and len(set(inner)) == len(inner)
        assert ex.end < trace.root.end
        # the execute span is the runner interval the request reports
        assert ex.end - ex.start <= r.exec_s
    rt.shutdown()


def test_profiler_trace_holds_full_forward_with_dispatch_and_fetch(
        toy_parts, tmp_path):
    import jax
    from jax.profiler import ProfileData

    engine = _engine(toy_parts)
    engine.full_forward()                     # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        engine.full_forward()
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    spans = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro.engine."):
                    spans.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    [(f0, f1)] = spans["repro.engine.full_forward"]
    [(d0, d1)] = spans["repro.engine.dispatch"]
    [(g0, g1)] = spans["repro.engine.fetch"]
    assert f0 <= d0 <= d1 <= g0 <= g1 <= f1


def test_profiler_trace_holds_the_graph_set_up_stages(toy_parts, tmp_path):
    """Building an engine's full graph writes ``registry.preprocess`` over
    its three stages, then ``registry.plan_grid`` and ``registry.upload``
    for the step's device operands."""
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        _engine(toy_parts, spmm_impl="pallas_sparse", interpret=True)
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    spans = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("repro.registry.",
                                       "repro.preprocess.")):
                    spans.setdefault(ev.name[len("repro."):], []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    [(p0, p1)] = spans["registry.preprocess"]
    for stage in ("edge_cut", "vertex_cut", "ell"):
        [(s0, s1)] = spans[f"preprocess.{stage}"]
        assert p0 <= s0 <= s1 <= p1
    [(g0, g1)] = spans["registry.plan_grid"]
    [(u0, u1)] = spans["registry.upload"]
    assert p1 <= g0 <= g1 <= u0 <= u1
