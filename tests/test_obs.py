"""Tests for repro.obs: end-to-end tracing, telemetry export, and the
measured-latency feedback loop into the planner.

Trace assertions run under the virtual clock, so span edges are exact —
no sleeps, no tolerance windows.  The feedback tests prove the ROADMAP
item 5 loop both ways: injected measurements that contradict the cost
model provably change ``choose_plan``'s pick, and an injected
measurement favouring the static default provably keeps it (the
never-worse invariant, in measured terms).
"""

import json
import os

import numpy as np
import pytest

from repro.obs import (
    PlanFeedback,
    Tracer,
    bucket_key,
    plan_key,
    render_prometheus,
    render_traces_json,
    use_span,
    write_metrics_json,
    write_prometheus,
    write_traces_json,
)
from repro.obs.feedback import default_path, plan_key_from_plan
from repro.runtime import (
    BatchScheduler,
    FixedEstimator,
    MetricsRegistry,
    QueueFullError,
    Request,
    RequestQueue,
    VirtualClock,
    labeled,
    parse_labeled,
)
from repro.serve.batcher import Bucket

B64 = Bucket(nodes=64, rows=128)


# ---------------------------------------------------------------------------
# labeled(): escaping regression + parse round-trip
# ---------------------------------------------------------------------------


def test_labeled_values_with_separators_do_not_collide():
    """Regression: label values containing ``,``/``=`` used to collapse
    distinct (name, labels) pairs onto one registry key."""
    a = labeled("completed", tenant="a,b=c")
    b = labeled("completed", tenant="a", b="c")
    assert a != b
    reg = MetricsRegistry()
    reg.inc(a)
    reg.inc(b)
    snap = reg.snapshot()["counters"]
    assert snap[a] == 1 and snap[b] == 1


@pytest.mark.parametrize("labels", [
    {},
    {"tenant": "cold"},
    {"tenant": "a,b", "servable": "x=y"},
    {"k": "br{ace}s"},
    {"k": "back\\slash", "j": "plain"},
])
def test_parse_labeled_round_trips(labels):
    key = labeled("metric_name", **labels)
    name, parsed = parse_labeled(key)
    assert name == "metric_name"
    assert parsed == labels


def test_parse_labeled_plain_key():
    assert parse_labeled("completed") == ("completed", {})


# ---------------------------------------------------------------------------
# trace primitives
# ---------------------------------------------------------------------------


def test_trace_span_tree_and_idempotent_finish():
    clock = VirtualClock(start=5.0)
    tracer = Tracer(clock=clock)
    trace = tracer.trace("request", graph_key="g")
    assert trace.trace_id == "t000000"
    child = trace.span("prepare", start=5.0)
    clock.advance(1.0)
    child.finish()
    child.finish(at=99.0)                 # idempotent: first wins
    assert child.end == 6.0 and child.duration == 1.0
    assert child.parent_id == trace.root.span_id
    trace.finish(status="ok", at=6.0)
    trace.finish(status="failed", at=7.0)  # first-wins status
    assert trace.status == "ok" and trace.root.end == 6.0
    [drained] = tracer.drain()
    assert drained is trace
    assert tracer.drain() == []            # drained exactly once
    d = trace.to_dict()
    assert d["status"] == "ok"
    assert [s["name"] for s in d["spans"]] == ["request", "prepare"]


def test_tracer_buffer_is_bounded():
    clock = VirtualClock()
    tracer = Tracer(clock=clock, max_traces=3)
    for i in range(5):
        tracer.trace("request", i=i).finish()
    drained = tracer.drain()
    assert len(drained) == 3               # oldest two evicted
    assert [t.root.attributes["i"] for t in drained] == [2, 3, 4]
    assert tracer.started == 5 and tracer.completed == 5


# ---------------------------------------------------------------------------
# queue/scheduler-level trace statuses (virtual clock, no engine)
# ---------------------------------------------------------------------------


def _traced_rig(*, capacity=8, est=0.25, max_batch=4):
    clock = VirtualClock()
    tracer = Tracer(clock=clock)
    queue = RequestQueue(capacity=capacity, clock=clock,
                         estimator=FixedEstimator(est))
    sched = BatchScheduler(queue, max_batch=max_batch, max_wait_s=None)
    return clock, tracer, queue, sched


def _traced_req(tracer, *, deadline=None, bucket=B64):
    trace = tracer.trace("request", graph_key="g")
    return Request(graph_key="g", seeds=(0,), deadline=deadline,
                   bucket=bucket, padded=object(), trace=trace)


def test_admission_span_and_rejection_status():
    clock, tracer, queue, _ = _traced_rig(capacity=1)
    ok = _traced_req(tracer)
    queue.submit(ok)
    [adm] = ok.trace.find("admission")
    assert adm.attributes["verdict"] == "admitted"
    assert adm.start == adm.end == clock.now()
    assert not ok.trace.done               # still in flight

    victim = _traced_req(tracer)
    with pytest.raises(QueueFullError):
        queue.submit(victim)
    assert victim.trace.status == "rejected_queue_full"
    [vadm] = victim.trace.find("admission")
    assert vadm.attributes["verdict"] == "rejected_queue_full"
    [done] = tracer.drain()
    assert done is victim.trace


def test_shed_expired_trace():
    clock, tracer, queue, sched = _traced_rig(est=0.25)
    req = _traced_req(tracer, deadline=clock.now() + 1.0)
    queue.submit(req)
    clock.advance(2.0)                     # deadline now unmeetable
    sched.poll()
    assert req.trace.status == "shed_expired"
    [qw] = req.trace.find("queue_wait")
    assert qw.attributes["close_reason"] == "shed_expired"
    assert qw.start == req.arrival and qw.end == clock.now()


def test_cancelled_trace():
    clock, tracer, queue, _ = _traced_rig()
    req = _traced_req(tracer)
    queue.submit(req)
    assert queue.cancel(req)
    assert req.trace.status == "cancelled"


# ---------------------------------------------------------------------------
# full serving vertical (toy engine, virtual clock): complete traces
# ---------------------------------------------------------------------------


@pytest.fixture(autouse=True)
def _isolated_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_CACHE", str(tmp_path / "cache"))


@pytest.fixture(scope="module")
def toy_engine_parts():
    from repro.graphs.datasets import (
        DatasetSpec,
        gcn_normalize,
        synthesize_adjacency,
    )

    spec = DatasetSpec("toy", nodes=400, edges=1_600, feature_dim=32,
                       classes=5)
    adj_norm = gcn_normalize(synthesize_adjacency(spec, seed=7))
    rng = np.random.default_rng(7)
    feats = rng.standard_normal(
        (spec.nodes, spec.feature_dim)).astype(np.float32)
    return spec, adj_norm, feats


def _toy_engine(toy_engine_parts, **kw):
    from repro.models.gcn import GCNConfig
    from repro.serve import ServeEngine

    spec, adj_norm, feats = toy_engine_parts
    cfg = GCNConfig(in_dim=spec.feature_dim, hidden_dim=8,
                    out_dim=spec.classes)
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


def test_serve_runtime_yields_complete_traces(toy_engine_parts):
    """Every request through ServeRuntime yields one trace covering the
    whole vertical — prepare (sample, induce, build, pad), admission,
    queue wait, close, execute with plan attrs (stack, dispatch, fetch)
    — with exact virtual-clock span edges."""
    engine = _toy_engine(toy_engine_parts)
    engine.warmup()
    clock = VirtualClock(start=100.0)
    tracer = Tracer(clock=clock)
    rt = engine.runtime(capacity=64, clock=clock, tracer=tracer)
    rng = np.random.default_rng(11)
    reqs = [rt.submit(rng.choice(400, size=2, replace=False),
                      deadline_s=1.0) for _ in range(6)]
    _drive(rt)
    for r in reqs:
        r.future.result(timeout=0)

    traces = tracer.drain()
    assert len(traces) == len(reqs)
    fdim = int(engine.features.shape[1])
    for r, trace in zip(reqs, traces):
        assert trace.status == "ok"
        assert trace.root.attributes["slo"] == "slo_met"
        names = [s.name for s in trace.spans]
        for expected in ("request", "prepare", "admission", "queue_wait",
                         "close", "execute"):
            assert expected in names, f"missing {expected} in {names}"

        [prep] = trace.find("prepare")
        assert prep.parent_id == trace.root.span_id
        assert prep.attributes["bucket"] == str(r.bucket)
        assert prep.start == prep.end == r.arrival
        for stage in ("sample", "induce", "build", "pad"):
            [st] = trace.find(stage)
            assert st.parent_id == prep.span_id
            assert st.start == st.end == prep.start

        [adm] = trace.find("admission")
        assert adm.attributes["verdict"] == "admitted"
        [qw] = trace.find("queue_wait")
        [close] = trace.find("close")
        [ex] = trace.find("execute")
        # exact virtual-clock edges: wait starts at arrival, ends at the
        # batch close instant, which is also when the (zero-duration
        # under a virtual clock) close and execute spans run.
        assert qw.start == r.arrival
        assert qw.end == close.start == close.end == ex.start == ex.end
        assert qw.attributes["close_reason"] in (
            "full", "deadline", "flush")
        assert close.attributes["close_reason"] == \
            qw.attributes["close_reason"]
        assert ex.parent_id == trace.root.span_id
        assert ex.attributes["bucket_key"] == bucket_key(r.bucket, fdim)
        assert ex.attributes["plan_key"]
        assert ex.attributes["impl"] == "reference"
        assert ex.attributes["precision"] == "f32"
        assert ex.attributes["mesh_width"] == 1
        # one plan-attribute dict per layer rides on the execute span
        layers = ex.attributes["layers"]
        assert len(layers) == engine.cfg.n_layers
        assert all(ls["impl"] == "reference" for ls in layers)
        # the batcher's stages nest under execute, in order
        stages = [s for s in trace.spans if s.parent_id == ex.span_id]
        assert [s.name for s in stages] == ["stack", "dispatch", "fetch"]
        assert all(s.start == s.end == ex.start for s in stages)
        # no fabricated per-layer spans or modeled-byte events
        assert not trace.find("execute_layer")
        assert not any(ev.name == "ledger" for s in trace.spans
                       for ev in s.events)
    rt.shutdown()


def test_untraced_serving_leaves_ledger_untouched(toy_engine_parts):
    """Without a tracer the runtime must not ledger batch traffic — the
    global LEDGER stays exactly as the eager paths left it."""
    from repro.dist.collectives import LEDGER

    engine = _toy_engine(toy_engine_parts)
    engine.warmup()
    rt = engine.runtime(capacity=16, clock=VirtualClock(start=10.0))
    before = dict(LEDGER.bytes)
    req = rt.submit([1, 2], deadline_s=1.0)
    _drive(rt)
    req.future.result(timeout=0)
    assert dict(LEDGER.bytes) == before
    rt.shutdown()


# ---------------------------------------------------------------------------
# fleet: traces, tenant attribution, per-method ACLs
# ---------------------------------------------------------------------------


def _fake_fleet(tracer=None, tenants=(), **kw):
    from repro.fleet import FleetManager, FleetRuntime, TenantTable
    from tests.test_fleet import FakeServable

    clock = VirtualClock()
    mgr = FleetManager(capacity_units=16.0)
    sv = FakeServable("gcn")
    mgr.register(sv)
    rt = FleetRuntime(mgr, tenants=TenantTable(tenants), clock=clock,
                      tracer=tracer, **kw)
    return clock, sv, rt


def test_fleet_trace_carries_tenant_and_servable():
    from repro.fleet import TenantPolicy

    tracer = Tracer(clock=VirtualClock())
    clock, _, rt = _fake_fleet(
        tracer=tracer, tenants=[TenantPolicy("hot", deadline_s=1.0)])
    tracer.clock = rt.clock
    req = rt.submit("gcn", [1, 2], tenant="hot")
    rt.drain()
    assert req.future.result(timeout=0) is not None
    [trace] = tracer.drain()
    assert trace.status == "ok"
    root = trace.root.attributes
    assert root["servable"] == "gcn" and root["tenant"] == "hot"
    assert root["priority"] == 0
    assert trace.find("admission") and trace.find("execute")


def test_fleet_acl_rejects_before_quota():
    """An ACL-denied call raises MethodDeniedError, counts rejected_acl
    (fleet-wide and per-tenant), finishes the trace with that status —
    and never burns a token from the tenant's bucket."""
    from repro.fleet import MethodDeniedError, TenantPolicy

    tracer = Tracer(clock=VirtualClock())
    clock, _, rt = _fake_fleet(
        tracer=tracer,
        tenants=[TenantPolicy("locked", qps=10.0, burst=2.0,
                              allowed_methods=("other",))])
    tracer.clock = rt.clock
    with pytest.raises(MethodDeniedError):
        rt.submit("gcn", [1], tenant="locked")
    m = rt.metrics
    assert m.count("rejected_acl") == 1
    assert m.count(labeled("rejected_acl", tenant="locked",
                           servable="gcn")) == 1
    assert m.count("submitted") == 1
    [trace] = tracer.drain()
    assert trace.status == "rejected_acl"
    # the denial happened before acquire: full token bucket, no inflight
    st = rt.tenants.state("locked")
    assert st["tokens"] == 2.0 and st["inflight"] == 0


def test_fleet_acl_allows_listed_methods_and_none_means_all():
    from repro.fleet import TenantPolicy, TenantTable

    table = TenantTable([TenantPolicy("a", allowed_methods=["gcn"])])
    table.check_method("a", "gcn")          # listed: fine
    table.check_method("anon", "anything")  # default policy: all allowed
    with pytest.raises(Exception):
        table.check_method("a", "lm")
    # list input is normalised to a tuple (policy stays hashable)
    assert table.policy("a").allowed_methods == ("gcn",)


def test_fleet_from_config_parses_allowed_methods():
    from repro.fleet.tenancy import TenantPolicy

    pol = TenantPolicy(name="t", allowed_methods=["x", "y"])
    assert pol.allowed_methods == ("x", "y")
    empty = TenantPolicy(name="deny", allowed_methods=())
    assert empty.allowed_methods == ()


# ---------------------------------------------------------------------------
# straggler monitor gauges
# ---------------------------------------------------------------------------


def test_straggler_monitor_publishes_ewma_and_alive_gauges():
    from repro.dist.straggler import StragglerMonitor

    reg = MetricsRegistry()
    mon = StragglerMonitor(3, warn_factor=2.0, drop_factor=4.0,
                           patience=2, metrics=reg, ewma=0.5)
    mon.observe([1.0, 1.0, 1.0])
    g = reg.snapshot()["gauges"]
    assert g[labeled("straggler_step_ewma_s", replica="0")] == 1.0
    assert g[labeled("straggler_alive", replica="2")] == 1.0

    mon.observe([1.0, 1.0, 5.0])          # replica 2: 5x median, streak 1
    g = reg.snapshot()["gauges"]
    # first observation seeds the EWMA, the second folds at ewma=0.5
    assert g[labeled("straggler_step_ewma_s", replica="2")] == \
        pytest.approx(0.5 * 1.0 + 0.5 * 5.0)
    assert g[labeled("straggler_alive", replica="2")] == 1.0

    mon.observe([1.0, 1.0, 5.0])          # streak 2 -> dropped
    g = reg.snapshot()["gauges"]
    assert g[labeled("straggler_alive", replica="2")] == 0.0
    assert g[labeled("straggler_alive", replica="0")] == 1.0
    np.testing.assert_array_equal(mon.alive(), [1.0, 1.0, 0.0])


# ---------------------------------------------------------------------------
# PlanFeedback: EWMA math, persistence, trace ingestion
# ---------------------------------------------------------------------------


def test_plan_feedback_ewma_and_batch_normalisation():
    fb = PlanFeedback(ewma=0.5)
    k = plan_key("reference", 128, 128, 128)
    assert fb.measured("b", k) is None
    fb.record("b", k, seconds=0.8, batch=4)     # 0.2 per operand
    assert fb.measured("b", k) == pytest.approx(0.2)
    fb.record("b", k, seconds=0.4, batch=1)
    assert fb.measured("b", k) == pytest.approx(0.5 * 0.2 + 0.5 * 0.4)
    assert len(fb) == 1 and fb.has_bucket("b") and not fb.has_bucket("x")


def test_plan_feedback_save_load_round_trip(tmp_path):
    fb = PlanFeedback(ewma=0.4)
    fb.record("b1", "p1", 0.5)
    fb.record("b1", "p2", 0.25)
    fb.record("b2", "p1", 0.125)
    path = str(tmp_path / "fb.json")
    assert fb.save(path) == path
    back = PlanFeedback.load(path)
    assert back.ewma == 0.4
    assert back.entries() == fb.entries()
    assert len(back) == 3


def test_plan_feedback_loads_a_store_of_older_keys(tmp_path):
    """A store whose plan keys carry a trailing segment no longer written
    (``.../f32/unfused``) loads whole, and its entries simply miss."""
    path = str(tmp_path / "old.json")
    with open(path, "w") as f:
        json.dump({"version": 1, "ewma": 0.3, "entries": {
            "b1": {"reference/r128.k128.f128/w1/f32/unfused":
                   {"seconds": 0.5, "count": 2}}}}, f)
    fb = PlanFeedback.load(path)
    assert len(fb) == 1 and fb.has_bucket("b1")
    key = plan_key("reference", 128, 128, 128, 1, "f32")
    assert key == "reference/r128.k128.f128/w1/f32"
    assert fb.measured("b1", key) is None
    assert not os.path.exists(path + ".corrupt")


def test_plan_feedback_load_missing_and_corrupt(tmp_path):
    missing = str(tmp_path / "nope.json")
    assert len(PlanFeedback.load(missing)) == 0

    corrupt = str(tmp_path / "bad.json")
    with open(corrupt, "w") as f:
        f.write('{"version": 1, "entries": [not json')
    fb = PlanFeedback.load(corrupt)
    assert len(fb) == 0
    assert os.path.exists(corrupt + ".corrupt")
    assert not os.path.exists(corrupt)


def test_plan_feedback_default_path_tracks_bench_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_BENCH_DIR", str(tmp_path))
    assert default_path() == str(tmp_path / "PLAN_FEEDBACK.json")
    fb = PlanFeedback()
    fb.record("b", "p", 0.1)
    fb.save()
    assert len(PlanFeedback.load()) == 1


def test_plan_feedback_ingests_drained_traces():
    clock = VirtualClock(start=0.0)
    tracer = Tracer(clock=clock)
    trace = tracer.trace("request")
    ex = trace.span("execute", start=0.0, bucket_key="bk", plan_key="pk",
                    padded_batch=2)
    ex.finish(at=0.4)
    trace.span("execute", start=0.0)      # no identity attrs: skipped
    trace.span("prepare", start=0.0).finish(at=0.1)
    trace.finish()
    fb = PlanFeedback()
    assert fb.ingest(tracer.drain()) == 1
    assert fb.measured("bk", "pk") == pytest.approx(0.2)  # 0.4 s / batch 2


# ---------------------------------------------------------------------------
# feedback -> choose_plan: measurements beat the model, never-worse holds
# ---------------------------------------------------------------------------


def _choose(feedback=None):
    from repro.plan.autoplan import choose_plan
    from repro.plan.cost import synthetic_stats

    stats = synthetic_stats(rows=512, n_out_rows=256, n_dense_rows=256,
                            nnz=2048, tau=8)
    return choose_plan(
        stats, 64,
        impls=("reference",),
        block_candidates=(64, 128),
        widths=(1,),
        schedulable=False,
        feedback=feedback,
        feedback_key="bkt" if feedback is not None else None,
    )


def test_measured_latency_overrides_model_choice():
    """Injected measurements contradicting the model change the pick:
    the modeled winner gets a slow measurement, a modeled loser a fast
    one — choose_plan must follow the measurements."""
    baseline = _choose()
    base_key = plan_key_from_plan(baseline.plan)
    assert baseline.measured_used == 0

    # pick any other enumerated candidate as the measured winner
    rival = ("reference", 64, 64, 64)
    rival_key = plan_key(*rival, 1, "f32")
    assert rival_key != base_key

    fb = PlanFeedback()
    fb.record("bkt", base_key, seconds=1.0)       # measured: slow
    fb.record("bkt", rival_key, seconds=1e-12)    # measured: fast
    steered = _choose(feedback=fb)
    assert plan_key_from_plan(steered.plan) == rival_key
    assert steered.measured_used >= 2


def test_never_worse_than_static_holds_in_measured_terms():
    """A measurement saying the static default is fastest keeps the
    static default, whatever the model claims about other candidates."""
    from repro.plan.autoplan import choose_plan
    from repro.plan.cost import synthetic_stats

    stats = synthetic_stats(rows=512, n_out_rows=256, n_dense_rows=256,
                            nnz=2048, tau=8)
    static_key = plan_key("reference", 128, 128, 128, 1, "f32")
    fb = PlanFeedback()
    fb.record("bkt", static_key, seconds=1e-9)    # static: measured fastest
    choice = choose_plan(
        stats, 64, impls=("reference", "pallas"),
        block_candidates=(16, 64, 128), widths=(1,), schedulable=False,
        feedback=fb, feedback_key="bkt",
    )
    assert plan_key_from_plan(choice.plan) == static_key
    assert choice.measured_used >= 1


def test_serving_records_feedback_entries(toy_engine_parts):
    """The live loop: serving with a feedback store attached records one
    measured (bucket, plan) entry per executed batch."""
    engine = _toy_engine(toy_engine_parts)
    engine.warmup()
    fb = PlanFeedback()
    rt = engine.runtime(capacity=16, clock=VirtualClock(start=50.0),
                        feedback=fb)
    reqs = [rt.submit([i, i + 1], deadline_s=1.0) for i in range(4)]
    _drive(rt)
    for r in reqs:
        r.future.result(timeout=0)
    assert len(fb) >= 1
    fdim = int(engine.features.shape[1])
    bkey = bucket_key(reqs[0].bucket, fdim)
    assert fb.has_bucket(bkey)
    plans = fb.entries()[bkey]
    for entry in plans.values():
        assert entry["count"] >= 1 and entry["seconds"] >= 0.0
    rt.shutdown()


def test_feedback_informed_engine_pins_plans_at_warmup(toy_engine_parts):
    """An engine built over a feedback store with entries for a bucket
    serves that bucket with the feedback-informed plan, pinned at warmup
    (zero post-warmup recompiles still holds)."""
    engine = _toy_engine(toy_engine_parts, autoplan=True)
    fdim = int(engine.features.shape[1])
    probe = engine._prepare([1, 2])
    bkey = bucket_key(probe.bucket, fdim)

    fb = PlanFeedback()
    ref_key = plan_key("reference", engine.cfg.block_rows,
                       engine.cfg.block_k, engine.cfg.block_f)
    fb.record(bkey, ref_key, seconds=1e-9)
    engine2 = _toy_engine(toy_engine_parts, autoplan=True, feedback=fb)
    plan = engine2.batcher.plan_for_bucket(probe.bucket, fdim)
    assert plan_key_from_plan(plan) == ref_key
    layer_plans = engine2.batcher.layer_plans_for_bucket(probe.bucket, fdim)
    assert len(layer_plans) == engine2.cfg.n_layers
    assert all(plan_key_from_plan(p) == ref_key for p in layer_plans)


# ---------------------------------------------------------------------------
# eager execute_layer spans (thread-local current span)
# ---------------------------------------------------------------------------


def test_eager_execute_layer_attaches_span_and_ledger_events():
    import jax.numpy as jnp

    from repro.core import preprocess, random_power_law_csr
    from repro.exec import SpmmOperands, SpmmPlan
    from repro.exec.dispatch import execute_layer

    adj = random_power_law_csr(48, 48, 300, seed=3)
    res = preprocess(adj, tau=4, tile_rows=16)
    ops = SpmmOperands.from_ell(res.ell)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((48, 8)), jnp.float32)
    layer = {
        "w": jnp.asarray(rng.standard_normal((8, 8)), jnp.float32),
        "b": jnp.zeros((8,), jnp.float32),
    }
    plan = SpmmPlan(impl="reference", block_rows=16, block_k=16, block_f=16)

    tracer = Tracer(clock=VirtualClock())
    trace = tracer.trace("eager")
    with use_span(trace.root):
        out = execute_layer(plan, ops, x, layer)
    assert out.shape == (48, 8)
    [ls] = trace.find("execute_layer")
    assert ls.end is not None
    assert ls.attributes["impl"] == "reference"
    assert ls.attributes["precision"] == "f32"
    kinds = {ev.attributes["kind"] for ev in ls.events
             if ev.name == "ledger"}
    assert "spmm_dram" in kinds and "combination_dram" in kinds

    # outside any span, the same call is uninstrumented (and still runs)
    out2 = execute_layer(plan, ops, x, layer)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))
    assert len(trace.find("execute_layer")) == 1


# ---------------------------------------------------------------------------
# exporters: JSON + Prometheus text format
# ---------------------------------------------------------------------------


def test_write_traces_json(tmp_path):
    clock = VirtualClock()
    tracer = Tracer(clock=clock)
    for _ in range(3):
        tracer.trace("request").finish()
    path = str(tmp_path / "traces.json")
    assert write_traces_json(path, tracer.drain()) == 3
    with open(path) as f:
        payload = json.load(f)
    assert len(payload["traces"]) == 3
    assert payload["traces"][0]["trace_id"] == "t000000"
    assert render_traces_json([]).startswith('{')


def test_prometheus_rendering(tmp_path):
    reg = MetricsRegistry()
    reg.inc("completed", 5)
    reg.inc(labeled("completed", tenant="cold", servable="a b"), 2)
    reg.set_gauge("queue_depth", 3)
    for v in (0.010, 0.020, 0.030):
        reg.observe("e2e_s", v)
    text = render_prometheus(reg)
    assert "# TYPE repro_completed counter" in text
    assert "repro_completed 5" in text
    assert 'repro_completed{servable="a b",tenant="cold"} 2' in text
    assert "# TYPE repro_queue_depth gauge" in text
    assert "repro_queue_depth 3" in text
    # histograms render as summaries with quantiles + _count + _sum
    assert 'repro_e2e_s_ms{quantile="0.5"} 20' in text
    assert "repro_e2e_s_ms_count 3" in text
    assert "# TYPE repro_shed_rate gauge" in text
    assert text.endswith("\n")

    path = str(tmp_path / "m.prom")
    assert write_prometheus(path, reg) == text
    json_path = str(tmp_path / "m.json")
    snap = write_metrics_json(json_path, reg)
    with open(json_path) as f:
        assert json.load(f)["counters"]["completed"] == 5
    assert snap["counters"]["completed"] == 5


def test_prometheus_escapes_label_values():
    reg = MetricsRegistry()
    reg.inc(labeled("completed", tenant='we"ird\\val'))
    text = render_prometheus(reg)
    assert 'tenant="we\\"ird\\\\val"' in text


# ---------------------------------------------------------------------------
# BENCH_summary.json: append-only log contract
# ---------------------------------------------------------------------------


def _summary_record(i=0, ok=True):
    return {"run_at": "2026-01-01T00:00:00", "bench": f"bench_{i}",
            "title": f"t{i}", "ok": ok, "seconds": 1.0, "summary": {}}


def test_bench_summary_appends_not_overwrites(tmp_path):
    from benchmarks.run import append_summary

    path = str(tmp_path / "BENCH_summary.json")
    append_summary([_summary_record(0)], path=path)
    append_summary([_summary_record(1), _summary_record(2)], path=path)
    with open(path) as f:
        rows = json.load(f)
    assert [r["bench"] for r in rows] == ["bench_0", "bench_1", "bench_2"]
    for r in rows:                         # schema every consumer greps on
        assert {"run_at", "bench", "ok", "seconds"} <= set(r)


def test_bench_summary_sidesteps_corrupt_file(tmp_path):
    from benchmarks.run import append_summary

    path = str(tmp_path / "BENCH_summary.json")
    with open(path, "w") as f:
        f.write('[{"bench": "old"}')       # truncated write: invalid JSON
    append_summary([_summary_record(7)], path=path)
    with open(path) as f:
        rows = json.load(f)
    assert [r["bench"] for r in rows] == ["bench_7"]
    # history preserved, not clobbered
    with open(path + ".corrupt") as f:
        assert f.read().startswith('[{"bench": "old"')


def test_bench_summary_rejects_non_list_root(tmp_path):
    from benchmarks.run import append_summary

    path = str(tmp_path / "BENCH_summary.json")
    with open(path, "w") as f:
        json.dump({"not": "a list"}, f)
    append_summary([_summary_record(1)], path=path)
    with open(path) as f:
        assert [r["bench"] for r in json.load(f)] == ["bench_1"]
    assert os.path.exists(path + ".corrupt")


def test_bench_metrics_export(tmp_path):
    from benchmarks.run import export_metrics

    reg = MetricsRegistry()
    reg.inc("bench_ok", 2)
    reg.observe(labeled("bench_s", bench="bench_plan"), 1.5)
    jp = str(tmp_path / "BENCH_metrics.json")
    pp = str(tmp_path / "BENCH_metrics.prom")
    export_metrics(reg, json_path=jp, prom_path=pp)
    with open(jp) as f:
        assert json.load(f)["counters"]["bench_ok"] == 2
    with open(pp) as f:
        text = f.read()
    assert "repro_bench_ok 2" in text
    assert 'bench="bench_plan"' in text
