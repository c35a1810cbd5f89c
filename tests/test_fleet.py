"""Tests for repro.fleet: multi-tenant servables behind one runtime.

Scheduling assertions run under the virtual clock with fake servables —
every close time, pick order, and shed verdict is exact.  Engine-level
tests prove the acceptance invariants on the real stack: a fleet holding
one GcnServable is bit-identical to ``ServeRuntime``, and a GCN + LM
fleet serves both model kinds through the one loop with zero
post-warmup compilations.
"""

import numpy as np
import pytest

from repro.fleet import (
    FleetBucket,
    FleetManager,
    FleetRuntime,
    GcnServable,
    InflightLimitError,
    QuotaExceededError,
    Servable,
    TenantPolicy,
    TenantTable,
)
from repro.runtime import UnknownServableError, VirtualClock, labeled
from repro.runtime.scheduler import BatchProfile, WeightedFairPicker


class FakeServable(Servable):
    """Deterministic scaffolding: echoes payloads, fixed cost estimate."""

    def __init__(self, key, *, est=0.01, max_batch=4, cost=1.0,
                 bucket="b0"):
        self.key = key
        self.bucket_name = bucket
        self.max_batch_ = max_batch
        self._cost = cost
        self.loads = 0
        self.unloads = 0
        self.ran = []       # batch sizes, in execution order

        class _Est:
            def estimate(self_, bucket_, batch=1):
                return est

            def observe(self_, *a):
                pass

        self._e = _Est()

    def load(self):
        self.loads += 1

    def unload(self):
        self.unloads += 1

    @property
    def estimator(self):
        return self._e

    def profile(self):
        sizes, b = [1], 1
        while b < self.max_batch_:
            b = min(b * 2, self.max_batch_)
            sizes.append(b)
        return BatchProfile(self.max_batch_, tuple(sizes))

    def cost_units(self):
        return self._cost

    def prepare(self, payload):
        class P:
            pass

        p = P()
        p.bucket = self.bucket_name
        p.payload = tuple(int(x) for x in payload)
        return p

    def run_batch(self, prepared):
        self.ran.append(len(prepared))
        return [np.asarray(p.payload, np.float32) for p in prepared]


def _fleet(*servables, tenants=(), capacity=64, weights=None,
           capacity_units=16.0):
    clock = VirtualClock()
    mgr = FleetManager(capacity_units=capacity_units)
    for sv in servables:
        mgr.register(sv)
    rt = FleetRuntime(mgr, tenants=TenantTable(tenants), clock=clock,
                      capacity=capacity, weights=weights)
    return clock, mgr, rt


# ---------------------------------------------------------------------------
# deterministic scheduling across servables (virtual clock)
# ---------------------------------------------------------------------------


def test_two_servables_close_deterministically():
    """Each servable's deadline trigger fires at its own
    ``deadline - est - margin`` — per-servable estimators inside one
    scheduler — and replaying the same submissions yields the same
    batches at the same instants."""

    def run_once():
        a, b = FakeServable("a", est=0.01), FakeServable("b", est=0.05)
        clock, _, rt = _fleet(a, b)
        rt.submit("a", [1], deadline_s=1.0)
        rt.submit("b", [2], deadline_s=1.0)
        events = []
        for _ in range(8):
            nxt = rt.scheduler.next_close_time()
            if nxt is None:
                break
            clock.set_time(max(nxt, clock.now()))
            for batch in rt.scheduler.poll():
                events.append((round(clock.now(), 6),
                               batch.bucket.servable,
                               len(batch.requests)))
                rt.loop.execute(batch)
        return events

    first = run_once()
    # b's bigger estimate fires its trigger first: 1.0 - 0.05 < 1.0 - 0.01
    assert first == [(0.95, "b", 1), (0.99, "a", 1)]
    assert run_once() == first


def test_fleet_buckets_never_mix_servables():
    a = FakeServable("a", bucket="same")
    b = FakeServable("b", bucket="same")   # identical inner bucket
    clock, _, rt = _fleet(a, b)
    rt.submit("a", [1])
    rt.submit("b", [2])
    assert len(rt.queue.groups()) == 2     # namespaced by servable
    rt.drain()
    assert a.ran == [1] and b.ran == [1]


def test_per_servable_profile_governs_full_close():
    a = FakeServable("a", max_batch=2)
    b = FakeServable("b", max_batch=4)
    clock, _, rt = _fleet(a, b)
    for i in range(2):
        rt.submit("a", [i])
        rt.submit("b", [i])
    closed = rt.scheduler.poll()
    # a reached ITS max_batch (2); b (max 4) is still coalescing
    assert [c.bucket.servable for c in closed] == ["a"]
    assert len(closed[0].requests) == 2


def test_weighted_fair_pick_interleaves_flows():
    picker = WeightedFairPicker(flow_of=lambda b: b, weights={"hot": 1.0,
                                                              "cold": 1.0})
    # 4 ready "hot" batches, 1 "cold": cold must not wait out all of hot.
    order = picker.order(["hot", "hot", "hot", "cold", "hot"])
    assert order.index("cold") <= 1
    # 2:1 weights over many rounds converge to the weight ratio
    picker = WeightedFairPicker(flow_of=lambda b: b[0],
                                weights={"h": 2.0, "c": 1.0})
    picks = picker.order([("h", i) for i in range(20)]
                         + [("c", i) for i in range(20)])
    first12 = [f for f, _ in picks[:12]]
    assert first12.count("h") == 8 and first12.count("c") == 4


# ---------------------------------------------------------------------------
# tenancy: quota / inflight shed accounting
# ---------------------------------------------------------------------------


def test_quota_sheds_with_exact_accounting():
    a = FakeServable("a")
    clock, _, rt = _fleet(
        a, tenants=[TenantPolicy("hot", qps=1.0, burst=2)])
    rt.submit("a", [0], tenant="hot")
    rt.submit("a", [1], tenant="hot")      # burst of 2 exhausted
    for _ in range(3):
        with pytest.raises(QuotaExceededError):
            rt.submit("a", [9], tenant="hot")
    m = rt.metrics
    assert m.count("rejected_quota") == 3
    assert m.count(labeled("rejected_quota", tenant="hot")) == 3
    assert m.count("submitted") == 5       # sheds count as offered
    # tokens refill at qps from the virtual clock: +1 token after 1s
    clock.advance(1.0)
    rt.submit("a", [2], tenant="hot")
    with pytest.raises(QuotaExceededError):
        rt.submit("a", [9], tenant="hot")
    assert m.count("rejected_quota") == 4
    # another tenant (and the anonymous flow) are untouched by hot's quota
    rt.submit("a", [3], tenant="other")
    rt.submit("a", [4])
    rt.drain()
    assert m.count("completed") == 5


def test_inflight_cap_sheds_and_releases_on_completion():
    a = FakeServable("a")
    clock, _, rt = _fleet(
        a, tenants=[TenantPolicy("t", max_inflight=2)])
    r1 = rt.submit("a", [0], tenant="t")
    rt.submit("a", [1], tenant="t")
    with pytest.raises(InflightLimitError):
        rt.submit("a", [2], tenant="t")
    m = rt.metrics
    assert m.count("rejected_inflight") == 1
    assert m.count(labeled("rejected_inflight", tenant="t")) == 1
    assert rt.tenants.state("t")["inflight"] == 2
    rt.drain()                              # resolves both futures
    assert r1.future.done()
    assert rt.tenants.state("t")["inflight"] == 0
    rt.submit("a", [3], tenant="t")         # slots returned
    assert m.count("rejected_inflight") == 1


def test_inflight_slot_returns_on_cancel_and_shed():
    a = FakeServable("a")
    clock, _, rt = _fleet(
        a, tenants=[TenantPolicy("t", max_inflight=1)])
    r = rt.submit("a", [0], tenant="t")
    assert rt.cancel(r)
    assert rt.tenants.state("t")["inflight"] == 0
    # queued-then-expired shed also releases (future gets the exception)
    r2 = rt.submit("a", [1], tenant="t", deadline_s=0.5)
    clock.advance(2.0)
    rt.scheduler.poll()
    assert r2.future.done()
    assert rt.tenants.state("t")["inflight"] == 0
    assert rt.metrics.count(labeled("shed_expired", tenant="t")) == 1


def test_tenant_policy_maps_slo_class_onto_request():
    a = FakeServable("a")
    clock, _, rt = _fleet(
        a, tenants=[TenantPolicy("gold", priority=2, deadline_s=1.5)])
    r = rt.submit("a", [0], tenant="gold")
    assert r.priority == 2
    assert r.deadline == pytest.approx(clock.now() + 1.5)
    # explicit arguments override the class defaults
    r2 = rt.submit("a", [1], tenant="gold", priority=0, deadline_s=9.0)
    assert r2.priority == 0
    assert r2.deadline == pytest.approx(clock.now() + 9.0)


def test_hot_tenant_cannot_starve_cold_tenant():
    """Hot floods far past its quota; cold's requests still admit,
    schedule, and meet their deadlines — the isolation the fleet is for."""
    a = FakeServable("a", est=0.01, max_batch=4)
    clock, _, rt = _fleet(
        a,
        tenants=[TenantPolicy("hot", qps=1.0, burst=2),
                 TenantPolicy("cold", priority=1)],
        capacity=8)
    shed = 0
    for i in range(10):                   # hot burst: 2 admit, 8 shed
        try:
            rt.submit("a", [i], tenant="hot", deadline_s=5.0)
        except QuotaExceededError:
            shed += 1
    assert shed == 8
    cold = [rt.submit("a", [100 + i], tenant="cold", deadline_s=1.0)
            for i in range(3)]            # queue has room: hot shed at door
    clock.advance(1.0)
    rt.drain()
    for r in cold:
        assert r.future.result(timeout=0) is not None
    m = rt.metrics
    assert m.count(labeled("slo_met", tenant="cold")) == 3
    assert m.count(labeled("rejected_quota", tenant="hot")) == 8
    assert m.count("rejected_queue_full") == 0


def test_unknown_servable_rejected_at_admission():
    a = FakeServable("a")
    clock, _, rt = _fleet(a)
    with pytest.raises(UnknownServableError):
        rt.submit("nope", [0], tenant="t")
    m = rt.metrics
    assert m.count("rejected_unknown_servable") == 1
    assert m.count(labeled("rejected_unknown_servable", tenant="t")) == 1
    assert m.count("submitted") == 1
    assert rt.tenants.state("t")["inflight"] == 0   # never acquired


# ---------------------------------------------------------------------------
# manager: hot load/unload under the capacity budget
# ---------------------------------------------------------------------------


def test_manager_lazy_load_and_lru_unload():
    a = FakeServable("a", cost=1.0)
    b = FakeServable("b", cost=1.0)
    c = FakeServable("c", cost=1.0)
    mgr = FleetManager(capacity_units=2.0)
    for sv in (a, b, c):
        mgr.register(sv)
    assert not mgr.loaded("a") and a.loads == 0    # registered != loaded
    mgr.resolve("a")
    mgr.resolve("b")
    assert a.loads == 1 and b.loads == 1 and mgr.loads == 2
    mgr.resolve("a")                               # touch: a is now MRU
    mgr.resolve("c")                               # budget 2: evicts b
    assert b.unloads == 1 and mgr.unloads == 1
    assert mgr.loaded("a") and not mgr.loaded("b") and mgr.loaded("c")
    mgr.resolve("b")                               # hot reload
    assert b.loads == 2 and not mgr.loaded("a")    # a was LRU this time


def test_manager_weighted_costs_and_registration():
    big = FakeServable("big", cost=3.0)
    small = FakeServable("small", cost=1.0)
    mgr = FleetManager(capacity_units=3.5)
    mgr.register(big)
    mgr.register(small)
    with pytest.raises(ValueError):
        mgr.register(FakeServable("big"))          # duplicate key
    mgr.resolve("big")
    mgr.resolve("small")                           # 4.0 > 3.5: evicts big
    assert big.unloads == 1 and mgr.loaded("small")
    with pytest.raises(UnknownServableError):
        mgr.servable("ghost")


def test_runtime_serves_through_a_reload():
    a = FakeServable("a", cost=1.0)
    b = FakeServable("b", cost=1.0)
    clock, mgr, rt = _fleet(a, b, capacity_units=1.0)  # one resident max
    r1 = rt.submit("a", [1])
    rt.drain()
    r2 = rt.submit("b", [2])                       # loading b evicts a
    rt.drain()
    r3 = rt.submit("a", [3])                       # a hot-reloads
    rt.drain()
    assert [r.future.result(timeout=0)[0] for r in (r1, r2, r3)] \
        == [1.0, 2.0, 3.0]
    assert a.loads == 2 and a.unloads >= 1 and mgr.unloads >= 2


# ---------------------------------------------------------------------------
# real engines: bit-identity with ServeRuntime, GCN + LM end to end
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


def _drive(rt, clock):
    """Step the loop at every close trigger until the queue drains."""
    for _ in range(64):
        rt.loop.step()
        nxt = rt.scheduler.next_close_time()
        if nxt is None:
            break
        if nxt > clock.now():
            clock.set_time(nxt)
    rt.loop.drain()


def test_single_gcn_servable_bit_identical_to_serve_runtime(
        toy_engine_parts):
    """Acceptance: same submissions, same clock steps -> byte-identical
    outputs from a one-servable fleet and the single-engine runtime."""
    from repro.runtime import ServeRuntime

    engine = _toy_engine(toy_engine_parts)
    engine.warmup()
    rng = np.random.default_rng(5)
    requests = [
        rng.choice(400, size=int(rng.integers(1, 5)), replace=False)
        for _ in range(13)
    ]
    deadlines = [float(1 + (i % 3)) for i in range(len(requests))]

    clock_a = VirtualClock(start=100.0)
    solo = ServeRuntime(engine, capacity=64, clock=clock_a)
    solo_reqs = [solo.submit(s, deadline_s=d)
                 for s, d in zip(requests, deadlines)]
    _drive(solo, clock_a)

    clock_b = VirtualClock(start=100.0)
    mgr = FleetManager(capacity_units=4.0)
    sv = mgr.register(engine.servable(key="toy"))
    mgr.resolve("toy")
    fleet = FleetRuntime(mgr, clock=clock_b, capacity=64)
    fleet_reqs = [fleet.submit("toy", s, deadline_s=d)
                  for s, d in zip(requests, deadlines)]
    _drive(fleet, clock_b)

    for a, b in zip(solo_reqs, fleet_reqs):
        np.testing.assert_array_equal(a.future.result(timeout=0),
                                      b.future.result(timeout=0))
    # identical batch accounting, not just identical outputs
    for key in ("batches_full", "batches_deadline", "batches_flush",
                "completed"):
        assert solo.metrics.count(key) == fleet.metrics.count(key), key


def test_gcn_plus_lm_fleet_end_to_end(toy_engine_parts):
    """Both model kinds through one loop, zero compiles after load()."""
    from repro.fleet import LmServable

    engine = _toy_engine(toy_engine_parts)
    mgr = FleetManager(capacity_units=4.0)
    mgr.register(engine.servable(key="gcn"))
    lm = mgr.register(LmServable("internlm2-1.8b", key="lm",
                                 seq_buckets=(8,), max_batch=2))
    mgr.resolve("gcn")
    mgr.resolve("lm")
    gcn_compiles = engine.compile_count
    lm_compiles = lm.compiles
    assert lm_compiles == 2                     # seq 8 x batch (1, 2)

    clock = VirtualClock(start=10.0)
    rt = FleetRuntime(mgr, clock=clock, capacity=64)
    rng = np.random.default_rng(3)
    gcn_reqs = [rt.submit("gcn",
                          rng.choice(400, size=2, replace=False),
                          tenant="graphs", deadline_s=2.0)
                for _ in range(3)]
    lm_payloads = [list(rng.integers(0, lm.cfg.vocab, size=5))
                   for _ in range(3)]
    lm_reqs = [rt.submit("lm", p, tenant="words", deadline_s=2.0)
               for p in lm_payloads]
    _drive(rt, clock)

    for r in gcn_reqs:
        out = r.future.result(timeout=0)
        np.testing.assert_allclose(out, engine.query(list(r.seeds)),
                                   rtol=1e-4, atol=1e-4)
    for r, payload in zip(lm_reqs, lm_payloads):
        out = r.future.result(timeout=0)
        assert out.shape == (lm.cfg.vocab,)
        # oracle: unbatched forward at the last real position
        from repro.models.lm import forward

        toks = np.zeros((1, 8), np.int32)
        toks[0, : len(payload)] = payload
        want = np.asarray(forward(lm.params, lm.cfg, toks))
        np.testing.assert_allclose(out, want[0, len(payload) - 1],
                                   rtol=1e-4, atol=1e-4)
    assert engine.compile_count == gcn_compiles
    assert lm.compiles == lm_compiles
    m = rt.metrics
    assert m.count("completed") == 6
    # per-tenant / per-servable labeled series landed beside the plain ones
    assert m.count(labeled("completed", tenant="graphs",
                           servable="gcn")) == 3
    assert m.count(labeled("completed", tenant="words", servable="lm")) == 3
    assert m.histogram(labeled("exec_s", servable="lm")).count >= 1


def test_lm_servable_validates_payloads():
    from repro.fleet import LmServable

    lm = LmServable("internlm2-1.8b", seq_buckets=(8,), max_batch=2)
    with pytest.raises(ValueError):
        lm.prepare([])                          # empty
    with pytest.raises(ValueError):
        lm.prepare(list(range(9)))              # exceeds top bucket
    with pytest.raises(ValueError):
        lm.prepare([lm.cfg.vocab + 5])          # out-of-vocab token
    p = lm.prepare([1, 2, 3])
    assert p.bucket.seq == 8 and p.n_tokens == 3
    assert p.tokens.tolist() == [1, 2, 3, 0, 0, 0, 0, 0]


def test_fleet_config_round_trip(toy_engine_parts, tmp_path):
    """The --fleet-config schema builds a runnable fleet."""
    from repro.fleet import fleet_from_config

    config = {
        "servables": [
            {"kind": "lm", "key": "lm", "arch": "internlm2-1.8b",
             "seq_buckets": [8], "max_batch": 2},
        ],
        "capacity_units": 2.0,
        "tenants": [
            {"name": "gold", "priority": 1, "deadline_s": 5.0},
            {"name": "free", "qps": 1.0, "burst": 1.0},
        ],
        "weights": {"lm": 2.0},
    }
    clock = VirtualClock()
    rt = fleet_from_config(config, clock=clock)
    assert rt.manager.knows("lm") and not rt.manager.knows("gcn")
    r = rt.submit("lm", [1, 2, 3], tenant="gold")
    assert r.priority == 1 and r.deadline == pytest.approx(5.0)
    rt.submit("lm", [4], tenant="free")
    with pytest.raises(QuotaExceededError):
        rt.submit("lm", [5], tenant="free")
    clock.advance(0.1)
    rt.drain()
    assert r.future.result(timeout=0).shape == (rt.manager.servable(
        "lm").cfg.vocab,)


# ---------------------------------------------------------------------------
# serve_gcn: a failed request makes the async and fleet scenarios exit
# non-zero; shed load alone does not
# ---------------------------------------------------------------------------


def _raise_runner(*_a, **_k):
    raise RuntimeError("runner failure injected by the test")


def _serve_gcn_args(*argv):
    from repro.launch.serve_gcn import build_parser

    return build_parser().parse_args(
        ["--qps", "400", "--deadline-ms", "5000", *argv])


def _run_async(toy_engine_parts, monkeypatch, fail: bool):
    from repro.launch.serve_gcn import run_async_scenario

    engine = _toy_engine(toy_engine_parts)
    engine.warmup()
    if fail:
        monkeypatch.setattr(engine.batcher, "run", _raise_runner)
    rng = np.random.default_rng(2)
    requests = [rng.choice(400, size=2, replace=False) for _ in range(6)]
    run_async_scenario(engine, requests, _serve_gcn_args())


def _run_fleet(tmp_path, monkeypatch, fail: bool):
    import json

    from repro.fleet import LmServable
    from repro.launch.serve_gcn import run_fleet_scenario

    if fail:
        monkeypatch.setattr(LmServable, "run_batch", _raise_runner)
    config = {
        "servables": [{"kind": "lm", "key": "lm", "arch": "internlm2-1.8b",
                       "seq_buckets": [8], "max_batch": 2}],
        "capacity_units": 2.0,
        "tenants": [{"name": "t", "deadline_s": 5.0}],
        "loads": [{"tenant": "t", "servable": "lm", "qps": 400,
                   "requests": 4, "deadline_ms": 5000, "seq_len": 5}],
    }
    path = tmp_path / "fleet.json"
    path.write_text(json.dumps(config))
    run_fleet_scenario(_serve_gcn_args("--fleet-config", str(path)))


@pytest.mark.parametrize("scenario", ["async", "fleet"])
def test_serve_gcn_exits_nonzero_when_a_request_fails(
        scenario, toy_engine_parts, tmp_path, monkeypatch):
    with pytest.raises(SystemExit) as exc:
        if scenario == "async":
            _run_async(toy_engine_parts, monkeypatch, fail=True)
        else:
            _run_fleet(tmp_path, monkeypatch, fail=True)
    # a message exits with status 1
    assert exc.value.code and "requests failed" in str(exc.value.code)


@pytest.mark.parametrize("scenario", ["async", "fleet"])
def test_serve_gcn_returns_when_every_request_succeeds(
        scenario, toy_engine_parts, tmp_path, monkeypatch):
    if scenario == "async":
        _run_async(toy_engine_parts, monkeypatch, fail=False)
    else:
        _run_fleet(tmp_path, monkeypatch, fail=False)
