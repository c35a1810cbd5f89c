"""The generator's schedule and the tail arithmetic, with a stub runtime."""

import math
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest
import scipy.sparse as sp

from bench import drivers, traffic

OPEN = {"kind": "open_loop", "rate_per_s": 200.0, "seeds_per_request": [1, 4],
        "pool_seed": 3, "hops": 2}


def test_arrivals_count_and_range_are_fixed_by_rate():
    for seed in (1, 2**40 + 3):
        off = traffic.arrival_offsets(OPEN, 10.0, np.random.default_rng(seed))
        assert len(off) == 2000
        assert np.all(np.diff(off) >= 0)
        assert off[0] >= 0 and off[-1] < 10.0


def test_bursts_keep_the_mean_rate_and_crowd_the_burst():
    t = dict(OPEN, burst={"period_s": 5.0, "burst_s": 1.0,
                          "burst_rate_factor": 3.0})
    off = traffic.arrival_offsets(t, 20.0, np.random.default_rng(0))
    assert len(off) == 4000
    in_burst = np.mean((off % 5.0) < 1.0)
    assert in_burst == pytest.approx(3.0 / 5.0, abs=0.03)


def test_impossible_burst_is_refused():
    with pytest.raises(ValueError):
        traffic.validate(dict(OPEN, burst={"period_s": 5, "burst_s": 2,
                                           "burst_rate_factor": 3}))


def test_pool_is_fixed_by_pool_seed_and_distinct():
    a = traffic.request_pool(OPEN, 500, 300)
    b = traffic.request_pool(OPEN, 500, 300)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    keys = {tuple(sorted(r)) for r in a}
    assert len(keys) == 300
    assert all(1 <= len(r) <= 4 for r in a)


def test_window_serves_the_same_set_in_another_order():
    graph = sp.random(400, 400, density=0.01, format="csr",
                      random_state=0)
    graph = ((graph + graph.T) > 0).astype(np.float32).tocsr()
    g = (graph.indptr, graph.indices)
    w1, r1 = traffic.plan_requests(OPEN, 400, 10, 200,
                                   np.random.default_rng(1), graph=g)
    w2, r2 = traffic.plan_requests(OPEN, 400, 10, 200,
                                   np.random.default_rng(2), graph=g)
    key = lambda reqs: sorted(tuple(sorted(r)) for r in reqs)  # noqa: E731
    assert key(r1) == key(r2)
    assert [tuple(r) for r in r1] != [tuple(r) for r in r2]
    assert not set(map(tuple, w1)) & set(map(tuple, r1))


def test_field_sizes_match_a_plain_walk():
    graph = sp.random(60, 60, density=0.05, format="csr", random_state=1)
    graph = ((graph + graph.T) > 0).astype(np.float32).tocsr()
    reqs = [np.array([0, 5]), np.array([7]), np.array([59, 1, 2])]
    got = traffic.field_sizes(graph.indptr, graph.indices, 60, reqs, 2)
    for r, n in zip(reqs, got):
        seen = set(int(s) for s in r)
        frontier = set(seen)
        for _ in range(2):
            nxt = set()
            for u in frontier:
                nxt |= set(graph.indices[graph.indptr[u]:graph.indptr[u + 1]])
            frontier = nxt - seen
            seen |= nxt
        assert n == len(seen)


def test_stratified_prefix_holds_every_size():
    sizes = np.repeat([1, 10, 100, 1000], [400, 300, 200, 100])
    order = traffic.stratified_order(sizes, np.random.default_rng(5))
    assert sorted(order) == list(range(1000))
    prefix = sizes[order[:250]]
    for v, share in ((1, 0.4), (10, 0.3), (100, 0.2), (1000, 0.1)):
        assert abs(np.sum(prefix == v) - 250 * share) <= 33


def test_zipf_hot_set_moves():
    t = dict(OPEN, seed_nodes="zipf", zipf_s=1.1, hot_set_move_s=10.0,
             seeds_per_request=[1, 1])
    offs = np.concatenate([np.zeros(500), np.full(500, 15.0)])
    reqs = traffic.zipf_requests(t, 1000, offs, np.random.default_rng(0))
    first = np.bincount(np.concatenate(reqs[:500]), minlength=1000)
    second = np.bincount(np.concatenate(reqs[500:]), minlength=1000)
    assert first.max() > 40                      # a hot node
    assert first.argmax() != second.argmax()     # that moved


# -- drivers against a stub runtime ------------------------------------------


class Refused(RuntimeError):
    pass


class Shed(RuntimeError):
    pass


class StubRequest:
    def __init__(self):
        self.future = Future()
        self.wait_s, self.prep_s = 0.001, 0.002


class StubRuntime:
    """Answers after ``delay`` seconds; refuses seed 13, sheds seed 14,
    fails seed 15."""

    def __init__(self, delay=0.01):
        self.delay, self.sent = delay, []

    def submit(self, seeds, deadline):
        self.sent.append((time.perf_counter(), deadline))
        if int(seeds[0]) == 13:
            raise Refused("queue full")
        req = StubRequest()

        def answer():
            s = int(seeds[0])
            if s == 14:
                req.future.set_exception(Shed("expired"))
            elif s == 15:
                req.future.set_exception(ValueError("boom"))
            else:
                req.future.set_result(np.full((len(seeds), 2), float(s)))

        threading.Timer(self.delay, answer).start()
        return req


def test_open_loop_sends_on_schedule_and_times_from_due():
    rt = StubRuntime(delay=0.02)
    sender = drivers.Sender(rt.submit, (Refused,), (Shed,))
    t0 = time.perf_counter() + 0.05
    due = t0 + np.arange(20) * 0.01
    reqs = [np.array([100 + i]) for i in range(20)]
    recs = drivers.open_loop(sender, reqs, due, threads=4, deadline_s=0.2)
    for r in recs:
        sender.settle(r, 5.0)
    assert [r.status for r in recs] == ["ok"] * 20
    late = drivers.lateness_s(recs)
    assert np.all(late >= 0) and np.all(late < 0.02)
    lat = [r.latency_s for r in recs]
    assert min(lat) >= 0.02 and max(lat) < 0.2
    assert all(d == pytest.approx(u + 0.2) for (_, d), u in
               zip(sorted(rt.sent), due))
    assert recs[0].wait_s == 0.001 and recs[0].prep_s == 0.002


def test_unanswered_requests_rank_slowest_and_failures_are_counted():
    rt = StubRuntime(delay=0.01)
    sender = drivers.Sender(rt.submit, (Refused,), (Shed,))
    t0 = time.perf_counter() + 0.02
    reqs = [np.array([s]) for s in (1, 2, 13, 14, 15, 3, 4, 5, 6, 7)]
    recs = drivers.open_loop(sender, reqs, t0 + np.zeros(10), threads=2,
                             deadline_s=None)
    for r in recs:
        sender.settle(r, 5.0)
    status = {int(r.seeds[0]): r.status for r in recs}
    assert status[13] == "refused" and status[14] == "shed"
    assert status[15] == "failed"
    ranked = drivers.rank_latencies(recs)
    answered = max(r.latency_s * 1e3 for r in recs if r.status == "ok")
    assert all(v >= answered for v in ranked[7:])
    assert drivers.percentile(ranked, 50) <= answered
    assert drivers.percentile(ranked, 95) == ranked[-1]


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert drivers.percentile(xs, 50) == 50
    assert drivers.percentile(xs, 95) == 95
    assert drivers.percentile([7.0], 95) == 7.0
    assert drivers.percentile([], 50) is None


def test_closed_loop_keeps_each_client_to_one_request():
    rt = StubRuntime(delay=0.005)
    sender = drivers.Sender(rt.submit, (Refused,), (Shed,))
    reqs = [np.array([100 + i]) for i in range(1000)]
    until = time.perf_counter() + 0.3
    recs = drivers.closed_loop(sender, reqs, clients=3, until=until,
                               deadline_s=None, timeout_s=5.0)
    assert all(r.status == "ok" for r in recs)
    assert [r.index for r in recs] == list(range(len(recs)))
    # three clients, each waiting ~5 ms per answer, for 0.3 s
    assert 30 <= len(recs) <= 200
    assert not math.isnan(recs[-1].done)
