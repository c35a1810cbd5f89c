"""The one traffic generator: every mix is a data file of its parameters.

``traffic/<name>.json`` holds ``kind`` and the parameters below; nothing
else about a mix is code.

* ``kind``: ``full_graph`` (back-to-back full forwards from one
  caller), ``open_loop`` (requests sent on a schedule whether or not
  earlier ones finished) or ``closed_loop`` (``clients`` threads, each
  sending its next request when its last one is answered).
* Open loop: ``rate_per_s`` (Poisson arrivals); optional
  ``burst`` ``{"period_s", "burst_s", "burst_rate_factor"}``: for
  ``burst_s`` of every ``period_s`` the rate is ``burst_rate_factor``
  times the mean, and lower the rest of the time, so the mean stays
  ``rate_per_s``; ``submit_threads`` senders.
* Requests: ``seeds_per_request`` ``[lo, hi]`` (uniform, inclusive);
  ``seed_nodes`` ``"uniform"`` or ``"zipf"`` (then ``zipf_s`` and
  ``hot_set_move_s``: node popularity falls as rank^-s, and the ranking
  moves to other nodes every ``hot_set_move_s`` seconds).
* Uniform requests come from a pool drawn from ``pool_seed``, the same
  for every run, of distinct seed sets: the warm-up's requests first,
  then the window's.  ``--seed`` only orders the window's part, so every
  run serves the same set of requests.  The order is stratified by each
  request's receptive field (its exact ``hops``-hop closure), so any
  prefix of it holds requests of every size in the pool's proportions.
* Serving parameters: ``fanout`` (null: uncapped), ``hops``,
  ``deadline_ms`` (null: best effort), ``max_batch``, ``queue_capacity``,
  ``sampler_seed``; ``warm_s`` seconds of the mix's own traffic run
  before the window; ``check_sample`` answers compared (null: all).
"""

from __future__ import annotations

import math
from typing import List, Optional, Sequence

import numpy as np
import scipy.sparse as sp

KINDS = ("full_graph", "open_loop", "closed_loop")


def validate(t: dict) -> dict:
    if t.get("kind") not in KINDS:
        raise ValueError(f"traffic kind {t.get('kind')!r} not in {KINDS}")
    if t["kind"] == "open_loop" and float(t["rate_per_s"]) <= 0:
        raise ValueError("open_loop needs rate_per_s > 0")
    b = t.get("burst")
    if b:
        spare = b["period_s"] - b["burst_rate_factor"] * b["burst_s"]
        if spare < 0 or b["burst_s"] >= b["period_s"]:
            raise ValueError(f"burst {b} leaves no rate for the rest of "
                             "the period")
    return t


# -- arrivals -------------------------------------------------------------


def _intensity_knots(t: dict, seconds: float):
    """``(times, cumulative expected arrivals)`` at every rate change."""
    rate = float(t["rate_per_s"])
    b = t.get("burst")
    if not b:
        return np.array([0.0, seconds]), np.array([0.0, rate * seconds])
    period, width = float(b["period_s"]), float(b["burst_s"])
    hi = rate * float(b["burst_rate_factor"])
    lo = (rate * period - hi * width) / (period - width)
    knots, cum, now, acc = [0.0], [0.0], 0.0, 0.0
    while now < seconds:
        for length, r in ((width, hi), (period - width, lo)):
            end = min(now + length, seconds)
            acc += r * (end - now)
            now = end
            knots.append(now)
            cum.append(acc)
            if now >= seconds:
                break
    return np.array(knots), np.array(cum)


def arrival_offsets(t: dict, seconds: float, rng: np.random.Generator
                    ) -> np.ndarray:
    """Sorted send offsets in ``[0, seconds)``: a Poisson process (with
    its bursts) conditioned on its expected count, so every run sends
    the same number of requests."""
    knots, cum = _intensity_knots(t, seconds)
    count = int(round(cum[-1]))
    u = np.sort(rng.uniform(0.0, cum[-1], size=count))
    return np.interp(u, cum, knots)


# -- requests -------------------------------------------------------------


def _n_seeds(t: dict, rng: np.random.Generator) -> int:
    lo, hi = t["seeds_per_request"]
    return int(rng.integers(lo, hi + 1))


def request_pool(t: dict, n_nodes: int, size: int) -> List[np.ndarray]:
    """``size`` distinct uniform seed sets drawn from ``pool_seed``."""
    rng = np.random.default_rng(int(t["pool_seed"]))
    seen, pool = set(), []
    while len(pool) < size:
        seeds = rng.choice(n_nodes, size=_n_seeds(t, rng), replace=False)
        key = tuple(sorted(int(s) for s in seeds))
        if key not in seen:
            seen.add(key)
            pool.append(seeds.astype(np.int64))
    return pool


def field_sizes(indptr, indices, n_nodes: int,
                requests: Sequence[np.ndarray], hops: int) -> np.ndarray:
    """Nodes in each request's exact ``hops``-hop closure."""
    cols = np.repeat(np.arange(len(requests)), [len(r) for r in requests])
    rows = np.concatenate(requests)
    m = sp.csr_matrix((np.ones(len(rows), np.float32), (rows, cols)),
                      shape=(n_nodes, len(requests)))
    a = sp.csr_matrix((np.ones(len(indices), np.float32), indices, indptr),
                      shape=(n_nodes, n_nodes))
    a = (a + sp.eye(n_nodes, format="csr", dtype=np.float32)).tocsr()
    a.data[:] = 1.0
    for _ in range(hops):
        m = a @ m
        m.data[:] = 1.0
    return np.diff(m.tocsc().indptr)


def stratified_order(sizes: np.ndarray, rng: np.random.Generator,
                     strata: int = 8) -> np.ndarray:
    """A random order in which every prefix holds each size stratum in
    its share of the whole (to within one request)."""
    n = len(sizes)
    ranked = np.argsort(sizes, kind="stable")
    groups = np.array_split(ranked, min(strata, n))
    idx, keys = [], []
    for g in groups:
        g = rng.permutation(g)
        phase = rng.uniform()
        idx.append(g)
        keys.append((np.arange(len(g)) + phase) / len(g))
    idx, keys = np.concatenate(idx), np.concatenate(keys)
    return idx[np.argsort(keys, kind="stable")]


def zipf_requests(t: dict, n_nodes: int, offsets: np.ndarray,
                  rng: np.random.Generator) -> List[np.ndarray]:
    """Seed sets whose nodes follow a Zipf law over a ranking that moves
    every ``hot_set_move_s`` seconds (``offsets``: each request's send
    time from the start)."""
    s = float(t["zipf_s"])
    move = float(t.get("hot_set_move_s") or math.inf)
    weights = (np.arange(1, n_nodes + 1, dtype=np.float64)) ** -s
    cdf = np.cumsum(weights / weights.sum())
    ranking = rng.permutation(n_nodes)
    shifts = {}
    out = []
    for off in offsets:
        epoch = int(off // move) if math.isfinite(move) else 0
        if epoch not in shifts:
            shifts[epoch] = int(rng.integers(n_nodes))
        k = _n_seeds(t, rng)
        ranks = np.searchsorted(cdf, rng.uniform(size=k))
        nodes = ranking[(np.minimum(ranks, n_nodes - 1) + shifts[epoch])
                        % n_nodes]
        out.append(np.unique(nodes).astype(np.int64))
    return out


def plan_requests(t: dict, n_nodes: int, n_warm: int, n_window: int,
                  rng: np.random.Generator, graph=None,
                  warm_offsets: Optional[np.ndarray] = None,
                  window_offsets: Optional[np.ndarray] = None):
    """``(warm requests, window requests)``.

    ``graph`` is ``(indptr, indices)`` of the raw adjacency, used to
    stratify uniform requests by receptive field.  Zipf requests need
    each request's send offset (open loop)."""
    if t.get("seed_nodes", "uniform") == "zipf":
        warm_rng = np.random.default_rng(int(t["pool_seed"]))
        warm = zipf_requests(t, n_nodes,
                             warm_offsets if warm_offsets is not None
                             else np.zeros(n_warm), warm_rng)
        window = zipf_requests(t, n_nodes,
                               window_offsets if window_offsets is not None
                               else np.zeros(n_window), rng)
        return warm, window
    pool = request_pool(t, n_nodes, n_warm + n_window)
    warm, window = pool[:n_warm], pool[n_warm:]
    if graph is not None and n_window:
        sizes = field_sizes(graph[0], graph[1], n_nodes, window,
                            int(t["hops"]))
        order = stratified_order(sizes, rng)
    else:
        order = rng.permutation(n_window)
    return warm, [window[i] for i in order]
