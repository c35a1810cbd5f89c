"""Plain reference of the GCN configurations: f64 on the host.

Imports nothing of the program under test.  It takes the graph's raw
edges (the data set) and the harness's features and weights, normalizes
the adjacency itself, and computes

    logits = A_hat relu(A_hat (X W0 + b0)) W1 + b1,   A_hat = D^-1/2 (A+I) D^-1/2

(Kipf & Welling, arXiv:1609.02907, eq. 9; the bias is added before the
aggregation, as the program does).  A query's answer is the same forward
over the induced subgraph of its seeds' sampled k-hop field, with the
globally normalized values kept, and the sampler's documented draws: one
generator per request seeded with ``[sampler_seed] + sorted(unique seeds)``,
the frontier visited in ascending order, each node's neighbours (CSR order,
self loop included) cut to ``fanout`` by a choice without replacement.

``products="bf16"`` gives the reference at the configuration's stated
arithmetic, the TPU's default precision for an f32 matmul: the operands
of every product, the combination's and the aggregation's (the graph's
values and the dense rows), are rounded to bfloat16 (round to nearest
even, by way of f32) and the products are exact.  Nothing else is
rounded.  See ``bench/check.py`` for how both variants decide
``correct``.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np
import scipy.sparse as sp

Weights = List[Tuple[np.ndarray, np.ndarray]]


def bf16_round(a) -> np.ndarray:
    """Round to the nearest bfloat16 (ties to even), returned as f64."""
    u = np.ascontiguousarray(a, dtype=np.float32).view(np.uint32).copy()
    u += np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))
    u &= np.uint32(0xFFFF0000)
    return u.view(np.float32).astype(np.float64)


def normalize(indptr, indices, n: int) -> sp.csr_matrix:
    """``D^-1/2 (A + I) D^-1/2`` in f64 from a raw symmetric adjacency."""
    a = sp.csr_matrix((np.ones(len(indices)), np.asarray(indices),
                       np.asarray(indptr)), shape=(n, n))
    a = (a + sp.eye(n, format="csr")).tocsr()
    a.data[:] = 1.0
    d = 1.0 / np.sqrt(np.asarray(a.sum(axis=1)).ravel())
    out = (sp.diags(d) @ a @ sp.diags(d)).tocsr()
    out.sort_indices()
    return out


def forward(a: sp.csr_matrix, x: np.ndarray, weights: Weights,
            products: str = "exact") -> np.ndarray:
    """Logits of the GCN stack over ``a`` (any node set)."""
    if products not in ("exact", "bf16"):
        raise ValueError(f"products must be exact or bf16, not {products!r}")
    rnd = bf16_round if products == "bf16" else (
        lambda v: np.asarray(v, np.float64))
    if products == "bf16":
        a = a.copy()
        a.data = bf16_round(a.data)
    h = np.asarray(x, np.float64)
    for i, (w, b) in enumerate(weights):
        h = a @ rnd(rnd(h) @ rnd(w) + np.asarray(b, np.float64))
        if i < len(weights) - 1:
            h = np.maximum(h, 0.0)
    return h


def sample_k_hop(a: sp.csr_matrix, seeds: Sequence[int], hops: int,
                 fanout: Optional[int], rng: np.random.Generator
                 ) -> np.ndarray:
    """Sorted ids of the seeds' ``hops``-hop field, each frontier node's
    neighbour list cut to ``fanout`` (None: the exact field)."""
    seeds = np.unique(np.asarray(seeds, dtype=np.int64))
    visited = np.zeros(a.shape[0], dtype=bool)
    visited[seeds] = True
    frontier = seeds
    for _ in range(hops):
        nxt = []
        for u in frontier:
            nbrs = a.indices[a.indptr[u]:a.indptr[u + 1]]
            if fanout is not None and len(nbrs) > fanout:
                nbrs = rng.choice(nbrs, size=fanout, replace=False)
            nxt.append(nbrs)
        if not nxt:
            break
        cand = np.unique(np.concatenate(nxt).astype(np.int64))
        frontier = cand[~visited[cand]]
        visited[frontier] = True
        if frontier.size == 0:
            break
    return np.flatnonzero(visited)


def query(a: sp.csr_matrix, x: np.ndarray, weights: Weights,
          seeds: Sequence[int], *, hops: int, fanout: Optional[int],
          sampler_seed: int, products: str = "exact") -> np.ndarray:
    """Seed logits of one query, in the request's seed order."""
    rng = np.random.default_rng(
        [sampler_seed] + sorted(int(s) for s in np.unique(seeds)))
    nodes = sample_k_hop(a, seeds, hops, fanout, rng)
    sub = a[nodes][:, nodes].tocsr()
    out = forward(sub, np.asarray(x)[nodes], weights, products)
    return out[np.searchsorted(nodes, np.asarray(seeds, dtype=np.int64))]
