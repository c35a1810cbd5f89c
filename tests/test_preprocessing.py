"""Property tests for the hybrid preprocessing (Algorithm 1 + edge-cut)."""

import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # seeded-sweep fallback, tests/_propcheck.py
    from tests._propcheck import given, settings, strategies as st

from repro.core import (
    ell_to_dense,
    preprocess,
    random_power_law_csr,
    vertex_cut_tile,
    partition_into_tiles,
)
from repro.graphs.partition import (
    cluster_greedy_bfs,
    edge_cut_quality,
    label_propagation_permutation,
)
from repro.graphs.datasets import load_dataset


@settings(max_examples=20, deadline=None)
@given(
    n=st.integers(8, 120),
    nnz=st.integers(1, 600),
    tau=st.integers(1, 9),
    seed=st.integers(0, 10_000),
)
def test_vertex_cut_properties(n, nnz, tau, seed):
    """Algorithm 1 invariants: RNZ bound, nnz preservation, exact rebuild."""
    adj = random_power_law_csr(n, n, nnz, seed=seed)
    res = preprocess(adj, tau=tau, tile_rows=16, edge_cut="none")
    # 1. the per-row bound holds
    rnz = (res.ell.cols != -1).sum(axis=1)
    assert rnz.max() <= tau
    # 2. no nonzero lost or duplicated
    assert res.ell.nnz == adj.nnz
    # 3. the reassembled matrix is numerically identical
    np.testing.assert_allclose(
        ell_to_dense(res.ell), adj.to_scipy().toarray(), rtol=1e-5, atol=1e-6
    )


def test_vertex_cut_balances_misses():
    """Split sub-rows carry a balanced share of misses (Fig 6)."""
    adj = random_power_law_csr(64, 64, 800, seed=7)
    tiles = partition_into_tiles(adj, 16)
    tau = 4
    for t in tiles:
        vc = vertex_cut_tile(t, tau)
        assert all(len(c) <= tau for c in vc.sub_rows_cols)
        # sub-rows of one original row never exceed ceil(rnz/tau) + leftovers
        rnz = t.rnz()
        for r, n in enumerate(rnz):
            subs = (vc.sub_row_map == t.row_start + r).sum()
            assert subs >= -(-int(n) // tau) or n == 0


def test_edge_cut_permutation_is_permutation():
    adj = random_power_law_csr(100, 100, 700, seed=8)
    for method in ("rcm", "degree", "none"):
        from repro.core import edge_cut_permutation

        perm = edge_cut_permutation(adj, method)
        assert sorted(perm.tolist()) == list(range(100))


def test_clustering_beats_random_locality():
    ds = load_dataset("cora", with_features=False)
    rng = np.random.default_rng(0)
    rand_q = edge_cut_quality(ds.adj_norm, rng.permutation(ds.spec.nodes), 16)
    bfs_q = edge_cut_quality(ds.adj_norm, cluster_greedy_bfs(ds.adj_norm, 16), 16)
    lp_q = edge_cut_quality(
        ds.adj_norm, label_propagation_permutation(ds.adj_norm), 16
    )
    assert bfs_q > rand_q
    assert lp_q > rand_q


def test_preprocess_spmm_correct_after_permutation():
    """Edge-cut permutes rows AND columns: out[perm] == A[perm][:,perm] @ X[perm]."""
    adj = random_power_law_csr(90, 90, 500, seed=9)
    x = np.random.default_rng(1).standard_normal((90, 8)).astype(np.float32)
    res = preprocess(adj, tau=5, tile_rows=16, edge_cut="rcm")
    from repro.core import spmm_ell

    out_perm = np.asarray(spmm_ell(res.ell, x[res.perm]))
    expected = (adj.to_scipy() @ x)[res.perm]
    np.testing.assert_allclose(out_perm, expected, rtol=1e-4, atol=1e-5)


def _ell_tile_by_tile(adj, tau, tile_rows, pad_rows_to):
    """The ELL of the per-tile Algorithm 1 loop (partition_into_tiles,
    vertex_cut_tile), assembled row by row."""
    from repro.core.sparse_formats import csr_rows_to_ell

    cols, vals, rmap = [], [], []
    for t in partition_into_tiles(adj, tile_rows):
        vt = vertex_cut_tile(t, tau)
        for c, v, m in zip(vt.sub_rows_cols, vt.sub_rows_vals,
                           vt.sub_row_map):
            cols.append(t.col_ids[c].astype(np.int32))
            vals.append(v)
            rmap.append(int(m))
    return csr_rows_to_ell(cols, vals, rmap, tau=tau, n_dense_rows=adj.cols,
                           n_orig_rows=adj.rows, pad_rows_to=pad_rows_to)


@pytest.mark.parametrize("graph,tau,tile_rows", [
    ("pubmed", 6, 16), ("power_law", 2, 16), ("power_law", 3, 5),
    ("power_law", 6, 16)])
def test_vectorised_vertex_cut_equals_the_tile_loop(graph, tau, tile_rows):
    """``preprocess`` cuts every tile at once; its ELL equals, array for
    array, the one the per-tile loop gives on the same permuted graph."""
    from repro.core.preprocessing import apply_symmetric_permutation

    if graph == "pubmed":
        adj = load_dataset("pubmed", with_features=False).adj_norm
    else:
        adj = random_power_law_csr(300, 300, 5000, seed=tau)
    res = preprocess(adj, tau=tau, tile_rows=tile_rows, pad_rows_to=128)
    want = _ell_tile_by_tile(apply_symmetric_permutation(adj, res.perm),
                             tau, tile_rows, 128)
    assert (res.ell.cols != -1).sum(axis=1).max() <= tau
    for name in ("cols", "vals", "row_map"):
        got, ref = getattr(res.ell, name), getattr(want, name)
        assert got.dtype == ref.dtype
        np.testing.assert_array_equal(got, ref)
    assert (res.ell.n_dense_rows, res.ell.n_orig_rows) == (
        want.n_dense_rows, want.n_orig_rows)
