"""Dataset synthesis: Table III statistics + power-law shape (Fig 2)."""

import numpy as np
import pytest

from repro.graphs import DATASETS, load_dataset
from repro.graphs.datasets import gcn_normalize, synthesize_adjacency


@pytest.mark.parametrize("name", ["cora", "citeseer", "pubmed"])
def test_table3_statistics(name):
    spec = DATASETS[name]
    ds = load_dataset(name)
    assert ds.adj.rows == spec.nodes
    # undirected edge count within 25% of Table III
    edges = ds.adj.nnz / 2
    assert abs(edges - spec.edges) / spec.edges < 0.25
    assert ds.features.shape == (spec.nodes, spec.feature_dim)


def test_yelp_edge_count_is_undirected():
    """yelp's ``edges`` is an undirected count, as every other graph's is
    (GraphSAINT's 6,977,410, not the 13,954,819 of both directions): its
    symmetric adjacency holds about twice that many nonzeros.  Adjacency
    only: yelp's features alone are 860 MB."""
    spec = DATASETS["yelp"]
    assert (spec.nodes, spec.edges, spec.feature_dim, spec.classes) == (
        716_847, 6_977_410, 300, 100)
    adj = synthesize_adjacency(spec, seed=0)
    assert adj.rows == spec.nodes
    edges = adj.nnz / 2
    assert abs(edges - spec.edges) / spec.edges < 0.25


def test_power_law_degree_shape():
    """A small set of supernodes, a long tail (Fig 2)."""
    ds = load_dataset("pubmed", with_features=False)
    deg = np.sort(ds.adj.row_nnz())[::-1]
    # top 1% of nodes hold a disproportionate share of edges
    top = deg[: len(deg) // 100].sum() / deg.sum()
    assert top > 0.08
    # the median node has low degree
    assert np.median(deg) <= deg.mean()


def test_normalization_is_symmetric_and_bounded():
    ds = load_dataset("cora", with_features=False)
    a = ds.adj_norm.to_scipy()
    diff = abs(a - a.T)
    assert diff.max() < 1e-6
    # spectral bound: rows of D^-1/2 (A+I) D^-1/2 sum to <= sqrt(deg)
    assert a.data.max() <= 1.0 + 1e-6


def test_determinism():
    a1 = synthesize_adjacency(DATASETS["cora"], seed=42)
    a2 = synthesize_adjacency(DATASETS["cora"], seed=42)
    assert np.array_equal(a1.indices, a2.indices)
    a3 = synthesize_adjacency(DATASETS["cora"], seed=43)
    assert not np.array_equal(a1.indices, a3.indices)


def test_gcn_normalize_rowsum():
    ds = load_dataset("citeseer", with_features=False)
    an = ds.adj_norm
    # every node has its self-loop: diagonal present
    m = an.to_scipy()
    assert (m.diagonal() > 0).all()
