"""Hybrid graph preprocessing (paper Section IV).

Two steps:

1. **Inter-tile edge-cut** — partition the sparse operand into row tiles
   sized for the VRF (not the buffer, unlike GROW).  METIS is unavailable
   offline, so locality comes from a reverse Cuthill–McKee (RCM) symmetric
   permutation (scipy) or a greedy BFS clustering; contiguous tiles of the
   permuted matrix minimize cross-tile edges the way METIS edge-cut tiles do
   (DESIGN.md §5.2).

2. **Intra-tile vertex-cut (Algorithm 1)** — split rows with more than
   ``tau`` nonzeros into ceil(RNZ/tau) sub-rows, distributing VRF *misses*
   and *hits* evenly across the splits so no sub-row exceeds the per-row RNZ
   bound.  Split rows carry a ``row_map`` entry back to the original row; the
   partial outputs are summed (the paper's CMP partial-sum flag).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import numpy as np
import scipy.sparse as sp
from scipy.sparse.csgraph import reverse_cuthill_mckee

from repro.core.sparse_formats import (
    PAD_COL,
    CSRMatrix,
    TiledELL,
    _ceil_div,
)


# ---------------------------------------------------------------------------
# Inter-tile edge-cut
# ---------------------------------------------------------------------------


def edge_cut_permutation(adj: CSRMatrix, method: str = "rcm") -> np.ndarray:
    """Compute a locality-preserving node permutation.

    ``rcm``    — reverse Cuthill–McKee bandwidth minimization (fast, scales
                 to tens of millions of edges; our METIS stand-in).
    ``degree`` — descending-degree order (groups supernodes together, the
                 HDN-style clustering GROW uses for its cache).
    ``none``   — identity.
    """
    n = adj.rows
    if method == "none":
        return np.arange(n)
    if method == "degree":
        deg = adj.row_nnz() + adj.col_nnz()[:n] if adj.cols == n else adj.row_nnz()
        return np.argsort(-deg, kind="stable")
    if method == "rcm":
        m = adj.to_scipy()
        sym = (m + m.T).tocsr() if m.shape[0] == m.shape[1] else m
        perm = reverse_cuthill_mckee(sym.astype(np.float64), symmetric_mode=True)
        return np.asarray(perm, dtype=np.int64)
    raise ValueError(f"unknown edge-cut method: {method}")


def apply_symmetric_permutation(adj: CSRMatrix, perm: np.ndarray) -> CSRMatrix:
    """Permute rows and columns of a square adjacency by ``perm``."""
    m = adj.to_scipy()
    inv = np.empty_like(perm)
    inv[perm] = np.arange(perm.size)
    out = m[perm][:, perm] if m.shape[0] == m.shape[1] else m[perm]
    del inv
    return CSRMatrix.from_scipy(out.tocsr())


@dataclasses.dataclass(frozen=True)
class Tile:
    """One inter-tile edge-cut tile: ``rows`` sparse rows of the operand.

    ``col_ids`` are *global* dense-row indices touched by the tile;
    ``local_cols[r]`` hold, per row, indices into ``col_ids`` — the tile-local
    view matching the paper's 16x16 sub-matrices (Fig 5).
    """

    row_start: int
    rows: int
    col_ids: np.ndarray            # (tile_cols,) global dense-row indices
    local_rows_cols: List[np.ndarray]  # per-row tile-local column indices
    local_rows_vals: List[np.ndarray]  # per-row values

    def rnz(self) -> np.ndarray:
        return np.array([len(c) for c in self.local_rows_cols], dtype=np.int64)

    def cnz(self) -> np.ndarray:
        """Nonzeros per tile-local column (Algorithm 2 input)."""
        counts = np.zeros(len(self.col_ids), dtype=np.int64)
        for c in self.local_rows_cols:
            np.add.at(counts, c, 1)
        return counts


def partition_into_tiles(adj: CSRMatrix, tile_rows: int) -> List[Tile]:
    """Cut the (already permuted) operand into row tiles of ``tile_rows``.

    Each tile's columns are compacted to the set actually touched, mirroring
    the paper's per-tile dense-row working set that must fit the VRF.
    """
    tiles: List[Tile] = []
    for start in range(0, adj.rows, tile_rows):
        stop = min(start + tile_rows, adj.rows)
        lo, hi = adj.indptr[start], adj.indptr[stop]
        g_cols = adj.indices[lo:hi]
        g_vals = adj.data[lo:hi]
        uniq, local = np.unique(g_cols, return_inverse=True)
        rows_cols, rows_vals = [], []
        off = 0
        for r in range(start, stop):
            n = int(adj.indptr[r + 1] - adj.indptr[r])
            rows_cols.append(local[off : off + n].astype(np.int32))
            rows_vals.append(np.asarray(g_vals[off : off + n]))
            off += n
        tiles.append(
            Tile(
                row_start=start,
                rows=stop - start,
                col_ids=uniq.astype(np.int64),
                local_rows_cols=rows_cols,
                local_rows_vals=rows_vals,
            )
        )
    return tiles


# ---------------------------------------------------------------------------
# Intra-tile vertex-cut — Algorithm 1
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class VertexCutTile:
    """Tile after Algorithm 1: no (sub-)row exceeds tau nonzeros."""

    tile: Tile
    sub_rows_cols: List[np.ndarray]  # tile-local col indices per sub-row
    sub_rows_vals: List[np.ndarray]
    sub_row_map: np.ndarray          # (n_sub_rows,) -> global output row
    tau: int

    def rnz(self) -> np.ndarray:
        return np.array([len(c) for c in self.sub_rows_cols], dtype=np.int64)


def _hot_columns(cnz: np.ndarray, tau: int) -> np.ndarray:
    """Columns assumed resident under an ideal VRF of depth tau (Alg 1)."""
    k = min(tau, cnz.size)
    return np.argsort(-cnz, kind="stable")[:k]


def vertex_cut_tile(tile: Tile, tau: int) -> VertexCutTile:
    """Algorithm 1: intra-tile vertex-cut workload balancing.

    Rows with RNZ <= tau pass through.  A row with RNZ > tau is split into
    K = ceil(RNZ/tau) sub-rows; its column indices are classified into a
    MissList (columns *not* among the tau hottest of the tile) and a HitList
    (columns among them), and each sub-row pops n_miss = ceil(|Miss|/K)
    misses plus n_hit = tau - n_miss hits, evening out the expensive VRF
    misses across the splits.
    """
    if tau < 1:
        raise ValueError("tau must be >= 1")
    cnz = tile.cnz()
    hot = set(_hot_columns(cnz, tau).tolist())

    sub_cols: List[np.ndarray] = []
    sub_vals: List[np.ndarray] = []
    sub_map: List[int] = []
    for local_r, (cols, vals) in enumerate(
        zip(tile.local_rows_cols, tile.local_rows_vals)
    ):
        g_row = tile.row_start + local_r
        rnz = len(cols)
        if rnz <= tau:
            sub_cols.append(cols)
            sub_vals.append(vals)
            sub_map.append(g_row)
            continue
        # Step 1: separate miss/hit indices for this row.
        is_hit = np.fromiter((c in hot for c in cols.tolist()), dtype=bool, count=rnz)
        miss_list = list(np.nonzero(~is_hit)[0])
        hit_list = list(np.nonzero(is_hit)[0])
        k_splits = _ceil_div(rnz, tau)
        n_miss = _ceil_div(len(miss_list), k_splits)
        n_hit = tau - n_miss
        # Step 2: distribute into sub-rows.
        for _ in range(k_splits):
            take_m = [miss_list.pop(0) for _ in range(min(n_miss, len(miss_list)))]
            take_h = [hit_list.pop(0) for _ in range(min(n_hit, len(hit_list)))]
            idx = np.array(take_m + take_h, dtype=np.int64)
            if idx.size == 0:
                continue
            sub_cols.append(cols[idx])
            sub_vals.append(vals[idx])
            sub_map.append(g_row)
        # Leftovers (pop shortfall) go into extra sub-rows of <= tau each.
        rest = miss_list + hit_list
        while rest:
            idx = np.array(rest[:tau], dtype=np.int64)
            rest = rest[tau:]
            sub_cols.append(cols[idx])
            sub_vals.append(vals[idx])
            sub_map.append(g_row)

    return VertexCutTile(
        tile=tile,
        sub_rows_cols=sub_cols,
        sub_rows_vals=sub_vals,
        sub_row_map=np.array(sub_map, dtype=np.int32),
        tau=tau,
    )


# ---------------------------------------------------------------------------
# Whole-matrix pipeline -> kernel-facing ELL
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PreprocessResult:
    """Output of the full hybrid preprocessing pipeline."""

    ell: TiledELL                  # bounded-row sparse operand (global cols)
    perm: np.ndarray               # node permutation applied (edge-cut)
    tau: int
    tile_rows: int


def _row_rank(flags: np.ndarray, indptr: np.ndarray,
              row: np.ndarray) -> np.ndarray:
    """For each entry, how many flagged entries precede it in its row."""
    c = np.concatenate([[0], np.cumsum(flags)])
    return c[:-1] - c[indptr[:-1]][row]


def vertex_cut_rows(adj: CSRMatrix, tau: int, tile_rows: int):
    """Algorithm 1 over every tile of ``adj`` at once.

    Gives the sub-row of each nonzero as ``(row, sub)``, with ``sub`` the
    split's index within its row, and the order in which the nonzeros
    fill their sub-rows: the sub-rows of :func:`vertex_cut_tile` on each
    tile of :func:`partition_into_tiles`, in the same order, holding the
    same nonzeros in the same order (``tests/test_preprocessing.py``
    checks it against the per-tile loop).
    """
    if tau < 1:
        raise ValueError("tau must be >= 1")
    n, nnz = adj.rows, adj.nnz
    indptr = adj.indptr
    rnz = np.diff(indptr)
    row = np.repeat(np.arange(n, dtype=np.int64), rnz)
    # Hot columns: each tile's tau columns of most nonzeros, ties to the
    # lower column (``_hot_columns`` over the tile's sorted column ids).
    key = (row // tile_rows) * max(adj.cols, 1) + adj.indices
    uniq, inv, cnz = np.unique(key, return_inverse=True, return_counts=True)
    tile_u = uniq // max(adj.cols, 1)
    order = np.lexsort((uniq, -cnz, tile_u))
    rank = np.arange(uniq.size) - np.searchsorted(tile_u[order],
                                                  tile_u[order])
    hot_u = np.empty(uniq.size, dtype=bool)
    hot_u[order] = rank < tau
    # Rows past tau split into K = ceil(rnz / tau) sub-rows: sub-row j
    # takes misses [j*n_miss, (j+1)*n_miss) and hits [j*n_hit, ...), and
    # the hits left over fill further sub-rows of tau each.
    long_e = (rnz > tau)[row]
    hit = hot_u[inv.reshape(-1)] & long_e
    miss = ~hot_u[inv.reshape(-1)] & long_e
    k = -(-rnz // tau)
    n_miss = np.bincount(row[miss], minlength=n)
    per_miss = -(-n_miss // np.maximum(k, 1))
    per_hit = tau - per_miss
    sub = np.zeros(nnz, dtype=np.int64)
    m_rank = _row_rank(miss, indptr, row)
    sub[miss] = m_rank[miss] // per_miss[row[miss]]
    h_rank = _row_rank(hit, indptr, row)[hit]
    h_row = row[hit]
    taken = k[h_row] * per_hit[h_row]
    sub[hit] = np.where(
        h_rank < taken, h_rank // np.maximum(per_hit[h_row], 1),
        k[h_row] + (h_rank - taken) // tau)
    # Within a sub-row: its misses, then its hits, each in column order.
    span = 2 * (int(sub.max(initial=0)) + 1)
    fill = np.argsort(row * span + 2 * sub + hit, kind="stable")
    return row, sub, fill


def ell_from_vertex_cut(adj: CSRMatrix, row, sub, fill, tau: int,
                        pad_rows_to: int = 1, dtype=np.float32) -> TiledELL:
    """Lay the sub-rows of :func:`vertex_cut_rows` out as an ELL table, in
    row order, each row's sub-rows in the order of their ``sub`` (an
    empty row keeps one empty sub-row)."""
    n = adj.rows
    row_s, sub_s = row[fill], sub[fill]
    new = np.ones(row_s.size, dtype=bool)
    new[1:] = (row_s[1:] != row_s[:-1]) | (sub_s[1:] != sub_s[:-1])
    group = np.cumsum(new) - 1                  # sub-row of each entry
    g_start = np.flatnonzero(new)
    g_row = row_s[g_start]
    n_sub = np.maximum(np.bincount(g_row, minlength=n), 1)
    row_off = np.concatenate([[0], np.cumsum(n_sub)])
    g_first = np.concatenate([[0], np.cumsum(np.bincount(g_row,
                                                         minlength=n))])
    g_index = row_off[g_row] + np.arange(g_row.size) - g_first[g_row]
    n_rows = int(row_off[-1])
    padded = _ceil_div(max(n_rows, 1), pad_rows_to) * pad_rows_to
    cols = np.full((padded, tau), PAD_COL, dtype=np.int32)
    vals = np.zeros((padded, tau), dtype=dtype)
    rmap = np.full((padded,), -1, dtype=np.int32)
    rmap[:n_rows] = np.repeat(np.arange(n, dtype=np.int32), n_sub)
    at = g_index[group]
    slot = np.arange(row_s.size) - g_start[group]
    cols[at, slot] = adj.indices[fill]
    vals[at, slot] = adj.data[fill]
    return TiledELL(cols=cols, vals=vals, row_map=rmap,
                    n_dense_rows=adj.cols, n_orig_rows=n)


def preprocess(
    adj: CSRMatrix,
    tau: int,
    tile_rows: int = 16,
    edge_cut: str = "rcm",
    pad_rows_to: int = 1,
    dtype=np.float32,
) -> PreprocessResult:
    """Full hybrid pipeline: edge-cut -> tiles -> vertex-cut -> ELL.

    The returned ELL carries *global* column indices (into the permuted dense
    operand) so a single kernel launch covers the whole matrix; the
    simulator's per-tile views come from :func:`partition_into_tiles` and
    :func:`vertex_cut_tile`, which cut the same sub-rows tile by tile.
    """
    from repro.obs.trace import span  # deferred: core imports no layer

    with span("preprocess.edge_cut"):
        perm = edge_cut_permutation(adj, edge_cut)
        padj = (apply_symmetric_permutation(adj, perm)
                if edge_cut != "none" else adj)
    with span("preprocess.vertex_cut"):
        row, sub, fill = vertex_cut_rows(padj, tau, tile_rows)
    with span("preprocess.ell"):
        ell = ell_from_vertex_cut(padj, row, sub, fill, tau,
                                  pad_rows_to=pad_rows_to, dtype=dtype)
    return PreprocessResult(ell=ell, perm=perm, tau=tau, tile_rows=tile_rows)


def hot_column_permutation(ell: TiledELL, n_hot: int) -> np.ndarray:
    """Beyond-tile analogue of the VRF fixed region (DESIGN.md §2).

    Returns a permutation of the dense rows placing the ``n_hot``
    highest-CNZ columns first, so they land in the leading k-tiles that stay
    VMEM-resident across the kernel's row-block grid axis.
    """
    valid = ell.cols != -1
    cnz = np.bincount(ell.cols[valid].ravel(), minlength=ell.n_dense_rows)
    order = np.argsort(-cnz, kind="stable")
    hot = order[:n_hot]
    cold = np.sort(order[n_hot:])
    return np.concatenate([hot, cold]).astype(np.int64)
