"""The one cost model behind every plan decision.

FlexVector's co-design claim (PAPER.md §IV–V) is that preprocessing and
partitioning are *chosen to match* the hardware — VRF capacity, the
row-wise dataflow, DRAM bandwidth — rather than fixed by heuristics.
Before this module the repo had four independent plan-selection sites
(``exec.SpmmPlan`` defaults, ``dist.sharding`` first-viable candidate
order, the serving bucket ladder, ``exec.sharded``'s uniform sub-row
split) while the traffic terms that should drive them sat stranded in the
roofline report and the PPA simulator.  ``repro.plan.cost`` extracts
those terms into pure functions over graph statistics and a device model
so every chooser ranks its candidates with the same arithmetic:

* :func:`spmm_cost`        — DRAM bytes, SRAM energy (via
  ``sim.hw_config.sram_pj_per_byte``), collective bytes and FLOPs for one
  planned SpMM, per impl / block sizes / shard count;
* :func:`roofline_seconds` — the compute/memory/collective roofline bound
  (the arithmetic ``repro.roofline.analysis`` now delegates to);
* :func:`rank_specs`       — estimated gradient-sync collective bytes of
  candidate partition specs (``dist.sharding``'s chooser);
* :func:`balanced_split_points` — contiguous split of a weighted row axis
  (``exec.sharded``'s nnz-weighted sub-row split).

Everything here is numpy + dataclasses: no jax, no device state, so the
model is usable at trace time, in tests, and from the benchmarks alike.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence, Tuple

import numpy as np

from repro.core.sparse_formats import PAD_COL, TiledELL
from repro.sim.hw_config import HWConfig, PJ_PER_BYTE_DRAM, sram_pj_per_byte


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _round_up(x: int, q: int) -> int:
    return _ceil_div(max(x, 0), q) * q


# Storage widths of the ``exec.quant`` precisions: the stored value width
# and the activation (dense operand / writeback) width — int8 keeps
# activations in bf16, hence the asymmetry.
_PRECISION_BYTES = {"f32": 4, "bf16": 2, "int8": 1}
_PRECISION_ACT_BYTES = {"f32": 4, "bf16": 2, "int8": 2}


# ---------------------------------------------------------------------------
# Device model
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DeviceModel:
    """Per-chip peaks + energy constants the cost terms are normalized by.

    ``step_overhead_s`` charges each visited kernel grid step a fixed
    launch/setup cost (the ASIC's per-tile ``c_setup`` analogue); it is
    what keeps the block-size argmin away from degenerate tiny tiles.
    """

    name: str = "tpu-v5e"
    peak_flops: float = 197e12           # bf16 FLOP/s per chip
    hbm_bw: float = 819e9                # bytes/s per chip
    ici_bw: float = 50e9                 # bytes/s per link
    hbm_capacity_bytes: float = 16e9
    dram_pj_per_byte: float = PJ_PER_BYTE_DRAM
    dense_buffer_bytes: int = 2048       # SRAM-energy anchor (HWConfig)
    sparse_buffer_bytes: int = 256
    step_overhead_s: float = 2e-9

    def bytes_per_element(self, dtype) -> int:
        """Stored bytes per element, the one element-size helper every
        traffic term routes through (no more hardcoded f32 fours).

        Accepts ``exec.quant`` precision names (``"f32"``/``"bf16"``/
        ``"int8"``) and anything ``np.dtype`` understands (including
        ml_dtypes' bfloat16 class).
        """
        if isinstance(dtype, str) and dtype in _PRECISION_BYTES:
            return _PRECISION_BYTES[dtype]
        return int(np.dtype(dtype).itemsize)


TPU_V5E = DeviceModel()


def flexvector_device(hw: Optional[HWConfig] = None) -> DeviceModel:
    """Device model of the paper's FlexVector tile (Section VI-A3)."""
    hw = hw or HWConfig()
    return DeviceModel(
        name="flexvector",
        peak_flops=2.0 * hw.lanes * hw.freq_hz,
        hbm_bw=hw.dram_bw_bytes_per_s,
        ici_bw=hw.dram_bw_bytes_per_s,   # single tile: no ICI, DRAM-bound
        hbm_capacity_bytes=1e12,
        dram_pj_per_byte=hw.dram_pj_per_bit * 8,
        dense_buffer_bytes=hw.dense_buffer_bytes,
        sparse_buffer_bytes=hw.sparse_buffer_bytes,
        step_overhead_s=hw.c_setup / hw.freq_hz,
    )


# ---------------------------------------------------------------------------
# Graph statistics
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class GraphStats:
    """The sparse-operand statistics every cost term is a function of."""

    padded_rows: int            # ELL rows incl. block padding
    n_sub_rows: int             # real (row_map >= 0) vertex-cut sub-rows
    n_out_rows: int             # original output rows
    n_dense_rows: int           # K dimension
    nnz: int
    tau: int
    row_nnz: Optional[np.ndarray] = None   # (padded_rows,) valid counts
    ell: Optional[TiledELL] = None         # exact block occupancy, if host
    # occupancy memo: the O(nnz) block_occupancy scan depends only on
    # (block_rows, block_k), but autoplan scores ~20 (block_f, width)
    # candidates per pair — without the memo every one re-scans the graph
    _occ_cache: dict = dataclasses.field(
        default_factory=dict, repr=False, compare=False)

    @property
    def rows_per_node(self) -> int:
        """Vertex-cut expansion factor: padded sub-rows per output row —
        the serving bucket ladder's ELL-row budget per node."""
        return _ceil_div(self.padded_rows, max(self.n_out_rows, 1))

    @property
    def mean_row_nnz(self) -> float:
        return self.nnz / max(self.n_sub_rows, 1)

    def occupied_pairs(self, block_rows: int, block_k: int) -> int:
        """Non-empty (row-block, k-tile) cells of the launch grid.

        Exact via ``TiledELL.block_occupancy`` when the host container is
        available; otherwise the spread upper bound min(grid, nnz).
        """
        key = (block_rows, block_k)
        hit = self._occ_cache.get(key)
        if hit is not None:
            return hit
        n_rb = _ceil_div(self.padded_rows, block_rows)
        n_kb = _ceil_div(self.n_dense_rows, block_k)
        if self.ell is not None:
            pairs = int(self.ell.block_occupancy(block_rows, block_k).sum())
        else:
            pairs = int(min(n_rb * n_kb, max(self.nnz, n_rb)))
        self._occ_cache[key] = pairs
        return pairs


def graph_stats_from_ell(ell: TiledELL) -> GraphStats:
    """Exact stats of a preprocessed bounded-row operand."""
    valid = ell.cols != PAD_COL
    return GraphStats(
        padded_rows=ell.padded_rows,
        n_sub_rows=int((ell.row_map >= 0).sum()),
        n_out_rows=ell.n_orig_rows,
        n_dense_rows=ell.n_dense_rows,
        nnz=int(valid.sum()),
        tau=ell.tau,
        row_nnz=valid.sum(axis=1).astype(np.int64),
        ell=ell,
    )


def synthetic_stats(
    rows: int,
    n_out_rows: int,
    n_dense_rows: int,
    nnz: int,
    tau: int,
) -> GraphStats:
    """Stats for a shape that exists only as a plan (e.g. a serving bucket
    rung before any request has landed in it)."""
    return GraphStats(
        padded_rows=rows,
        n_sub_rows=rows,
        n_out_rows=n_out_rows,
        n_dense_rows=n_dense_rows,
        nnz=int(min(nnz, rows * tau)),
        tau=tau,
    )


# ---------------------------------------------------------------------------
# SpMM cost terms
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CostBreakdown:
    """Traffic / energy / time estimate of one planned SpMM."""

    flops: float                 # total useful+padded MACs x2
    dram_bytes: float            # total DRAM traffic, all shards
    collective_bytes: float      # per-device cross-shard bytes
    sram_pj: float               # on-chip buffer energy
    dram_pj: float
    compute_s: float             # per-device roofline terms
    memory_s: float
    collective_s: float
    dominant: str

    @property
    def seconds(self) -> float:
        """The roofline bound — the scalar every argmin minimizes."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def energy_pj(self) -> float:
        return self.sram_pj + self.dram_pj


def roofline_seconds(
    flops_per_device: float,
    bytes_per_device: float,
    coll_bytes_per_device: float,
    device: DeviceModel = TPU_V5E,
) -> Tuple[float, float, float, str]:
    """compute/memory/collective roofline terms + the dominant one.

    This is the term arithmetic of the dry-run roofline report
    (``repro.roofline.analysis`` delegates here).
    """
    compute = flops_per_device / device.peak_flops
    memory = bytes_per_device / device.hbm_bw
    collective = coll_bytes_per_device / device.ici_bw
    terms = {"compute": compute, "memory": memory, "collective": collective}
    return compute, memory, collective, max(terms, key=terms.get)


def psum_bytes(n_out_rows: int, feature_dim: int, n_shards: int,
               dtype_bytes: int = 4) -> float:
    """Per-device bytes of the full-height cross-shard segment-psum that
    folds vertex-cut partials (ring all-reduce: 2(n-1)/n of the buffer)."""
    if n_shards <= 1:
        return 0.0
    buf = float(n_out_rows) * feature_dim * dtype_bytes
    return 2.0 * buf * (n_shards - 1) / n_shards


def reduce_scatter_bytes(n_out_rows: int, feature_dim: int, n_shards: int,
                         dtype_bytes: int = 4) -> float:
    """Per-device bytes of the row-sharded epilogue
    (``segment_reduce_scatter``): ring reduce-scatter moves (n-1)/n of the
    buffer — half the all-reduce — over the *padded* output height
    (``round_up`` to the axis width, the height the next layer consumes)."""
    if n_shards <= 1:
        return 0.0
    buf = float(_round_up(n_out_rows, n_shards)) * feature_dim * dtype_bytes
    return buf * (n_shards - 1) / n_shards


def all_gather_bytes(n_rows: int, feature_dim: int, n_shards: int,
                     dtype_bytes: int = 4) -> float:
    """Per-device bytes to all-gather a row-sharded dense operand inside
    the shard body (ring all-gather: (n-1)/n of the full buffer)."""
    if n_shards <= 1:
        return 0.0
    buf = float(_round_up(n_rows, n_shards)) * feature_dim * dtype_bytes
    return buf * (n_shards - 1) / n_shards


def activation_writeback_bytes(
    n_out_rows: int,
    feature_dim: int,
    n_shards: int,
    layout: str = "replicated",
    dtype_bytes: int = 4,
) -> float:
    """Total DRAM bytes the mesh writes to materialize one layer's output
    activation under ``layout``: a replicated activation is written by
    *every* device (n x the full height), a row-sharded one is written
    once across the mesh (the padded height).  This is the term that makes
    keeping activations sharded between layers win in the pipeline DP even
    before counting the halved collective."""
    n = max(n_shards, 1)
    if layout == "row_sharded" and n > 1:
        return float(_round_up(n_out_rows, n)) * feature_dim * dtype_bytes
    return float(n) * n_out_rows * feature_dim * dtype_bytes


def spmm_cost(
    stats: GraphStats,
    feature_dim: int,
    *,
    impl: str = "reference",
    block_rows: int = 128,
    block_k: int = 128,
    block_f: int = 128,
    n_shards: int = 1,
    out_layout: str = "replicated",
    dense_layout: str = "replicated",
    shard_imbalance: float = 1.0,
    dtype_bytes: int = 4,
    idx_bytes: int = 4,
    precision: str = "f32",
    device: DeviceModel = TPU_V5E,
) -> CostBreakdown:
    """Traffic/energy/time estimate of ``A @ D`` under one plan.

    Per-impl traffic model (D is ``(K, F)``):

    * ``reference`` — XLA gather: one dense row read per nonzero (no tile
      reuse), no padding inflation;
    * ``pallas`` — masked dense grid: every (row-block, k-tile) pair is
      visited, so compute and sparse-operand reads scale with the *padded*
      grid and each row block re-streams its tau slots per k-tile;
    * ``pallas_sparse`` — block-skipping grid: only occupied pairs are
      visited (exact occupancy when the host ``TiledELL`` is available).

    Sharding divides compute/DRAM terms across ``n_shards`` and adds the
    epilogue collective term: the full-height segment-psum by default, or
    — ``out_layout="row_sharded"`` — the reduce-scatter at half the bytes;
    ``dense_layout="row_sharded"`` adds the in-body all-gather of the
    dense operand.  ``shard_imbalance`` (``split_imbalance`` of the chosen
    sub-row split, >= 1.0) scales the per-device compute/memory terms: the
    roofline waits on the heaviest shard, not the mean one.

    ``precision`` sizes every traffic term with the ``exec.quant``
    storage widths: stored ELL values at 1 (int8) or 2 (bf16) bytes plus
    the int8 per-row-block scale vector, activations (the dense operand,
    the writeback, the all-gathered prologue) at 2 bytes under bf16/int8.
    The reduction collectives still move f32 accumulator partials
    (``dtype_bytes``), matching what ``exec.sharded`` actually psums.
    """
    f = max(feature_dim, 1)
    r_pad = _round_up(stats.padded_rows, block_rows)
    k_pad = _round_up(stats.n_dense_rows, block_k)
    f_pad = _round_up(f, block_f)
    n_rb = _ceil_div(r_pad, block_rows)
    n_kb = _ceil_div(k_pad, block_k)
    n_fb = _ceil_div(f_pad, block_f)
    if precision == "f32":
        val_bytes, act_bytes = dtype_bytes, dtype_bytes
    else:
        val_bytes = device.bytes_per_element(precision)
        act_bytes = _PRECISION_ACT_BYTES[precision]
    ell_entry_bytes = idx_bytes + val_bytes
    scale_bytes = n_rb * 4.0 if precision == "int8" else 0.0

    if impl == "reference":
        visited = n_rb * n_kb   # no grid actually runs; reuse for overhead=0
        flops = 2.0 * stats.nnz * f
        dense_bytes = float(stats.nnz) * f * act_bytes   # gather, no reuse
        sparse_bytes = float(stats.nnz) * ell_entry_bytes + scale_bytes
        grid_steps = 0
    else:
        if impl == "pallas":
            visited = n_rb * n_kb
        elif impl == "pallas_sparse":
            visited = stats.occupied_pairs(block_rows, block_k)
        else:
            raise ValueError(f"unknown impl for cost model: {impl}")
        # each visited pair processes block_rows x tau slots per f-tile
        flops = 2.0 * visited * block_rows * stats.tau * f_pad
        dense_bytes = float(visited) * block_k * f_pad * act_bytes
        sparse_bytes = (
            float(visited) * n_fb * block_rows * stats.tau * ell_entry_bytes
            + scale_bytes
        )
        grid_steps = visited * n_fb

    out_bytes = float(r_pad + stats.n_out_rows) * f * act_bytes
    dram_bytes = dense_bytes + sparse_bytes + out_bytes
    if out_layout == "row_sharded":
        coll_bytes = reduce_scatter_bytes(
            stats.n_out_rows, f, n_shards, dtype_bytes)
    else:
        coll_bytes = psum_bytes(stats.n_out_rows, f, n_shards, dtype_bytes)
    if dense_layout == "row_sharded":
        coll_bytes += all_gather_bytes(
            stats.n_dense_rows, f, n_shards, act_bytes)

    shards = max(n_shards, 1)
    imb = max(float(shard_imbalance), 1.0)
    compute, memory, collective, dominant = roofline_seconds(
        flops / shards * imb, dram_bytes / shards * imb, coll_bytes, device
    )
    compute += (grid_steps / shards) * imb * device.step_overhead_s
    if compute > max(memory, collective):
        dominant = "compute"
    return CostBreakdown(
        flops=flops,
        dram_bytes=dram_bytes,
        collective_bytes=coll_bytes,
        sram_pj=(dense_bytes + out_bytes)
        * sram_pj_per_byte(device.dense_buffer_bytes)
        + sparse_bytes * sram_pj_per_byte(device.sparse_buffer_bytes),
        dram_pj=dram_bytes * device.dram_pj_per_byte,
        compute_s=compute,
        memory_s=memory,
        collective_s=collective,
        dominant=dominant,
    )


def combination_seconds(
    k_rows: int,
    f_in: int,
    f_out: int,
    *,
    n_shards: int = 1,
    precision: str = "f32",
    device: DeviceModel = TPU_V5E,
) -> float:
    """Roofline seconds of the standalone dense combination launch
    ``X @ W + b`` — one read of ``X`` and ``W``, one write of the
    intermediate ``XW`` activation (its read-back is charged to the
    aggregation's dense-operand term in :func:`spmm_cost`).  Row-sharded
    stacks run the matmul on local rows, so compute and traffic divide
    across ``n_shards``."""
    act_b = _PRECISION_ACT_BYTES.get(precision, 4)
    val_b = _PRECISION_BYTES.get(precision, 4)
    flops = 2.0 * k_rows * f_in * f_out
    dram = (
        float(k_rows) * f_in * act_b
        + float(f_in) * f_out * val_b
        + float(k_rows) * f_out * act_b
    )
    shards = max(n_shards, 1)
    compute, memory, _, _ = roofline_seconds(
        flops / shards, dram / shards, 0.0, device
    )
    return max(compute, memory)


def bucket_forward_seconds(
    rows: int,
    n_out_rows: int,
    mean_row_nnz: float,
    tau: int,
    f_dims: Sequence[int],
    *,
    impl: str = "reference",
    block_rows: int = 128,
    block_k: int = 128,
    block_f: int = 128,
    precision: str = "f32",
    device: "DeviceModel" = None,
) -> float:
    """Roofline seconds of one forward over a *planned* serving-bucket
    shape: ``rows`` ELL sub-rows at the graph's mean occupancy, one SpMM
    per entry of ``f_dims`` (each layer's output width).

    The single bucket-cost arithmetic behind both the runtime's admission
    estimator (``repro.runtime.queue.BucketEstimator``) and the ladder
    growth search (``repro.plan.autoplan.choose_ladder_growth``) — the
    two must price a rung with the same model or admission and ladder
    selection disagree.  ``pallas_sparse`` is priced as ``pallas``: a
    bucket exists only as a plan, with no host operand to schedule the
    block-skipping grid from.
    """
    device = device or TPU_V5E
    stats = synthetic_stats(
        rows=rows,
        n_out_rows=n_out_rows,
        n_dense_rows=n_out_rows,
        nnz=max(int(rows * mean_row_nnz), 1),
        tau=tau,
    )
    impl = "pallas" if impl == "pallas_sparse" else impl
    return sum(
        spmm_cost(
            stats, f, impl=impl, block_rows=block_rows, block_k=block_k,
            block_f=block_f, precision=precision, device=device,
        ).seconds
        for f in f_dims
    )


# ---------------------------------------------------------------------------
# Weighted contiguous splits (exec.sharded's sub-row partitioner)
# ---------------------------------------------------------------------------


def balanced_split_points(
    weights: Sequence[float], n_parts: int
) -> np.ndarray:
    """Boundaries of the contiguous split of a weighted axis into
    ``n_parts`` segments that minimizes the heaviest segment.

    Returns ``n_parts + 1`` nondecreasing offsets starting at 0 and ending
    at ``len(weights)``.  Exact minimax (binary search on the segment
    capacity, greedy fill per probe — O(n_parts log n) per probe on the
    cumulative sum), so the result is never worse-balanced than the
    uniform equal-count split; on a power-law row-nnz distribution it is
    dramatically better.  Zero-weight rows (ELL padding) are free to land
    on either side of a boundary; an all-zero weight vector degrades to
    the uniform split.  Deterministic: pure arithmetic, no RNG.
    """
    w = np.asarray(weights, dtype=np.float64)
    n = len(w)
    if n_parts < 1:
        raise ValueError(f"n_parts must be >= 1, got {n_parts}")
    total = float(w.sum())
    if total <= 0.0:
        base = _ceil_div(max(n, 1), n_parts)
        return np.minimum(np.arange(n_parts + 1, dtype=np.int64) * base, n)
    cum = np.cumsum(w)

    def greedy(cap: float) -> np.ndarray:
        """Cut offsets filling every segment up to ``cap`` (cap >= max(w));
        feasible iff the last offset reaches ``n``."""
        bounds = np.empty(n_parts + 1, dtype=np.int64)
        bounds[0] = 0
        base = 0.0
        for s in range(1, n_parts + 1):
            j = min(int(np.searchsorted(cum, base + cap, side="right")), n)
            bounds[s] = j
            base = cum[j - 1] if j > 0 else 0.0
        return bounds

    lo = max(float(w.max()), total / n_parts)   # minimax lower bound
    hi = total                                  # one segment always fits
    for _ in range(64):
        mid = 0.5 * (lo + hi)
        if greedy(mid)[-1] >= n:
            hi = mid
        else:
            lo = mid
    bounds = greedy(hi)
    bounds[-1] = n
    return np.maximum.accumulate(bounds)


def split_imbalance(weights: Sequence[float], bounds: np.ndarray) -> float:
    """max-segment / mean-segment weight ratio (1.0 = perfectly balanced).

    Cumulative-sum differences rather than ``reduceat`` so empty segments
    (a hub-dominated split can leave trailing shards with zero rows)
    contribute 0 instead of indexing past the array.
    """
    w = np.asarray(weights, dtype=np.float64)
    cum = np.concatenate(([0.0], np.cumsum(w)))
    bounds = np.asarray(bounds, dtype=np.int64)
    seg = cum[bounds[1:]] - cum[bounds[:-1]]
    mean = w.sum() / max(len(bounds) - 1, 1)
    return float(seg.max() / mean) if mean > 0 else 1.0


# ---------------------------------------------------------------------------
# Partition-spec scoring (dist.sharding's chooser)
# ---------------------------------------------------------------------------


def _mesh_sizes(mesh) -> dict:
    return dict(mesh.shape)


def spec_shard_factor(mesh, spec: Sequence) -> int:
    """Number of distinct shards a spec cuts an array into."""
    sizes = _mesh_sizes(mesh)
    factor = 1
    for entry in spec:
        if entry is None:
            continue
        names = entry if isinstance(entry, tuple) else (entry,)
        for name in names:
            factor *= int(sizes[name])
    return factor


def grad_sync_bytes(mesh, shape: Sequence[int], spec: Sequence,
                    dtype_bytes: int = 4) -> float:
    """Estimated per-device collective bytes to keep one leaf in sync.

    A leaf sharded ``factor`` ways is replicated across ``N / factor``
    devices; each step its replicated bytes ride a ring all-reduce
    (gradient sync / cache coherence): ``2 * (bytes/factor) * (r-1)/r``.
    Strictly decreasing in the shard factor, so the argmin prefers the
    most-sharded viable candidate — with ties broken by candidate order,
    preserving the historical first-viable semantics.
    """
    n_devices = int(math.prod(_mesh_sizes(mesh).values()))
    leaf_bytes = float(math.prod(shape) if len(shape) else 1) * dtype_bytes
    factor = spec_shard_factor(mesh, spec)
    replicas = max(n_devices // max(factor, 1), 1)
    return 2.0 * (leaf_bytes / max(factor, 1)) * (replicas - 1) / replicas


def rank_specs(mesh, shape: Sequence[int], specs: Sequence[Sequence],
               dtype_bytes: int = 4) -> int:
    """Index of the cheapest candidate spec by estimated collective bytes.

    Stable: earlier candidates win ties, so callers that order candidates
    most-preferred-first keep their historical choice whenever the cost
    model is indifferent.
    """
    if not specs:
        raise ValueError("rank_specs needs at least one candidate")
    best_idx, best_cost = 0, None
    for i, spec in enumerate(specs):
        c = grad_sync_bytes(mesh, shape, spec, dtype_bytes)
        if best_cost is None or c < best_cost:
            best_idx, best_cost = i, c
    return best_idx
