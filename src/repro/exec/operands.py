"""SpMM operand containers and the per-shard sub-row splitter.

:class:`SpmmOperands` unifies the two historical entry shapes — the
host-side :class:`~repro.core.sparse_formats.TiledELL` container and the
bare (possibly traced) ELL array triple — behind one object.  Keeping the
host container around when it exists is what lets the dispatcher plan the
block-skipping ``pallas_sparse`` schedule; bare arrays resolve to the
masked dense grid instead (see ``exec.plan``).

:func:`shard_operands` splits the sub-row axis into contiguous slices,
one per ``data``-axis shard.  Sub-rows are the vertex-cut unit of work
(each contiguous run of sub-rows is a run of vertex-cut partitions), so a
contiguous split maps partitions 1:1 onto shards; every shard
segment-accumulates its local partial products and the sharded executor
reduces them with a cross-shard psum.  The boundaries are nnz-weighted by
default (``repro.plan.cost.balanced_split_points``): on power-law graphs
a uniform row count per shard leaves the hub-owning shard with most of
the nonzeros, and the whole psum waits on it.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import numpy as np

from repro.core.dataflow import KernelGrid, plan_kernel_grid
from repro.core.sparse_formats import PAD_COL, TiledELL
from repro.plan import cost


def planned_grid(ell: Optional[TiledELL], plan) -> KernelGrid:
    """The ``pallas_sparse`` schedule of ``ell`` for ``plan``'s blocks."""
    if ell is None:
        raise ValueError("no planned grid fits the plan's blocks, and no "
                         "host TiledELL to plan one from")
    return plan_kernel_grid(
        ell, plan.block_f, block_rows=plan.block_rows, block_k=plan.block_k,
        block_f=plan.block_f, hot_k_first=plan.hot_k_first)


@dataclasses.dataclass(frozen=True)
class SpmmOperands:
    """The sparse side of one SpMM: ELL triple + output row count.

    ``ell`` keeps the host container when the caller had one — it is the
    scheduling handle for ``pallas_sparse`` grid compaction and the
    source of ``n_dense_rows`` for per-shard occupancy planning.

    ``grid`` is an already planned ``pallas_sparse`` schedule; with it
    the operands are schedulable without the host container, so a jitted
    step can take every graph operand as an argument.

    ``precision`` describes how ``vals`` is *stored* (``exec.quant``
    semantics): f32 vals may still be executed under a quantized plan
    (the dispatcher casts/quantizes at trace time), while int8 vals
    carry their per-row-block ``scales`` (granularity
    ``scale_block_rows``) from a prebuilt quantized artifact.
    """

    cols: jax.typing.ArrayLike      # (R, tau) int32, PAD_COL padding
    vals: jax.typing.ArrayLike      # (R, tau)
    row_map: jax.typing.ArrayLike   # (R,) int32, -1 padding
    n_out_rows: int
    ell: Optional[TiledELL] = None
    scales: Optional[jax.typing.ArrayLike] = None  # (ceil(R/sbr),) f32
    scale_block_rows: Optional[int] = None
    precision: str = "f32"
    grid: Optional[KernelGrid] = None

    @property
    def schedulable(self) -> bool:
        """A ``pallas_sparse`` schedule planned, or plannable on the host
        (TiledELL available)?"""
        return self.ell is not None or self.grid is not None

    @property
    def concrete(self) -> bool:
        """True when the arrays are host data rather than tracers."""
        return not any(
            isinstance(a, jax.core.Tracer)
            for a in (self.cols, self.vals, self.row_map)
        )

    @staticmethod
    def from_ell(ell: TiledELL) -> "SpmmOperands":
        return SpmmOperands(
            cols=ell.cols,
            vals=ell.vals,
            row_map=ell.row_map,
            n_out_rows=ell.n_orig_rows,
            ell=ell,
        )

    @staticmethod
    def from_arrays(cols, vals, row_map, n_out_rows: int) -> "SpmmOperands":
        return SpmmOperands(
            cols=cols, vals=vals, row_map=row_map, n_out_rows=n_out_rows
        )


@dataclasses.dataclass(frozen=True)
class ShardedOperands:
    """Shard-major operand layout: shard ``s`` owns rows
    ``[s * rows_per_shard, (s+1) * rows_per_shard)`` of the flat arrays."""

    cols: np.ndarray      # (n_shards * rows_per_shard, tau)
    vals: np.ndarray
    row_map: np.ndarray   # (n_shards * rows_per_shard,)
    n_out_rows: int
    n_shards: int
    rows_per_shard: int
    shard_ells: Tuple[TiledELL, ...]  # per-shard host views ((), if no ell)


def _round_up(x: int, q: int) -> int:
    return -(-x // q) * q


def shard_operands(
    operands: SpmmOperands,
    n_shards: int,
    block_rows: int,
    split: str = "nnz",
) -> ShardedOperands:
    """Split the sub-row axis into ``n_shards`` contiguous slices.

    ``split="nnz"`` (default) places the boundaries with the cost model's
    weighted splitter so every shard owns ~the same number of nonzeros —
    the load-balance fix for power-law rows; ``split="uniform"`` is the
    historical equal-row-count split (kept for parity tests and as the
    fallback when no nonzero counts exist).  Either way every slice is
    padded to the same block-aligned ``rows_per_shard`` (PAD_COL cols,
    zero vals, -1 row_map) so the shards run one identical program on
    different data.
    """
    if not operands.concrete:
        raise TypeError(
            "shard_operands needs concrete (host) operands: the per-shard "
            "split and grid schedules are planned host-side"
        )
    if split not in ("nnz", "uniform"):
        raise ValueError(f"unknown split: {split}")
    cols = np.asarray(operands.cols)
    vals = np.asarray(operands.vals)
    rmap = np.asarray(operands.row_map)
    r, tau = cols.shape
    if split == "nnz":
        weights = (cols != PAD_COL).sum(axis=1)
        bounds = cost.balanced_split_points(weights, n_shards)
    else:
        bounds = cost.balanced_split_points(np.zeros(r), n_shards)
    seg_len = int(np.diff(bounds).max()) if n_shards else 0
    per = _round_up(max(seg_len, 1), block_rows)
    out_cols = np.full((n_shards * per, tau), PAD_COL, dtype=np.int32)
    out_vals = np.zeros((n_shards * per, tau), dtype=vals.dtype)
    out_rmap = np.full((n_shards * per,), -1, dtype=np.int32)
    shard_ells = []
    for s in range(n_shards):
        lo, hi = int(bounds[s]), int(bounds[s + 1])
        n = max(hi - lo, 0)
        out_cols[s * per : s * per + n] = cols[lo:hi]
        out_vals[s * per : s * per + n] = vals[lo:hi]
        out_rmap[s * per : s * per + n] = rmap[lo:hi]
        if operands.ell is not None:
            shard_ells.append(
                TiledELL(
                    cols=out_cols[s * per : (s + 1) * per],
                    vals=out_vals[s * per : (s + 1) * per],
                    row_map=out_rmap[s * per : (s + 1) * per],
                    n_dense_rows=operands.ell.n_dense_rows,
                    n_orig_rows=operands.n_out_rows,
                )
            )
    return ShardedOperands(
        cols=out_cols,
        vals=out_vals,
        row_map=out_rmap,
        n_out_rows=operands.n_out_rows,
        n_shards=n_shards,
        rows_per_shard=per,
        shard_ells=tuple(shard_ells),
    )
