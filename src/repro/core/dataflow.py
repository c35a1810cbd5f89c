"""Hierarchical dataflow planner (paper Section V).

Two coordinated levels:

* **DRAM -> buffer: inner-product (output-stationary).**  The output tile
  stays resident in the Dense Buffer's Result region while partial products
  accumulate through the Temp region; the feature dimension is cut into
  f-tiles bounded by the buffer row width, and multi-buffering (factor m)
  overlaps the next tile group's DRAM loads with the current compute.

* **buffer -> VRF: row-wise product.**  Within a tile the sparse (sub-)rows
  stream through CMP against dense rows resident in the flexible VRF.

For the Pallas kernel the same plan materializes as the launch grid: the
k-tile axis is innermost (output-stationary accumulation), the feature axis
is outermost, and hot k-tiles lead so they stay VMEM-resident (DESIGN.md §2).
"""

from __future__ import annotations

import dataclasses
import jax
import numpy as np

from repro.core.sparse_formats import TiledELL, _ceil_div


@dataclasses.dataclass(frozen=True)
class BufferPlan:
    """DRAM–buffer level plan for the simulator."""

    f_tile: int          # feature columns per pass (fits Dense Buffer width)
    n_f_tiles: int
    m: int               # multi-buffer factor (m=2 double buffer, paper m=6)
    elem_bytes: int

    @property
    def overlapped(self) -> bool:
        return self.m >= 2


def plan_buffer(
    feature_dim: int,
    dense_buffer_bytes: int,
    tile_rows: int,
    m: int,
    elem_bytes: int = 1,
    rows_to_compute_frac: float = 0.5,
) -> BufferPlan:
    """Split the feature dimension so a tile group fits the Dense Buffer.

    The buffer is logically split into Rows-to-Compute / Result / Temp
    regions (Fig 4b); ``rows_to_compute_frac`` of the capacity feeds the
    VRF, the rest holds the output and partial-sum tiles.
    """
    rtc_bytes = int(dense_buffer_bytes * rows_to_compute_frac)
    per_buffer = max(rtc_bytes // max(m, 1), 1)
    # One buffered unit holds `tile_rows` dense rows of f_tile columns.
    f_tile = max(per_buffer // (tile_rows * elem_bytes), 1)
    f_tile = min(f_tile, feature_dim)
    return BufferPlan(
        f_tile=f_tile,
        n_f_tiles=_ceil_div(feature_dim, f_tile),
        m=m,
        elem_bytes=elem_bytes,
    )


@dataclasses.dataclass(frozen=True)
class KernelGrid:
    """Grid schedule for the Pallas kernel.

    The non-empty (row_block, k_tile) cells in output-stationary order:
    row block ``rb`` visits the k-tiles ``kb_ids[starts[rb]:starts[rb +
    1]]`` (hot k-tiles first with ``hot_k_first``), and every row block is
    visited at least once so the kernel zero-initializes its output there.
    A pytree over ``starts`` and ``kb_ids``, so a jitted step can take a
    planned grid's arrays as an argument.
    """

    block_rows: int
    block_k: int
    block_f: int
    starts: np.ndarray    # (n_row_blocks + 1,) int32 run offsets
    kb_ids: np.ndarray    # (n_steps,) int32 k-tile of each visit
    n_row_blocks: int
    n_k_tiles: int
    n_f_tiles: int
    density: float        # visited fraction of the dense grid
    hot_k_first: bool = True

    def fits(self, plan) -> bool:
        """Was this grid planned for ``plan``'s blocks and k-order?"""
        return (self.block_rows, self.block_k, self.hot_k_first) == (
            plan.block_rows, plan.block_k, plan.hot_k_first)

    @property
    def pairs(self) -> np.ndarray:
        """``(n_steps, 2)`` int32 ``[row_block, k_tile]`` per visit."""
        rb = np.repeat(np.arange(self.n_row_blocks, dtype=np.int32),
                       np.diff(self.starts))
        return np.stack([rb, self.kb_ids], axis=1)


jax.tree_util.register_dataclass(
    KernelGrid, data_fields=["starts", "kb_ids"],
    meta_fields=["block_rows", "block_k", "block_f", "n_row_blocks",
                 "n_k_tiles", "n_f_tiles", "density", "hot_k_first"])


def _k_order(ell: TiledELL, n_kb: int, block_k: int,
             hot_k_first: bool) -> np.ndarray:
    """k-tiles densest (hottest) first, ties in index order, so the
    leading tiles are shared across row blocks; else index order."""
    if not hot_k_first:
        return np.arange(n_kb)
    valid = ell.cols != -1
    counts = np.bincount((ell.cols[valid] // block_k).ravel(),
                         minlength=n_kb)
    return np.argsort(-counts, kind="stable")


def plan_kernel_grid(
    ell: TiledELL,
    feature_dim: int,
    block_rows: int = 128,
    block_k: int = 128,
    block_f: int = 128,
    skip_empty: bool = True,
    hot_k_first: bool = True,
) -> KernelGrid:
    """Build the compacted launch schedule from the ELL block occupancy."""
    occ = ell.block_occupancy(block_rows, block_k)
    n_rb, n_kb = occ.shape
    if not skip_empty:
        occ = np.ones_like(occ)
    k_order = _k_order(ell, n_kb, block_k, hot_k_first)
    # A row block with no occupied k-tile still gets one visit (to the
    # first tile of the order) to zero its output.
    occ = occ[:, k_order]
    if n_kb:
        occ[~occ.any(axis=1), 0] = True
    rb, pos = np.nonzero(occ)                 # row-major: rb, then order
    counts = np.bincount(rb, minlength=n_rb)
    starts = np.zeros(n_rb + 1, dtype=np.int32)
    np.cumsum(counts, out=starts[1:])
    return KernelGrid(
        block_rows=block_rows,
        block_k=block_k,
        block_f=block_f,
        starts=starts,
        kb_ids=k_order[pos].astype(np.int32),
        n_row_blocks=n_rb,
        n_k_tiles=n_kb,
        n_f_tiles=_ceil_div(feature_dim, block_f),
        density=float(starts[-1]) / float(max(n_rb * n_kb, 1)),
        hot_k_first=hot_k_first,
    )

