"""Operations and bytes a GCN forward needs, from its shapes alone.

Counts are algorithmic: from the normalized adjacency's nonzeros (self
loops included) and the published widths.  They never count the ELL
padding, the vertex-cut's extra sub-rows, the 128-lane feature padding
or a grid's empty cells, so they stay the same whatever kernel runs.
"""

from __future__ import annotations

from typing import Sequence

F32 = 4      # bytes of a stored value, an activation or a column index
INDEX = 4


def combination_flops(n: int, dims: Sequence[int]) -> float:
    """``X W`` of every layer: ``2 n d_in d_out`` each."""
    return float(sum(2 * n * a * b for a, b in zip(dims[:-1], dims[1:])))


def aggregation_flops(nnz: int, f: int) -> float:
    """One ``A_hat H`` with ``f`` columns: a multiply and an add per
    nonzero and column."""
    return float(2 * nnz * f)


def aggregation_bytes(n: int, nnz: int, f: int) -> float:
    """Values and column indices once, the dense rows read once, the
    output written once."""
    return float(nnz * (F32 + INDEX) + 2 * n * f * F32)


def forward_flops(n: int, nnz: int, dims: Sequence[int]) -> float:
    return combination_flops(n, dims) + sum(
        aggregation_flops(nnz, f) for f in dims[1:])


def aggregation_least_s(n: int, nnz: int, dims: Sequence[int],
                        peaks: dict) -> float:
    """Least time the chip could take for one forward's aggregations: per
    layer the larger of operations over peak FLOP/s and bytes over peak
    bandwidth."""
    return sum(
        max(aggregation_flops(nnz, f) / peaks["bf16_flops_per_s"],
            aggregation_bytes(n, nnz, f) / peaks["hbm_bytes_per_s"])
        for f in dims[1:])
