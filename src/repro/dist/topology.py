"""Mesh-shape planning.

``viable_mesh_shapes`` enumerates (data, model) factorizations of a chip
count.  The requested model-parallel width is an upper bound, not a
demand: when it does not divide the chip count the model axis degrades
downward until it does, so a job scheduled on an awkward slice (250 chips,
a prime count, fewer chips than the requested TP width) still gets a
legal mesh instead of an assertion failure.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax


def viable_mesh_shapes(n_chips: int,
                       model_parallel: int) -> List[Tuple[int, int]]:
    """All (data, model) shapes with data * model == n_chips and
    model <= model_parallel, widest model axis first."""
    if n_chips < 1:
        raise ValueError(f"n_chips must be >= 1, got {n_chips}")
    if model_parallel < 1:
        raise ValueError(
            f"model_parallel must be >= 1, got {model_parallel}")
    return [
        (n_chips // m, m)
        for m in range(min(model_parallel, n_chips), 0, -1)
        if n_chips % m == 0
    ]


def abstract_mesh(axis_sizes: Sequence[int],
                  axis_names: Sequence[str]) -> jax.sharding.AbstractMesh:
    """Device-free mesh for shape/sharding planning."""
    return jax.sharding.AbstractMesh(tuple(axis_sizes), tuple(axis_names))
