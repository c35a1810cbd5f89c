"""Production mesh construction.

Single pod: (16, 16) = 256 chips, axes (data, model).
Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model) — the pod axis
is pure data parallelism with int8 error-feedback gradient compression
across the inter-pod links (repro.train.compression).

Defined as functions (never module-level constants) so importing this
module touches no jax device state.
"""

from __future__ import annotations

from typing import Tuple

import jax
import numpy as np


def make_production_mesh(
    *, multi_pod: bool = False, data: int = 16, model: int = 16,
    pods: int = 2,
) -> jax.sharding.Mesh:
    """Build the (pod,) data, model mesh.

    The defaults reproduce the historical 16x16 / 2x16x16 cells; callers
    (``launch.dryrun``) now derive ``data``/``model`` from
    ``dist.topology.viable_mesh_shapes`` so awkward chip counts degrade
    the model axis instead of asserting.
    """
    shape = (pods, data, model) if multi_pod else (data, model)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Auto axes: model code places arrays with with_sharding_constraint
    # (dist.policy.constrain), which only Auto axes accept.
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_data_mesh(n_data: int) -> jax.sharding.Mesh:
    """1-axis ``data`` mesh over the first ``n_data`` local devices — the
    placement handle for sharded SpMM (``repro.exec``) and the serving
    batcher's request-granularity sharding."""
    devs = jax.devices()
    if n_data < 1 or n_data > len(devs):
        raise ValueError(
            f"n_data={n_data} not in [1, {len(devs)}] available devices"
        )
    return jax.sharding.Mesh(np.asarray(devs[:n_data]), ("data",))


def dp_axes(mesh: jax.sharding.Mesh) -> Tuple[str, ...]:
    """Axes that shard the batch (pod folds into data parallelism)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis(mesh: jax.sharding.Mesh) -> str:
    return "model"


def axis_size(mesh: jax.sharding.Mesh, name) -> int:
    if isinstance(name, (tuple, list)):
        out = 1
        for n in name:
            out *= mesh.shape[n]
        return out
    return mesh.shape[name]
