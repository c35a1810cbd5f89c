"""Logical-axis sharding constraints.

Model code never names a concrete mesh: it calls ``constrain(x, specs)``
with an ordered list of *candidate* partition specs (most-sharded first)
and the first candidate that is viable on the active mesh — every named
axis exists, no axis used twice, every named dim divisible — is applied
via ``with_sharding_constraint``.  With no active mesh (unit tests,
single-device smoke runs, vmap-emulated replicas) ``constrain`` is the
identity, so the same model code runs anywhere.

The active mesh is installed by ``sharding_policy(mesh)``, the context
manager the step builders in ``repro.launch.steps`` wrap around each
traced step.  State is thread-local: the dry-run driver traces cells from
a thread pool and each trace must see only its own mesh.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Iterator, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

AxisEntry = Union[str, Tuple[str, ...], None]
Spec = Sequence[AxisEntry]

_state = threading.local()


def active_mesh():
    """The mesh installed by the innermost ``sharding_policy``, or None."""
    return getattr(_state, "mesh", None)


@contextlib.contextmanager
def sharding_policy(mesh) -> Iterator[Optional[jax.sharding.Mesh]]:
    """Install ``mesh`` as the target of ``constrain`` calls underneath.

    ``mesh=None`` is valid and makes every ``constrain`` a no-op — the
    single-device / test configuration.
    """
    prev = getattr(_state, "mesh", None)
    _state.mesh = mesh
    try:
        yield mesh
    finally:
        _state.mesh = prev


def spec_viable(mesh, shape: Sequence[int], spec: Spec) -> bool:
    """True iff ``spec`` can legally shard an array of ``shape`` on ``mesh``."""
    if len(spec) > len(shape):
        return False
    used = set()
    for dim, axes in zip(shape, spec):
        if axes is None:
            continue
        names = axes if isinstance(axes, tuple) else (axes,)
        size = 1
        for n in names:
            if n not in mesh.shape or n in used:
                return False
            used.add(n)
            size *= mesh.shape[n]
        if dim % size:
            return False
    return True


def select_spec(mesh, shape: Sequence[int], specs: Sequence[Spec]):
    """First viable candidate spec, or None when nothing fits."""
    for spec in specs:
        if spec_viable(mesh, shape, spec):
            return P(*spec)
    return None


def _place(x: jax.Array, mesh, spec) -> jax.Array:
    """Apply ``spec`` on ``mesh``: a constraint on Auto axes, a reshard
    on Explicit ones (``jax.make_mesh`` makes Explicit axes by default,
    and ``with_sharding_constraint`` refuses them)."""
    sharding = NamedSharding(mesh, spec)
    if jax.sharding.AxisType.Explicit in tuple(mesh.axis_types):
        return jax.sharding.reshard(x, sharding)
    return jax.lax.with_sharding_constraint(x, sharding)


def constrain(x: jax.Array, specs: Sequence[Spec]) -> jax.Array:
    """Constrain ``x`` to the first viable candidate spec, if any."""
    mesh = active_mesh()
    if mesh is None:
        return x
    spec = select_spec(mesh, x.shape, specs)
    if spec is None:
        return x
    return _place(x, mesh, spec)


def constrain_ranked(x: jax.Array, specs: Sequence[Spec]) -> jax.Array:
    """Constrain ``x`` to the *cost-model-ranked* viable candidate.

    :func:`constrain` applies the first viable spec, so the caller's hand
    ordering IS the placement policy.  Here every viable candidate is
    scored by :func:`repro.plan.cost.rank_specs` (estimated per-device
    collective bytes to keep the array's replicas in sync) and the
    cheapest wins — with ties still broken by candidate order, so a list
    the cost model is indifferent about behaves exactly like
    :func:`constrain`.  This is the chooser for placements that decide a
    collective's shape, e.g. the MoE dispatch buffer whose sharding picks
    the token->expert all-to-all decomposition.
    """
    mesh = active_mesh()
    if mesh is None:
        return x
    viable = [s for s in specs if spec_viable(mesh, x.shape, s)]
    if not viable:
        return x
    from repro.plan.cost import rank_specs  # deferred: dist stays base-layer

    spec = viable[rank_specs(
        mesh, x.shape, viable, dtype_bytes=x.dtype.itemsize)]
    return _place(x, mesh, P(*spec))
