"""SpMM execution plans.

An :class:`SpmmPlan` captures every launch decision once — impl choice,
block sizes, interpret mode, device placement — so the entry points in
``repro.core.spmm`` stay thin wrappers and the serving batcher, the GCN
forward and the benchmarks all dispatch through the same pipeline.

Plans are *resolved* before execution: :meth:`SpmmPlan.resolve` pins the
impl that will actually run.  The one impl that can change under
resolution is ``pallas_sparse``: its block-skipping launch schedule needs
host-side occupancy planning over the :class:`TiledELL` container, which
is unavailable when the operands are bare (possibly traced) arrays — the
plan then degrades to the masked dense grid (``pallas``), emits a
one-time warning, and records the degradation so callers and benchmarks
can see which impl actually ran instead of being silently switched.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Optional

import jax

VALID_IMPLS = ("reference", "pallas", "pallas_sparse")
VALID_LAYOUTS = ("replicated", "row_sharded")
VALID_PRECISIONS = ("f32", "bf16", "int8")

# One-time warning registry: reasons already surfaced to the user.
_DEGRADE_WARNED: set = set()


def _warn_once(reason: str) -> None:
    if reason not in _DEGRADE_WARNED:
        _DEGRADE_WARNED.add(reason)
        warnings.warn(reason, RuntimeWarning, stacklevel=4)


def reset_degradation_warnings() -> None:
    """Clear the process-global warn-once registry.

    The registry is deliberately global (a degradation should be surfaced
    once per process, not once per call site), which makes warn-once
    assertions order-dependent under pytest; the autouse fixture in
    ``tests/conftest.py`` calls this before every test so each starts from
    a clean registry.
    """
    _DEGRADE_WARNED.clear()


@dataclasses.dataclass(frozen=True)
class SpmmPlan:
    """Immutable execution plan for one SpMM configuration.

    ``mesh``/``data_axis`` give the device placement: a mesh whose
    ``data`` axis is wider than one device routes :func:`execute` through
    the sharded path (``exec.sharded``); no mesh — or a trivial 1-device
    one — runs single-device.  ``effective_impl``/``degraded_reason`` are
    the resolution record; they are ``None`` on an unresolved plan.

    ``dense_layout``/``out_layout`` pick the sharded path's prologue and
    epilogue: a ``row_sharded`` output is produced with a reduce-scatter
    (each shard keeps its contiguous slice of output rows — the layout a
    following sharded layer consumes), a ``row_sharded`` dense operand is
    all-gathered inside the shard body.  Both degrade to ``replicated``
    semantics on a 1-wide data axis.  ``feature_axis`` names a second
    mesh axis to split the dense operand's feature dimension over (each
    feature-shard computes the full row space for its F slice; the
    output stays feature-sharded, the gather implicit in its layout).
    """

    impl: str = "reference"
    block_rows: int = 128
    block_k: int = 128
    block_f: int = 128
    interpret: Optional[bool] = None
    hot_k_first: bool = True          # sparse-grid schedule: hot k-tiles lead
    out_dtype: Optional[object] = None  # kernel accumulator override
    mesh: Optional[jax.sharding.Mesh] = None
    data_axis: str = "data"
    shard_split: str = "nnz"          # sub-row split: nnz-weighted | uniform
    dense_layout: str = "replicated"  # dense operand: replicated | row_sharded
    out_layout: str = "replicated"    # epilogue: psum | reduce-scatter
    feature_axis: Optional[str] = None  # mesh axis splitting the F dimension
    precision: str = "f32"            # storage precision: f32 | bf16 | int8
    effective_impl: Optional[str] = None
    degraded_reason: Optional[str] = None

    def __post_init__(self):
        if self.impl not in VALID_IMPLS:
            raise ValueError(
                f"unknown impl: {self.impl} (expected one of {VALID_IMPLS})"
            )
        if self.shard_split not in ("nnz", "uniform"):
            raise ValueError(
                f"unknown shard_split: {self.shard_split} "
                "(expected 'nnz' or 'uniform')"
            )
        for name in ("dense_layout", "out_layout"):
            if getattr(self, name) not in VALID_LAYOUTS:
                raise ValueError(
                    f"unknown {name}: {getattr(self, name)} "
                    f"(expected one of {VALID_LAYOUTS})"
                )
        if self.precision not in VALID_PRECISIONS:
            raise ValueError(
                f"unknown precision: {self.precision} "
                f"(expected one of {VALID_PRECISIONS})"
            )

    # -- placement ----------------------------------------------------------

    @property
    def n_shards(self) -> int:
        if self.mesh is None or self.data_axis not in self.mesh.shape:
            return 1
        return int(self.mesh.shape[self.data_axis])

    @property
    def sharded(self) -> bool:
        return self.n_shards > 1

    @property
    def n_feature_shards(self) -> int:
        if (
            self.mesh is None
            or self.feature_axis is None
            or self.feature_axis not in self.mesh.shape
        ):
            return 1
        return int(self.mesh.shape[self.feature_axis])

    @property
    def feature_sharded(self) -> bool:
        return self.n_feature_shards > 1

    # -- resolution ---------------------------------------------------------

    @property
    def resolved(self) -> bool:
        return self.effective_impl is not None

    @property
    def degraded(self) -> bool:
        return self.degraded_reason is not None

    def resolve(self, *, schedulable: bool) -> "SpmmPlan":
        """Pin the impl that will actually run.

        ``schedulable`` says whether a host-side :class:`TiledELL` is
        available for occupancy planning; without one, ``pallas_sparse``
        degrades to the masked dense grid (recorded, warned once).
        Resolving an already-resolved plan is a no-op.
        """
        if self.resolved:
            return self
        impl, reason = self.impl, None
        if self.impl == "pallas_sparse" and not schedulable:
            reason = (
                "pallas_sparse degraded to pallas: block-skipping needs "
                "host-side grid planning over a TiledELL, which is "
                "unavailable for bare-array (traced) operands"
            )
            impl = "pallas"
            _warn_once(reason)
        return dataclasses.replace(
            self, effective_impl=impl, degraded_reason=reason
        )


def plan_for_config(
    cfg,
    mesh: Optional[jax.sharding.Mesh] = None,
    interpret: Optional[bool] = None,
    *,
    ell=None,
    feature_dim: Optional[int] = None,
    n_devices: Optional[int] = None,
) -> SpmmPlan:
    """Build a plan from a :class:`~repro.models.gcn.GCNConfig`-like object
    (anything with ``spmm_impl``/``block_rows``/``block_k``/``block_f``).

    Without ``ell`` this is the *static* plan: the config's impl and block
    sizes, placed on ``mesh``.  With ``ell`` (a host
    :class:`~repro.core.sparse_formats.TiledELL`) the choice routes
    through the cost model instead: ``repro.plan.autoplan`` enumerates
    impl x block sizes x viable data-mesh widths and returns the
    argmin-cost plan (never costed worse than the static default, which is
    always a candidate).  ``feature_dim`` defaults to the config's hidden
    width — the dominant SpMM feature dimension in a GCN stack.
    """
    if ell is not None:
        from repro.plan.autoplan import autoplan  # deferred: no cycle

        return autoplan(
            ell,
            feature_dim or getattr(cfg, "hidden_dim", 128),
            cfg,
            mesh=mesh,
            n_devices=n_devices,
            interpret=interpret,
        )
    return SpmmPlan(
        impl=cfg.spmm_impl,
        block_rows=cfg.block_rows,
        block_k=cfg.block_k,
        block_f=cfg.block_f,
        interpret=interpret,
        mesh=mesh,
    )
