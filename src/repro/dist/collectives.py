"""Masked cross-replica reductions and the sharded-SpMM epilogues.

``masked_psum_mean`` is the gradient-averaging primitive behind straggler
dropping: replicas flagged by ``StragglerMonitor`` contribute a zero
weight, and the mean renormalizes over the replicas that remain — the
surviving replicas keep training on an unbiased average instead of
stalling on (or being poisoned by) the dropped one.

``segment_psum`` is the replicated epilogue behind the sharded SpMM hot
path (``repro.exec.sharded``): each shard folds its local vertex-cut
sub-row products into a full-height partial output, then the partials are
summed across the ``data`` axis into original output rows — the paper's
CMP partial-sum path stretched across the mesh.  ``segment_reduce_scatter``
is its row-sharded twin: the same fold, but the cross-shard sum lands each
shard only its own contiguous slice of output rows (half the collective
bytes of an all-reduce), which is the epilogue a *following* sharded SpMM
layer wants — activations never round-trip through replicated form.

Both work under real ``psum`` axes and under
``jax.vmap(..., axis_name=...)`` emulation, which is how the CPU tests
exercise them.

:class:`CollectiveLedger` is the measurement hook the pipeline benchmark
reads: ``exec.sharded`` records each epilogue's per-device collective
bytes (ring-algorithm arithmetic) and activation DRAM writeback at
dispatch time, so per-layer vs pipelined traffic is observable without
parsing HLO.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List

import jax
import jax.numpy as jnp


def masked_psum_mean(tree: Any, axis: str, alive: jax.Array) -> Any:
    """Mean of ``tree`` over the named replica axis, weighted by ``alive``.

    ``alive`` is this replica's scalar weight (1.0 = contribute, 0.0 =
    dropped).  The denominator is the live-replica count, clamped to 1 so
    an all-dropped step yields zeros rather than NaNs.
    """
    alive = jnp.asarray(alive, jnp.float32)
    n_alive = jnp.maximum(jax.lax.psum(alive, axis), 1.0)
    return jax.tree.map(
        lambda g: jax.lax.psum(g * alive.astype(g.dtype), axis)
        / n_alive.astype(g.dtype),
        tree,
    )


def segment_psum(
    sub_rows: jax.Array,   # (R_local, F) per-sub-row partial products
    row_map: jax.Array,    # (R_local,) int32 -> original row, -1 padding
    n_out_rows: int,
    axis: str,
) -> jax.Array:
    """Fold local sub-row partials into output rows, then psum over ``axis``.

    The local fold is the same segment-accumulate every single-device SpMM
    path uses (one implementation, imported lazily so ``dist`` keeps its
    no-upward-imports property at module load); the psum completes rows
    whose vertex-cut sub-rows landed on different shards.
    """
    from repro.core.spmm import _segment_accumulate

    return jax.lax.psum(
        _segment_accumulate(sub_rows, row_map, n_out_rows), axis
    )


def segment_reduce_scatter(
    sub_rows: jax.Array,   # (R_local, F) per-sub-row partial products
    row_map: jax.Array,    # (R_local,) int32 -> original row, -1 padding
    n_out_rows: int,       # padded: must be divisible by the axis size
    axis: str,
) -> jax.Array:
    """Row-sharded epilogue: fold local sub-row partials into output rows,
    reduce-scatter over ``axis`` so shard ``i`` receives rows
    ``[i * n_out_rows/n, (i+1) * n_out_rows/n)`` of the summed output.

    The cross-shard sum is identical to :func:`segment_psum`'s — each
    output row is the same reduction of the same per-shard partials — so
    a reduce-scatter epilogue followed by an all-gather reproduces the
    psum result bitwise; it just moves half the bytes and leaves the rows
    where the next sharded layer consumes them.  ``n_out_rows`` must
    already be padded to a multiple of the axis width (the caller owns
    the padding because the padded height is also the next layer's dense
    operand height).
    """
    from repro.core.spmm import _segment_accumulate

    return jax.lax.psum_scatter(
        _segment_accumulate(sub_rows, row_map, n_out_rows),
        axis,
        scatter_dimension=0,
        tiled=True,
    )


# ---------------------------------------------------------------------------
# Collective-traffic ledger
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CollectiveLedger:
    """Per-process tally of collective + activation DRAM traffic.

    ``exec.sharded`` (and the pipeline executor above it) record one entry
    per dispatched epilogue with the ring-algorithm per-device byte count
    — ``psum`` 2(n-1)/n, ``reduce_scatter``/``all_gather`` (n-1)/n of the
    buffer — plus the activation bytes written back to DRAM under the
    chosen layout (replicated output: every device writes the full
    height; row-sharded: the height is written once across the mesh).
    Recording happens host-side at dispatch, not inside traced code, so
    the totals are per *execution* and immune to jit caching.
    """

    counts: Dict[str, int] = dataclasses.field(default_factory=dict)
    bytes: Dict[str, float] = dataclasses.field(default_factory=dict)
    #: Observers called as ``listener(kind, nbytes, n)`` on every record.
    #: ``repro.obs`` registers one to adopt ledger records as span
    #: events; listeners never affect the tallies and ``reset`` leaves
    #: them installed.
    listeners: List[Callable[[str, float, int], None]] = dataclasses.field(
        default_factory=list)

    def record(self, kind: str, nbytes: float, n: int = 1) -> None:
        self.counts[kind] = self.counts.get(kind, 0) + n
        self.bytes[kind] = self.bytes.get(kind, 0.0) + float(nbytes)
        for listener in self.listeners:
            listener(kind, float(nbytes), n)

    def reset(self) -> None:
        self.counts.clear()
        self.bytes.clear()

    def count(self, kind: str) -> int:
        return self.counts.get(kind, 0)

    def total_bytes(self, *kinds: str) -> float:
        if not kinds:
            kinds = tuple(self.bytes)
        return sum(self.bytes.get(k, 0.0) for k in kinds)

    def snapshot(self) -> dict:
        return {"counts": dict(self.counts), "bytes": dict(self.bytes)}


#: The process-global ledger every sharded dispatch records into.
LEDGER = CollectiveLedger()
