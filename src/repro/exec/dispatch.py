"""The single SpMM dispatch path.

Every entry point — ``spmm_ell`` (host :class:`TiledELL`),
``spmm_ell_arrays`` (traced arrays inside the serving batcher's AOT step)
and the sharded executor — funnels through :func:`execute`: resolve the
plan, compute per-sub-row products with the planned impl, fold vertex-cut
splits back with ``segment_accumulate``.  The pad / impl-switch /
segment-accumulate logic that used to be duplicated across three call
sites lives here exactly once.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.sparse_formats import PAD_COL, TiledELL
from repro.core.spmm import segment_accumulate
from repro.exec import quant
from repro.core.dataflow import KernelGrid
from repro.exec.operands import SpmmOperands, planned_grid
from repro.exec.plan import SpmmPlan


def sub_row_products(
    plan: SpmmPlan,
    cols: jax.Array,      # (R, tau) int32, PAD_COL padding
    vals: jax.Array,      # (R, tau), already cast to the storage dtype
    dense: jax.Array,     # (K, F)
    ell: Optional[TiledELL] = None,
    scales: Optional[jax.Array] = None,  # (ceil(R/block_rows),) f32 (int8)
    grid: Optional[KernelGrid] = None,
) -> jax.Array:
    """Per-sub-row products ``(R, F)`` with the plan's effective impl.

    The row-wise product core of the paper: each bounded (sub-)row times
    the dense operand, *before* the CMP partial-sum fold.  ``pallas_sparse``
    runs the planned ``grid`` when it fits the plan's blocks, else plans
    one from ``ell``, the host container; the plan must
    already be resolved so the impl choice is pinned.  ``scales`` carries
    the per-row-block dequantization scales when ``vals`` is int8 — the
    kernels dequantize on load and still accumulate in f32.
    """
    impl = plan.effective_impl
    assert impl is not None, "resolve() the plan before dispatch"
    if impl == "reference":
        if scales is not None:
            vals = quant.dequantize_values(vals, scales, plan.block_rows)
        elif plan.precision != "f32":
            # bf16 storage: widen before the gather product so the
            # reference accumulates in f32 like the kernels do.
            vals = vals.astype(jnp.float32)
        return _sub_row_products_ref(cols, vals, dense)

    from repro.kernels import flexvector_spmm as fv  # deferred: keeps exec

    r, f = cols.shape[0], dense.shape[1]
    cols_p, vals_p, dense_p, _ = fv.pad_operands(
        cols, vals, dense, plan.block_rows, plan.block_k, plan.block_f
    )
    if impl == "pallas_sparse":
        if grid is None or not grid.fits(plan):
            grid = planned_grid(ell, plan)
        sub = fv.spmm_ell_sparse_grid(
            cols_p,
            vals_p,
            dense_p,
            grid.starts,
            grid.kb_ids,
            block_rows=plan.block_rows,
            block_k=plan.block_k,
            block_f=plan.block_f,
            out_dtype=plan.out_dtype,
            interpret=plan.interpret,
            scales=scales,
        )
    else:  # pallas: paper-faithful masked dense grid
        sub = fv.spmm_ell_dense_grid(
            cols_p,
            vals_p,
            dense_p,
            block_rows=plan.block_rows,
            block_k=plan.block_k,
            block_f=plan.block_f,
            out_dtype=plan.out_dtype,
            interpret=plan.interpret,
            scales=scales,
        )
    return sub[:r, :f]


def _sub_row_products_ref(cols, vals, dense) -> jax.Array:
    """Pure-jnp row-wise product oracle (XLA gather), any backend."""
    mask = cols != PAD_COL
    safe_cols = jnp.where(mask, cols, 0)
    gathered = dense[safe_cols]                      # (R, tau, F)
    return (gathered * (vals * mask)[..., None]).sum(axis=1)


@partial(jax.jit, static_argnames=("n_out_rows",))
def _ref_spmm(cols, vals, row_map, dense, n_out_rows: int) -> jax.Array:
    """Fused reference path: products + segment fold in one jitted step."""
    sub = _sub_row_products_ref(cols, vals, dense)
    return segment_accumulate(sub, row_map, n_out_rows)


def prepare_precision(plan: SpmmPlan, operands: SpmmOperands, dense: jax.Array):
    """Cast/quantize the value plane for the plan's storage precision.

    Returns ``(vals, scales, dense)`` ready for :func:`sub_row_products`:
    ``vals`` in its storage dtype, ``scales`` per-``plan.block_rows``-block
    f32 (int8 only, else ``None``), ``dense`` in its storage dtype.  The
    f32 path is bitwise-untouched — the same cast the dispatcher always
    did.  Pre-quantized operands (``operands.precision != "f32"``) are
    used as stored when their scale blocking aligns with the plan's
    kernel blocks, else dequantized exactly and carried at bf16.
    """
    precision = plan.precision
    stored = operands.precision
    vals = operands.vals
    if precision == "f32":
        if stored == "int8":
            vals = quant.dequantize_values(
                jnp.asarray(vals), jnp.asarray(operands.scales),
                operands.scale_block_rows,
            )
        return jnp.asarray(vals, dtype=dense.dtype), None, dense
    dense = quant.cast_dense(dense, precision)
    if precision == "bf16":
        if stored == "int8":
            vals = quant.dequantize_values(
                jnp.asarray(vals), jnp.asarray(operands.scales),
                operands.scale_block_rows,
            )
        return jnp.asarray(vals, jnp.bfloat16), None, dense
    # int8 execution
    if stored == "int8":
        scales = quant.align_scales(
            operands.scales, operands.scale_block_rows, plan.block_rows
        )
        if scales is None:  # kernel blocks straddle quantization blocks
            vals = quant.dequantize_values(
                jnp.asarray(vals), jnp.asarray(operands.scales),
                operands.scale_block_rows,
            )
            return jnp.asarray(vals, jnp.bfloat16), None, dense
        return (
            jnp.asarray(vals, jnp.int8),
            jnp.asarray(scales, jnp.float32),
            dense,
        )
    q, scales = quant.quantize_values(vals, plan.block_rows)
    return jnp.asarray(q), jnp.asarray(scales, jnp.float32), dense


def record_spmm_dram(
    plan: SpmmPlan, r: int, tau: int, k: int, f: int, n_out_rows: int
) -> None:
    """Ledger the modeled DRAM bytes one dispatch moves at this precision.

    Host-side accounting (``LEDGER.record``), mirroring the cost model's
    traffic terms: the ELL table (int32 cols + stored-width vals +
    row_map + int8 scale vector), one streaming pass over the dense
    operand, and the sub-row + folded activation writeback at the
    activation storage width.  Called only for concrete operands, so
    eager benches see per-execution totals.
    """
    from repro.dist.collectives import LEDGER  # deferred: no cycle

    vb = quant.bytes_per_value(plan.precision)
    ab = quant.activation_bytes(plan.precision)
    sparse = r * tau * (4 + vb) + r * 4
    if plan.precision == "int8":
        sparse += -(-r // plan.block_rows) * 4
    LEDGER.record(
        "spmm_dram", float(sparse + k * f * ab + (r + n_out_rows) * f * ab)
    )


def record_combination_dram(
    plan: SpmmPlan, k: int, f_in: int, f_out: int
) -> None:
    """Ledger the combination launch: ``X`` read, ``W`` read, and
    the intermediate ``XW`` activation written back to DRAM (its read-back
    is part of the aggregation launch's ``spmm_dram`` record)."""
    from repro.dist.collectives import LEDGER  # deferred: no cycle

    vb = quant.bytes_per_value(plan.precision)
    ab = quant.activation_bytes(plan.precision)
    LEDGER.record(
        "combination_dram",
        float(k * f_in * ab + f_in * f_out * vb + k * f_out * ab),
    )


def execute_layer(
    plan: SpmmPlan,
    operands: SpmmOperands,
    x: jax.Array,
    layer: dict,
    *,
    w_block_rows: int = quant.QUANT_BLOCK_ROWS,
) -> jax.Array:
    """One full GCN layer — combination ``x @ w + b`` then aggregation.

    This is the layer-level entry every forward path (``models.gcn``,
    ``exec.pipeline``, the serving batcher) routes through: the
    combination in XLA, its DRAM traffic ledgered, then :func:`execute`.
    ``layer`` holds ``"w"``/``"b"`` and optionally ``"w_scale"`` with
    ``w_block_rows`` granularity (see ``quant.quantize_params``).

    On the eager path (concrete operands) the layer is the
    ``exec.execute_layer`` span: inside a request trace it opens an
    ``execute_layer`` child stamped with the resolved plan's attributes,
    and the ledger records fired inside land on it as events.
    """
    plan = plan.resolve(schedulable=operands.schedulable)
    if operands.concrete and not isinstance(x, jax.core.Tracer):
        from repro.obs.trace import plan_attributes, span  # deferred

        with span("exec.execute_layer") as sp:
            sp.set(**plan_attributes(plan))
            return _execute_layer_inner(
                plan, operands, x, layer, w_block_rows=w_block_rows
            )
    return _execute_layer_inner(
        plan, operands, x, layer, w_block_rows=w_block_rows
    )


def _execute_layer_inner(
    plan: SpmmPlan,
    operands: SpmmOperands,
    x: jax.Array,
    layer: dict,
    *,
    w_block_rows: int,
) -> jax.Array:
    with jax.named_scope("combine"):
        xw = quant.affine(x, layer, plan.precision, w_block_rows)
    if operands.concrete and not isinstance(x, jax.core.Tracer):
        record_combination_dram(
            plan, x.shape[0], x.shape[1], int(xw.shape[1])
        )
    return execute(plan, operands, xw)


def execute(plan: SpmmPlan, operands: SpmmOperands, dense: jax.Array) -> jax.Array:
    """Run one planned SpMM: ``A @ dense`` for the bounded-row sparse ``A``.

    Resolves the plan against the operands (recording any impl
    degradation), then runs single-device or — when the plan's mesh has a
    ``data`` axis wider than one device — sharded over that axis with a
    cross-shard segment-psum.  Both routes share this entry and the
    per-impl product kernels above.
    """
    plan = plan.resolve(schedulable=operands.schedulable)
    if plan.sharded or plan.feature_sharded:
        from repro.exec.sharded import execute_sharded  # deferred: no cycle

        return execute_sharded(plan, operands, dense)
    cols = jnp.asarray(operands.cols)
    row_map = jnp.asarray(operands.row_map)
    vals, scales, dense = prepare_precision(plan, operands, dense)
    if operands.concrete:
        record_spmm_dram(
            plan, cols.shape[0], cols.shape[1], dense.shape[0],
            dense.shape[1], operands.n_out_rows,
        )
    if plan.effective_impl == "reference":
        if scales is not None:
            vals = quant.dequantize_values(vals, scales, plan.block_rows)
            scales = None
        elif plan.precision != "f32":
            vals = vals.astype(jnp.float32)
        with jax.named_scope("aggregate"):
            return _ref_spmm(cols, vals, row_map, dense, operands.n_out_rows)
    with jax.named_scope("aggregate"):
        sub = sub_row_products(
            plan, cols, vals, dense, ell=operands.ell, scales=scales,
            grid=operands.grid,
        )
    with jax.named_scope("fold"):
        return segment_accumulate(sub, row_map, operands.n_out_rows)
