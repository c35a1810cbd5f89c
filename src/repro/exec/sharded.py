"""Sharded SpMM execution over the ``data`` (and optional feature) mesh axes.

The row-wise, product-based dataflow makes vertex-cut partitions the
natural unit of parallel work: each shard owns a contiguous slice of the
sub-row axis (a run of vertex-cut partitions), computes its local sub-row
products with the *same* kernel the single-device path uses, folds them
into a full-height partial output with the local segment-accumulate, and
the partials are reduced across the mesh.  Sub-rows of one original row
may land on different shards — the cross-shard reduction is exactly the
CMP partial-sum path of the paper, stretched across the mesh.

The reduction epilogue is pluggable (``SpmmPlan.out_layout``):

* ``replicated``  — ``dist.collectives.segment_psum``: every device ends
  with the full-height output (the historical behaviour, and what a
  non-sharded consumer needs);
* ``row_sharded`` — ``dist.collectives.segment_reduce_scatter``: each
  device keeps only its contiguous slice of output rows, at half the
  collective bytes.  This is the layout a *following* sharded layer
  consumes: its combination matmul runs on local rows, and the dense
  operand is all-gathered inside this executor's shard body
  (``SpmmPlan.dense_layout="row_sharded"``) only where the aggregation
  actually needs full height.

``SpmmPlan.feature_axis`` names a second mesh axis that splits the dense
operand's feature dimension: each feature-shard computes the full row
space for its F slice (the sparse operand is replicated across that
axis), and the output stays feature-sharded — the gather is implicit in
the output layout.  Row sharding balances nonzeros; feature sharding
keeps wide-F layers from leaving the rest of the mesh idle.

The sub-row boundaries are nnz-weighted by default (the cost model's
``balanced_split_points``; ``SpmmPlan.shard_split="uniform"`` restores
the historical equal-row-count split), so a hub-heavy shard does not
serialize the cross-shard reduction behind its extra nonzeros.

``pallas_sparse`` keeps its block-skipping schedule per shard: each
shard's run offsets and visit list are planned host-side from its own
occupancy, and the lists padded at their end to a common length, so
every shard runs one identical program.

Every dispatch records its epilogue's per-device collective bytes and the
activation DRAM writeback into ``dist.collectives.LEDGER`` — recording is
host-side (never inside traced code), so totals are per execution and
immune to jit caching.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.dist.collectives import (
    LEDGER,
    segment_psum,
    segment_reduce_scatter,
)
from repro.exec import quant
from repro.exec.operands import SpmmOperands, planned_grid, shard_operands
from repro.exec.plan import SpmmPlan


def _round_up(x: int, q: int) -> int:
    return -(-x // q) * q


def _record_traffic(plan: SpmmPlan, n_out: int, n_out_pad: int, f: int,
                    dense_rows: int, act_bytes: int,
                    acc_bytes: int = 4) -> None:
    """Ledger entries for one dispatch: epilogue collective bytes
    (per-device ring arithmetic) + activation writeback under the chosen
    output layout.  The all-gathered dense operand and the activation
    writeback move at the storage width (``act_bytes`` — 2 under
    bf16/int8 precision); the reduction collectives move the f32
    accumulator partials (``acc_bytes``)."""
    n = plan.n_shards
    if n > 1 and plan.dense_layout == "row_sharded":
        LEDGER.record(
            "all_gather", (n - 1) / n * dense_rows * f * act_bytes)
    if n > 1 and plan.out_layout == "row_sharded":
        LEDGER.record(
            "reduce_scatter", (n - 1) / n * n_out_pad * f * acc_bytes)
        LEDGER.record("activation_dram", n_out_pad * f * act_bytes, n=0)
    elif n > 1:
        LEDGER.record("psum", 2.0 * (n - 1) / n * n_out * f * acc_bytes)
        LEDGER.record("activation_dram", n * n_out * f * act_bytes, n=0)


def execute_sharded(
    plan: SpmmPlan, operands: SpmmOperands, dense: jax.Array
) -> jax.Array:
    """``A @ dense`` sharded over ``plan.data_axis`` (and optionally
    ``plan.feature_axis``); exact parity with the single-device path for
    every impl (modulo float summation order).

    A ``row_sharded`` output is the *padded* height
    ``round_up(n_out_rows, n_shards)`` with each data shard holding its
    contiguous row slice; the pad rows are exact zeros and sit past every
    real row, so feeding the array straight into a consumer that indexes
    real rows (the next layer's combination matmul) is safe.
    """
    plan = plan.resolve(schedulable=operands.schedulable)
    if operands.precision != "f32":
        # Pre-quantized operands: the shard boundaries slice rows at
        # nnz-balanced (non-scale-block-aligned) offsets, so dequantize
        # exactly to f32 first and re-quantize per shard below.  Exact
        # for power-of-two values; otherwise within one int8 ulp.
        if operands.precision == "int8":
            vals_f = quant.dequantize_values(
                np.asarray(operands.vals), np.asarray(operands.scales),
                operands.scale_block_rows,
            )
        else:
            vals_f = np.asarray(operands.vals, dtype=np.float32)
        operands = dataclasses.replace(
            operands, vals=vals_f, scales=None, scale_block_rows=None,
            precision="f32",
        )
    mesh, axis, f_axis = plan.mesh, plan.data_axis, plan.feature_axis
    n_shards = plan.n_shards
    m_shards = plan.n_feature_shards
    assert mesh is not None and (n_shards > 1 or m_shards > 1)
    n_sub_rows = int((np.asarray(operands.row_map) >= 0).sum())
    if n_shards > max(n_sub_rows, 1):
        raise ValueError(
            f"mesh '{axis}' axis is {n_shards} devices wide but the operand "
            f"has only {n_sub_rows} vertex-cut sub-rows to distribute; use "
            f"a mesh with '{axis}' <= {max(n_sub_rows, 1)}"
        )
    impl = plan.effective_impl
    n_out = operands.n_out_rows
    n_out_pad = _round_up(n_out, n_shards)
    row_sharded_out = plan.out_layout == "row_sharded" and n_shards > 1
    row_sharded_dense = plan.dense_layout == "row_sharded" and n_shards > 1
    out_rows = n_out_pad if row_sharded_out else n_out

    if n_shards > 1:
        sh = shard_operands(
            operands,
            n_shards,
            plan.block_rows,
            split=plan.shard_split,
        )
        cols_h, vals_h, rmap_h = sh.cols, sh.vals, sh.row_map
    else:
        sh = None
        cols_h, vals_h, rmap_h = (
            np.asarray(operands.cols), np.asarray(operands.vals),
            np.asarray(operands.row_map),
        )

    dense = jnp.asarray(dense)
    if plan.precision != "f32":
        dense = quant.cast_dense(dense, plan.precision)
    f = dense.shape[1]
    # Feature sharding needs F divisible by the feature-axis width; pad
    # host-side (zero columns contribute zero products) and trim on exit.
    f_pad_m = _round_up(f, m_shards)
    if f_pad_m != f:
        dense = jnp.pad(dense, ((0, 0), (0, f_pad_m - f)))
    f_local = f_pad_m // m_shards
    cols = jnp.asarray(cols_h)
    scales = None
    if plan.precision == "int8":
        # Quantize the shard-major layout: every shard slice is padded to
        # a block_rows multiple, so each shard's scale run is contiguous
        # and shards with the same row partitioning as the values.
        q_h, s_h = quant.quantize_values(vals_h, plan.block_rows)
        vals = jnp.asarray(q_h)
        scales = jnp.asarray(s_h, jnp.float32)
    else:
        vals = jnp.asarray(vals_h, dtype=dense.dtype)
    rmap = jnp.asarray(rmap_h)
    _record_traffic(plan, n_out, n_out_pad, f_pad_m, dense.shape[0],
                    act_bytes=dense.dtype.itemsize)
    from repro.exec.dispatch import record_spmm_dram  # deferred: no cycle

    record_spmm_dram(plan, cols_h.shape[0], cols_h.shape[1],
                     dense.shape[0], f_pad_m, n_out)

    row_spec = axis if n_shards > 1 else None
    dense_spec = P(axis if row_sharded_dense else None,
                   f_axis if m_shards > 1 else None)
    out_spec = P(axis if row_sharded_out else None,
                 f_axis if m_shards > 1 else None)

    def epilogue(sub, m):
        if n_shards == 1:
            from repro.core.spmm import _segment_accumulate

            return _segment_accumulate(sub, m, out_rows)
        if row_sharded_out:
            return segment_reduce_scatter(sub, m, n_out_pad, axis)
        return segment_psum(sub, m, n_out, axis)

    def prologue(d):
        if row_sharded_dense:
            d = jax.lax.all_gather(d, axis, axis=0, tiled=True)
        return d

    # Optional per-row-block scale operand (int8): sharded like the other
    # row arrays — every shard's scale run is contiguous in shard-major
    # layout, so the same P(row_spec) partitioning applies.
    sc_specs = (P(row_spec),) if scales is not None else ()
    sc_args = (scales,) if scales is not None else ()

    if impl == "reference":
        from repro.exec.dispatch import _sub_row_products_ref

        def body(c, v, *rest):
            *sc, m, d = rest
            if sc:
                v = quant.dequantize_values(v, sc[0], plan.block_rows)
            elif plan.precision != "f32":
                v = v.astype(jnp.float32)  # f32 accumulation, as the kernels
            return epilogue(_sub_row_products_ref(c, v, prologue(d)), m)

        fn = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(P(row_spec), P(row_spec)) + sc_specs
            + (P(row_spec), dense_spec),
            out_specs=out_spec,
            check_vma=False,  # psum replicates; pallas has no vma rule anyway
        )
        return fn(cols, vals, *sc_args, rmap, dense)[:, :f]

    from repro.kernels import flexvector_spmm as fv  # deferred, as in dispatch

    if impl == "pallas":

        def body(c, v, *rest):
            *sc, m, d = rest
            r_loc = c.shape[0]
            c, v, d, _ = fv.pad_operands(
                c, v, prologue(d), plan.block_rows, plan.block_k, plan.block_f
            )
            sub = fv.spmm_ell_dense_grid(
                c,
                v,
                d,
                block_rows=plan.block_rows,
                block_k=plan.block_k,
                block_f=plan.block_f,
                out_dtype=plan.out_dtype,
                interpret=plan.interpret,
                scales=sc[0] if sc else None,
            )[:r_loc, :f_local]
            return epilogue(sub, m)

        fn = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(P(row_spec), P(row_spec)) + sc_specs
            + (P(row_spec), dense_spec),
            out_specs=out_spec,
            check_vma=False,
        )
        return fn(cols, vals, *sc_args, rmap, dense)[:, :f]

    # pallas_sparse: per-shard block-skipping schedules, padded to one length.
    if n_shards > 1:
        starts, kb = _shard_schedules(plan, sh)
    else:
        grid = planned_grid(operands.ell, plan)
        starts, kb = grid.starts, grid.kb_ids

    def body(starts_s, kb_s, c, v, *rest):
        *sc, m, d = rest
        r_loc = c.shape[0]
        c, v, d, _ = fv.pad_operands(
            c, v, prologue(d), plan.block_rows, plan.block_k, plan.block_f
        )
        sub = fv.spmm_ell_sparse_grid(
            c,
            v,
            d,
            starts_s,
            kb_s,
            block_rows=plan.block_rows,
            block_k=plan.block_k,
            block_f=plan.block_f,
            out_dtype=plan.out_dtype,
            interpret=plan.interpret,
            scales=sc[0] if sc else None,
        )[:r_loc, :f_local]
        return epilogue(sub, m)

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(row_spec), P(row_spec), P(row_spec), P(row_spec))
        + sc_specs + (P(row_spec), dense_spec),
        out_specs=out_spec,
        check_vma=False,
    )
    return fn(
        jnp.asarray(starts), jnp.asarray(kb), cols, vals, *sc_args, rmap,
        dense,
    )[:, :f]


def _shard_schedules(plan, sh):
    """Each shard's planned run offsets and visit list, the lists padded
    at their end to the longest (the offsets never reach the padding), so
    every shard runs one program on its own slices."""
    grids = [planned_grid(ell, plan) for ell in sh.shard_ells]
    n_visits = max(len(g.kb_ids) for g in grids)
    return (
        np.concatenate([g.starts for g in grids]).astype(np.int32),
        np.concatenate([np.pad(g.kb_ids, (0, n_visits - len(g.kb_ids)))
                        for g in grids]).astype(np.int32),
    )
