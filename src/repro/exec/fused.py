"""Fused GCN-layer execution: combination + aggregation in one launch.

The paper's §2 formulation treats a GCN layer as a two-stage SpMM —
``A @ (X @ W)`` — and wins by never letting the intermediate ``X @ W``
leave the register file.  The unfused execute path launches the dense
combination and the sparse aggregation separately, so every layer writes
the full ``(K, F_out)`` activation to HBM and reads it back.  This module
is the kernel-fused twin: one Pallas launch per layer computes each
``(block_k, block_f)`` slice of ``X @ W + b`` in VMEM and immediately
aggregates it through the ELL schedule, with the entire output column
slab VMEM-resident across the k sweep (see
``kernels.flexvector_spmm.spmm_ell_fused_*``).  The intermediate
activation never exists in DRAM; the ledger records an explicit 0-byte
writeback (`CollectiveLedger.record_fused_writeback`) so fused and
unfused runs stay count-comparable.

Parity contract: for every impl the fused path equals the unfused
two-launch path, bitwise at bf16 and int8 storage and to f32 rounding at
f32.  The in-kernel combination replicates ``exec.quant.affine`` per
k-tile (pre-cast bf16 inputs, f32 accumulate, f32 bias add,
storage-dtype round-trip), the per-row-block aggregation dots have
exactly the unfused kernels' shapes, and the fused sparse schedule visits
k-tiles in the same global hot-first order the unfused sparse grid
applies per row block.  What differs is the combination's shape — one
``(block_k, F_in)`` tile against one XLA dot over all of ``X`` — and the
compiler blocks an f32 contraction by shape, so f32 sums round
differently (``tests/test_fused.py`` states the bound).

Routing lives in ``exec.dispatch.execute_layer``: a resolved plan with
``fused=True`` and a pallas impl lands here; the reference impl and
feature-sharded plans fall back to the two-launch path (the reference
gather oracle has no launch to fuse, and feature sharding splits the
very dimension the fused launch keeps resident).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.spmm import segment_accumulate
from repro.dist.collectives import (
    LEDGER,
    segment_psum,
    segment_reduce_scatter,
)
from repro.exec import quant
from repro.exec.operands import SpmmOperands, shard_operands
from repro.exec.plan import SpmmPlan


def _round_up(x: int, q: int) -> int:
    return -(-x // q) * q


# -- operand preparation ----------------------------------------------------


def _prepare_fused_values(plan: SpmmPlan, operands: SpmmOperands):
    """ELL values + scales for the fused kernel, mirroring the unfused
    ``dispatch.prepare_precision`` exactly (minus the dense operand, which
    the fused kernel builds in VMEM)."""
    precision, stored = plan.precision, operands.precision
    vals = operands.vals

    def _dequant():
        return quant.dequantize_values(
            jnp.asarray(vals), jnp.asarray(operands.scales),
            operands.scale_block_rows,
        )

    if precision == "f32":
        if stored == "int8":
            return _dequant().astype(jnp.float32), None
        return jnp.asarray(vals, jnp.float32), None
    if precision == "bf16":
        if stored == "int8":
            return _dequant().astype(jnp.bfloat16), None
        return jnp.asarray(vals, jnp.bfloat16), None
    # int8 execution
    if stored == "int8":
        scales = quant.align_scales(
            operands.scales, operands.scale_block_rows, plan.block_rows
        )
        if scales is None:  # kernel blocks straddle quantization blocks
            return _dequant().astype(jnp.bfloat16), None
        return jnp.asarray(vals, jnp.int8), jnp.asarray(scales, jnp.float32)
    q, scales = quant.quantize_values(vals, plan.block_rows)
    return jnp.asarray(q), jnp.asarray(scales, jnp.float32)


def _prepare_fused_weights(plan: SpmmPlan, layer: dict, w_block_rows: int):
    """``(w, b_2d, x_cast, xw_cast)`` in the dtypes ``quant.affine`` and
    ``quant.cast_dense`` would produce between the two unfused launches."""
    w, b = layer["w"], layer["b"]
    if plan.precision == "f32":
        return (
            jnp.asarray(w), jnp.asarray(b).reshape(1, -1), None, None
        )
    if "w_scale" in layer:
        w = quant.dequantize_values(w, layer["w_scale"], w_block_rows)
    return (
        jnp.asarray(w).astype(jnp.bfloat16),
        jnp.asarray(b).astype(jnp.float32).reshape(1, -1),
        jnp.bfloat16,
        jnp.bfloat16,
    )


# -- ledger accounting ------------------------------------------------------


def record_fused_dram(
    plan: SpmmPlan,
    r: int,
    tau: int,
    k: int,
    f_in: int,
    f_out: int,
    n_out_rows: int,
    n_fb: int,
    occ_frac: float,
) -> None:
    """Ledger the modeled DRAM bytes one fused layer dispatch moves.

    Mirrors ``dispatch.record_spmm_dram``'s terms with the fused traffic
    shape: the ELL table streams once (the constant-index BlockSpec keeps
    it VMEM-resident across the whole grid), the layer input ``X`` streams
    once per f-tile over the *occupied* k-tiles, the weight slab streams
    once, and only the aggregated output is written — the intermediate
    activation's write + read-back (``2 * K * F_out`` elements) never
    happens, recorded as an explicit 0-byte writeback with the saving
    tallied under ``fused_writeback_saved``.
    """
    vb = quant.bytes_per_value(plan.precision)
    ab = quant.activation_bytes(plan.precision)
    sparse = r * tau * (4 + vb) + r * 4
    if plan.precision == "int8":
        sparse += -(-r // plan.block_rows) * 4
    x_read = n_fb * occ_frac * k * f_in * ab
    w_read = f_in * f_out * vb
    out = (r + n_out_rows) * f_out * ab
    LEDGER.record("fused_dram", float(sparse + x_read + w_read + out))
    LEDGER.record_fused_writeback(2.0 * k * f_out * ab)


def record_combination_dram(
    plan: SpmmPlan, k: int, f_in: int, f_out: int
) -> None:
    """Ledger the unfused combination launch: ``X`` read, ``W`` read, and
    the intermediate ``XW`` activation written back to DRAM (its read-back
    is part of the aggregation launch's ``spmm_dram`` record)."""
    vb = quant.bytes_per_value(plan.precision)
    ab = quant.activation_bytes(plan.precision)
    LEDGER.record(
        "combination_dram",
        float(k * f_in * ab + f_in * f_out * vb + k * f_out * ab),
    )


def _occupied_frac(plan: SpmmPlan, operands: SpmmOperands) -> float:
    """Fraction of k-tiles the fused launch streams ``X`` tiles for."""
    if plan.effective_impl != "pallas_sparse" or operands.ell is None:
        return 1.0
    occ = operands.ell.block_occupancy(plan.block_rows, plan.block_k)
    n_kb = occ.shape[1]
    return float(occ.any(axis=0).sum()) / float(max(n_kb, 1))


# -- execution --------------------------------------------------------------


def execute_fused(
    plan: SpmmPlan,
    operands: SpmmOperands,
    x: jax.Array,
    layer: dict,
    *,
    w_block_rows: int = quant.QUANT_BLOCK_ROWS,
) -> jax.Array:
    """One fused GCN layer: ``A @ (X @ W + b)`` in a single launch.

    ``layer`` is a param dict with ``"w"``/``"b"`` (optionally
    ``"w_scale"`` from ``quant.quantize_params``; ``w_block_rows`` is its
    scale granularity).  The plan must carry a pallas impl — callers
    route the reference impl through the unfused path
    (``dispatch.execute_layer`` does this automatically).
    """
    plan = plan.resolve(schedulable=operands.schedulable)
    if plan.feature_sharded:
        raise ValueError(
            "fused execution does not support feature-axis sharding: the "
            "fused launch keeps the full output feature slab VMEM-resident;"
            " plan such layers unfused"
        )
    if plan.effective_impl == "reference":
        raise ValueError(
            "the reference impl has no kernel launch to fuse; dispatch "
            "through exec.dispatch.execute_layer, which runs it unfused"
        )
    if plan.sharded:
        return _execute_fused_sharded(
            plan, operands, x, layer, w_block_rows=w_block_rows
        )

    from repro.kernels import flexvector_spmm as fv  # deferred, as dispatch

    cols = jnp.asarray(operands.cols)
    row_map = jnp.asarray(operands.row_map)
    r, tau = cols.shape
    k, f_in = x.shape
    f_out = int(np.shape(layer["w"])[1])
    vals, scales = _prepare_fused_values(plan, operands)
    w_eff, b2, x_cast, xw_cast = _prepare_fused_weights(
        plan, layer, w_block_rows
    )
    x_eff = x if x_cast is None else x.astype(x_cast)

    r_pad = _round_up(r, plan.block_rows)
    k_pad = _round_up(k, plan.block_k)
    f_out_pad = _round_up(f_out, plan.block_f)
    if r_pad != r:
        cols = jnp.pad(cols, ((0, r_pad - r), (0, 0)), constant_values=-1)
        vals = jnp.pad(vals, ((0, r_pad - r), (0, 0)))
    if k_pad != k:
        x_eff = jnp.pad(x_eff, ((0, k_pad - k), (0, 0)))
    if f_out_pad != f_out:
        w_eff = jnp.pad(w_eff, ((0, 0), (0, f_out_pad - f_out)))
        b2 = jnp.pad(b2, ((0, 0), (0, f_out_pad - f_out)))

    if operands.concrete and not isinstance(x, jax.core.Tracer):
        record_fused_dram(
            plan, r, tau, k, f_in, f_out, operands.n_out_rows,
            n_fb=f_out_pad // plan.block_f,
            occ_frac=_occupied_frac(plan, operands),
        )

    common = dict(
        block_rows=plan.block_rows,
        block_k=plan.block_k,
        block_f=plan.block_f,
        k_real=k,
        out_dtype=plan.out_dtype,
        interpret=plan.interpret,
        scales=scales,
        cast_xw=xw_cast,
    )
    if plan.effective_impl == "pallas_sparse":
        from repro.core.dataflow import plan_fused_k_schedule

        kb_ids = plan_fused_k_schedule(
            operands.ell, plan.block_rows, plan.block_k,
            hot_k_first=plan.hot_k_first,
        )
        with jax.named_scope("aggregate"):
            sub = fv.spmm_ell_fused_sparse_grid(
                cols, vals, x_eff, w_eff, b2, jnp.asarray(kb_ids), **common
            )
    else:  # pallas: masked full k sweep
        with jax.named_scope("aggregate"):
            sub = fv.spmm_ell_fused_dense_grid(
                cols, vals, x_eff, w_eff, b2, **common
            )
    with jax.named_scope("fold"):
        return segment_accumulate(
            sub[:r, :f_out], row_map, operands.n_out_rows
        )


def _execute_fused_sharded(
    plan: SpmmPlan,
    operands: SpmmOperands,
    x: jax.Array,
    layer: dict,
    *,
    w_block_rows: int,
) -> jax.Array:
    """Fused launch per data shard; the unfused sharded executor's
    prologue/epilogue structure unchanged.

    Each shard owns a contiguous slice of sub-rows (same nnz-balanced
    split, same shard-major layout) and runs the fused kernel on its
    slice.  A ``row_sharded`` dense layout shards the *layer input* ``X``
    over rows and all-gathers it inside the shard body — at ``F_in``
    width instead of the unfused path's ``F_out``-wide activation gather.
    The segment-psum / segment-reduce-scatter epilogues are exactly those
    of ``exec.sharded.execute_sharded``.
    """
    from jax.sharding import PartitionSpec as P

    from repro.kernels import flexvector_spmm as fv

    if operands.precision != "f32":
        # Pre-quantized operands: shard boundaries slice rows at
        # non-scale-block-aligned offsets — dequantize exactly and
        # re-quantize per shard, as the unfused sharded executor does.
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

    mesh, axis = plan.mesh, plan.data_axis
    n_shards = plan.n_shards
    assert mesh is not None and n_shards > 1
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
    row_sharded_out = plan.out_layout == "row_sharded"
    row_sharded_dense = plan.dense_layout == "row_sharded"

    sh = shard_operands(
        operands, n_shards, plan.block_rows, split=plan.shard_split)
    cols = jnp.asarray(sh.cols)
    scales = None
    if plan.precision == "int8":
        q_h, s_h = quant.quantize_values(sh.vals, plan.block_rows)
        vals = jnp.asarray(q_h)
        scales = jnp.asarray(s_h, jnp.float32)
    else:
        vals = jnp.asarray(
            sh.vals,
            dtype=jnp.float32 if plan.precision == "f32" else jnp.bfloat16,
        )
    rmap = jnp.asarray(sh.row_map)

    k, f_in = x.shape
    f_out = int(np.shape(layer["w"])[1])
    w_eff, b2, x_cast, xw_cast = _prepare_fused_weights(
        plan, layer, w_block_rows
    )
    x_eff = jnp.asarray(x) if x_cast is None else jnp.asarray(x).astype(x_cast)
    act_b = x_eff.dtype.itemsize
    k_pad = _round_up(k, plan.block_k)
    f_out_pad = _round_up(f_out, plan.block_f)
    if f_out_pad != f_out:
        w_eff = jnp.pad(w_eff, ((0, 0), (0, f_out_pad - f_out)))
        b2 = jnp.pad(b2, ((0, 0), (0, f_out_pad - f_out)))
    # A row-sharded input rides in with padded height (the previous
    # layer's reduce-scatter produced round_up(k, n_shards) rows); the
    # gather reassembles it and the pad rows are masked by k_real.
    k_in = x_eff.shape[0]

    if operands.concrete and not isinstance(x, jax.core.Tracer):
        record_fused_dram(
            plan, sh.cols.shape[0], sh.cols.shape[1], k, f_in, f_out, n_out,
            n_fb=f_out_pad // plan.block_f,
            occ_frac=_occupied_frac(plan, operands),
        )
        if row_sharded_dense:
            LEDGER.record(
                "all_gather", (n_shards - 1) / n_shards * k_in * f_in * act_b
            )
        if row_sharded_out:
            LEDGER.record(
                "reduce_scatter",
                (n_shards - 1) / n_shards * n_out_pad * f_out * 4,
            )
        else:
            LEDGER.record(
                "psum", 2.0 * (n_shards - 1) / n_shards * n_out * f_out * 4
            )

    def prologue(xs):
        if row_sharded_dense:
            xs = jax.lax.all_gather(xs, axis, axis=0, tiled=True)
        pad = k_pad - xs.shape[0]
        if pad > 0:
            xs = jnp.pad(xs, ((0, pad), (0, 0)))
        return xs[:k_pad]

    def epilogue(sub, m):
        if row_sharded_out:
            return segment_reduce_scatter(sub, m, n_out_pad, axis)
        return segment_psum(sub, m, n_out, axis)

    common = dict(
        block_rows=plan.block_rows,
        block_k=plan.block_k,
        block_f=plan.block_f,
        k_real=k,
        out_dtype=plan.out_dtype,
        interpret=plan.interpret,
        cast_xw=xw_cast,
    )
    sc_specs = (P(axis),) if scales is not None else ()
    sc_args = (scales,) if scales is not None else ()
    x_spec = P(axis if row_sharded_dense else None, None)
    out_spec = P(axis if row_sharded_out else None, None)

    if impl == "pallas_sparse":
        kb_ids = _padded_fused_schedules(plan, sh)

        def body(kb_s, c, v, *rest):
            *sc, m, xs, ws, bs = rest
            sub = fv.spmm_ell_fused_sparse_grid(
                c, v, prologue(xs), ws, bs, kb_s,
                scales=sc[0] if sc else None, **common,
            )[:, :f_out]
            return epilogue(sub, m)

        fn = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(P(axis), P(axis), P(axis)) + sc_specs
            + (P(axis), x_spec, P(None, None), P(None, None)),
            out_specs=out_spec,
            check_vma=False,
        )
        return fn(
            jnp.asarray(kb_ids), cols, vals, *sc_args, rmap, x_eff, w_eff, b2
        )

    def body(c, v, *rest):
        *sc, m, xs, ws, bs = rest
        sub = fv.spmm_ell_fused_dense_grid(
            c, v, prologue(xs), ws, bs,
            scales=sc[0] if sc else None, **common,
        )[:, :f_out]
        return epilogue(sub, m)

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis), P(axis)) + sc_specs
        + (P(axis), x_spec, P(None, None), P(None, None)),
        out_specs=out_spec,
        check_vma=False,
    )
    return fn(cols, vals, *sc_args, rmap, x_eff, w_eff, b2)


def _padded_fused_schedules(plan, sh) -> np.ndarray:
    """Per-shard fused k-tile schedules, padded to one length with ``-1``.

    The fused kernel skips ``-1`` steps entirely (no row block is
    touched; the output slab was zeroed at step 0), so no reserved
    padding row block is needed — shards just run identical-length
    scalar-prefetched programs.
    """
    from repro.core.dataflow import plan_fused_k_schedule

    per_shard = [
        plan_fused_k_schedule(
            ell, plan.block_rows, plan.block_k, hot_k_first=plan.hot_k_first
        )
        for ell in sh.shard_ells
    ]
    n_steps = max(len(s) for s in per_shard)
    return np.concatenate([
        np.concatenate([s, np.full(n_steps - len(s), -1, np.int32)])
        for s in per_shard
    ]).astype(np.int32)
