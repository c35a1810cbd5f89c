"""FlexVector SpMM Pallas TPU kernel.

TPU-native realization of the paper's row-wise product dataflow
(DESIGN.md §2).  The vertex-cut guarantees every sparse (sub-)row holds at
most ``tau`` nonzeros, so the sparse operand arrives as a dense
(rows, tau) ELL table.  Inside the kernel each (row-block x k-tile) cell is
*expanded* into a dense (BR, BK) block with an iota-compare one-hot
accumulation — the register-level analogue of the CSR decoder's one-hot
row-index bitmap (paper Fig 4d) — and the block is fed to the MXU against
the VMEM-resident dense k-tile.

Two launch schedules:

* ``spmm_ell_dense_grid`` — full (f, row-block, k-tile) grid with masking;
  the paper-faithful baseline.  The k axis is innermost, giving the
  output-stationary inner-product accumulation of the DRAM-buffer level
  (Section V-B); Pallas' pipelined DMA double-buffers the streamed dense
  k-tiles exactly like the double-VRF MV_Dyn/CMP overlap (Fig 7c).

* ``spmm_ell_sparse_grid`` — block-skipping schedule: a scalar-prefetched
  (row_block, k_tile) pair list visits only non-empty cells, the grid-level
  analogue of never issuing MV_Dyn for absent rows.  Hot k-tiles are
  ordered first within each row block (``hot_k_first``).  It runs one of
  two launches, chosen from the operands' size:

  - ``flexvector_sparse_grid_resident`` (``sparse_grid_resident``) while
    the dense (K, BF) column slab fits ``RESIDENT_VMEM_BUDGET``: the slab
    is one single-buffered block, DMA'd once per f-tile and kept in VMEM —
    the flexible VRF's fixed region, at slab granularity.  One grid step
    per row block builds the row block's expansion tables once (the
    broadcasts of its ELL slabs, which do not depend on the k-tile) and
    loops over its visits, slicing each k-tile from the slab.
  - ``flexvector_sparse_grid`` (the streamed schedule) past it, as for
    reddit's 119 MB f32 slab: one grid step per pair, each DMA-ing its
    own (BK, BF) dense tile, so high-reuse hot tiles stay VMEM-resident —
    the VRF fixed region, at tile granularity.

  Both visit the same pairs in the same order through the same expansion
  and dot, so their outputs are bitwise equal.

VMEM budget per grid step (dtype bytes b): BR*128*(4+b) sparse table
(the tau lanes pad to 128) + BK*BF*b dense tile + BR*BF*4 accumulator +
BR*BK*4 scratch.  The defaults (BR=BK=BF=128, f32) total about 0.5 MiB
with double-buffered inputs, well inside the 16 MiB of scoped VMEM a v5e
kernel gets by default.  The resident launch holds the K*BF*b slab, the
double-buffered ELL slabs and out block, and two (tau, BR, BK) expansion
tables (``resident_vmem_bytes``): at pubmed f32 that is 10.2 MB of slab
and 1.1 MB besides.  The fused kernels hold a whole (R, BF) slab instead;
``plan.cost.fused_vmem_bytes`` counts it.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _acc_dtype(dtype) -> jnp.dtype:
    return jnp.int32 if jnp.issubdtype(dtype, jnp.integer) else jnp.float32


def _expansion_tables(cols, vals, block_k, acc_dtype):
    """The k-tile-independent half of the expansion, per ELL slot ``t``:
    ``offs[t] = cols[:, t] - iota`` and ``vals[:, t]``, each broadcast
    along the (BR, BK) block's lanes."""
    br, tau = cols.shape
    iota = jax.lax.broadcasted_iota(jnp.int32, (br, block_k), 1)
    offs = [cols[:, t][:, None] - iota for t in range(tau)]
    vals = [jnp.broadcast_to(vals[:, t].astype(acc_dtype)[:, None],
                             (br, block_k)) for t in range(tau)]
    return offs, vals


def _expand_tile(offs, vals, kb_base, tau):
    """Scatter a bounded-RNZ sparse block into a dense (BR, BK) block from
    its expansion tables (lists, or ``(tau, BR, BK)`` refs): slot ``t``
    lands in lane ``cols - kb_base``; entries whose column falls outside
    [kb_base, kb_base + BK) — including PAD_COL — drop out."""
    a_blk = jnp.zeros(offs[0].shape, vals[0].dtype)
    for t in range(tau):                                     # tau is static
        a_blk = a_blk + jnp.where(offs[t] == kb_base, vals[t], 0)
    return a_blk


def _expand_block(cols, vals, kb_base, block_k, acc_dtype):
    """Scatter a bounded-RNZ sparse block into a dense (BR, BK) block.

    ``cols``/``vals`` are the (BR, tau) ELL slabs; entries whose column
    falls outside [kb_base, kb_base + block_k) — including PAD_COL — drop
    out via the iota-compare mask.
    """
    offs, vals = _expansion_tables(cols, vals, block_k, acc_dtype)
    return _expand_tile(offs, vals, kb_base, cols.shape[1])


def _split_scales(refs, scaled):
    """``(scales_ref or None, other refs)`` from a kernel's ref list, where
    an int8 launch passes the SMEM scale vector as the third input."""
    if not scaled:
        return None, refs
    return refs[2], refs[:2] + refs[3:]


def _dense_grid_kernel(*refs, block_k, scaled):
    """Masked full-grid step; with ``scaled`` the int8 values are widened
    by ``_expand_block`` and multiplied by their row block's scale (read
    from SMEM) before the MXU, so int8 lives only on the DRAM->VMEM path."""
    scales_ref, (cols_ref, vals_ref, dense_ref, out_ref) = _split_scales(
        refs, scaled)
    rb, kb = pl.program_id(1), pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    acc = _acc_dtype(out_ref.dtype)
    a_blk = _expand_block(
        cols_ref[...], vals_ref[...], kb * block_k, block_k, acc
    )
    if scales_ref is not None:
        a_blk = a_blk * scales_ref[rb].astype(acc)
    out_ref[...] += jax.lax.dot_general(
        a_blk,
        dense_ref[...].astype(acc),
        (((1,), (0,)), ((), ())),
        preferred_element_type=out_ref.dtype,
    )


def _block_scales(scales, r: int, block_rows: int) -> jax.Array:
    """Per-row-block scales for the kernel: ``(r // block_rows,)`` f32.

    Pads with 1.0 for trailing all-padding row blocks (their values are
    zero, so the scale is immaterial but must exist).  The vector rides in
    SMEM whole: a ``(1, 1)`` VMEM block of it would break the TPU's
    (8, 128) tiling rule.
    """
    n_rb = r // block_rows
    s = jnp.asarray(scales, jnp.float32).reshape(-1)
    if s.shape[0] < n_rb:
        s = jnp.pad(s, ((0, n_rb - s.shape[0]),), constant_values=1.0)
    return s[:n_rb]


_SMEM_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def _with_scales(in_specs, args, scales, r, block_rows):
    """Insert the SMEM scale vector as the third kernel input (int8)."""
    if scales is None:
        return in_specs, args
    return (
        in_specs[:2] + [_SMEM_SPEC] + in_specs[2:],
        args[:2] + (_block_scales(scales, r, block_rows),) + args[2:],
    )


def spmm_ell_dense_grid(
    cols: jax.Array,   # (R, tau) int32, PAD_COL = -1 padding
    vals: jax.Array,   # (R, tau)
    dense: jax.Array,  # (K, F)
    *,
    block_rows: int = 128,
    block_k: int = 128,
    block_f: int = 128,
    out_dtype=None,
    interpret: Optional[bool] = None,
    scales: Optional[jax.Array] = None,  # (r // block_rows,) f32 dequant
) -> jax.Array:
    """Paper-faithful baseline schedule: full grid, masked expansion.

    ``scales`` switches on the int8 dequantize-on-load path: one f32
    scale per ``block_rows`` row block, multiplied into the expanded
    block inside the kernel (accumulation stays f32).
    """
    r, tau = cols.shape
    k, f = dense.shape
    if r % block_rows or k % block_k or f % block_f:
        raise ValueError("operands must be padded to block multiples")
    out_dtype = out_dtype or _acc_dtype(dense.dtype)
    ell_spec = pl.BlockSpec((block_rows, tau), lambda fi, rb, kb: (rb, 0))
    dense_spec = pl.BlockSpec((block_k, block_f), lambda fi, rb, kb: (kb, fi))
    in_specs, args = _with_scales(
        [ell_spec, ell_spec, dense_spec], (cols, vals, dense), scales, r,
        block_rows,
    )
    return pl.pallas_call(
        functools.partial(
            _dense_grid_kernel, block_k=block_k, scaled=scales is not None
        ),
        grid=(f // block_f, r // block_rows, k // block_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (block_rows, block_f), lambda fi, rb, kb: (rb, fi)
        ),
        out_shape=jax.ShapeDtypeStruct((r, f), out_dtype),
        interpret=_default_interpret(interpret),
        name="flexvector_dense_grid",
    )(*args)


def _sparse_grid_kernel(rb_ids_ref, kb_ids_ref, first_ref, *refs, block_k,
                        scaled):
    scales_ref, (cols_ref, vals_ref, dense_ref, out_ref) = _split_scales(
        refs, scaled)
    s = pl.program_id(1)

    @pl.when(first_ref[s] == 1)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    acc = _acc_dtype(out_ref.dtype)
    a_blk = _expand_block(
        cols_ref[...], vals_ref[...], kb_ids_ref[s] * block_k, block_k, acc
    )
    if scales_ref is not None:
        a_blk = a_blk * scales_ref[rb_ids_ref[s]].astype(acc)
    out_ref[...] += jax.lax.dot_general(
        a_blk,
        dense_ref[...].astype(acc),
        (((1,), (0,)), ((), ())),
        preferred_element_type=out_ref.dtype,
    )


def spmm_ell_sparse_grid(
    cols: jax.Array,
    vals: jax.Array,
    dense: jax.Array,
    rb_ids: jax.Array,   # (n_steps,) int32 row-block per grid step
    kb_ids: jax.Array,   # (n_steps,) int32 k-tile per grid step
    first: jax.Array,    # (n_steps,) int32 1 on the first visit of rb
    *,
    block_rows: int = 128,
    block_k: int = 128,
    block_f: int = 128,
    out_dtype=None,
    interpret: Optional[bool] = None,
    scales: Optional[jax.Array] = None,  # (r // block_rows,) f32 dequant
) -> jax.Array:
    """Block-skipping schedule driven by a scalar-prefetched pair list.

    The (rb, kb) pair list must keep all visits of one row block
    consecutive (``plan_kernel_grid`` guarantees it) so the output block is
    revisited contiguously while it stays resident in VMEM.  ``scales``
    enables int8 dequantize-on-load, as in :func:`spmm_ell_dense_grid`.
    The three prefetched lists live in SMEM, which bounds ``n_steps``
    (about 40,000 steps fit a v5e core's 1 MiB SMEM; 400,000 do not).

    While :func:`resident_vmem_bytes` fits ``RESIDENT_VMEM_BUDGET`` the
    launch is :func:`sparse_grid_resident`: the same visits in the same
    order, through the same expansion and dot, so the outputs are bitwise
    equal.  ``first`` is then not read.
    """
    r, tau = cols.shape
    k, f = dense.shape
    if r % block_rows or k % block_k or f % block_f:
        raise ValueError("operands must be padded to block multiples")
    out_dtype = out_dtype or _acc_dtype(dense.dtype)
    if resident_vmem_bytes(
            k, tau, block_rows=block_rows, block_k=block_k, block_f=block_f,
            dtype=dense.dtype, out_dtype=out_dtype) <= RESIDENT_VMEM_BUDGET:
        return sparse_grid_resident(
            cols, vals, dense, rb_ids, kb_ids, block_rows=block_rows,
            block_k=block_k, block_f=block_f, out_dtype=out_dtype,
            interpret=interpret, scales=scales)
    n_steps = int(rb_ids.shape[0])
    ell_spec = pl.BlockSpec(
        (block_rows, tau), lambda fi, s, rb, kb, fs: (rb[s], 0)
    )
    dense_spec = pl.BlockSpec(
        (block_k, block_f), lambda fi, s, rb, kb, fs: (kb[s], fi)
    )
    in_specs, args = _with_scales(
        [ell_spec, ell_spec, dense_spec], (cols, vals, dense), scales, r,
        block_rows,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(f // block_f, n_steps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (block_rows, block_f), lambda fi, s, rb, kb, fs: (rb[s], fi)
        ),
    )
    return pl.pallas_call(
        functools.partial(
            _sparse_grid_kernel, block_k=block_k, scaled=scales is not None
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, f), out_dtype),
        interpret=_default_interpret(interpret),
        name="flexvector_sparse_grid",
    )(rb_ids, kb_ids, first, *args)


# A v5e TensorCore has 128 MiB of VMEM and a kernel gets a 16 MiB scope of
# it unless it asks for more.  The resident launch asks for its footprint
# plus that scope, and is taken while the footprint stays within half the
# VMEM (``tests/test_tpu_compile.py`` compiles one at this edge): pubmed's
# f32 dense slab (10.2 MB) fits, reddit's (119 MB) does not.
RESIDENT_VMEM_BUDGET = 64 * 2**20
_DEFAULT_SCOPED_VMEM = 16 * 2**20
# Visits the resident loop expands and multiplies before it adds their
# products, in order, into the out block: the MXU work of one overlaps
# the next one's expansion.
_VISITS_PER_ITER = 4


def resident_vmem_bytes(k, tau, *, block_rows, block_k, block_f, dtype,
                        out_dtype) -> int:
    """VMEM of :func:`sparse_grid_resident` for a ``(k, ·)`` dense operand
    of ``dtype`` and ``tau`` ELL slots: the single-buffered ``(k,
    block_f)`` slab, the double-buffered ELL slabs (the tau lanes pad to
    128) and out block, and the two ``(tau, block_rows, block_k)``
    expansion tables."""
    lanes = -(-tau // 128) * 128
    slab = k * block_f * jnp.dtype(dtype).itemsize
    ell = 2 * 2 * block_rows * lanes * 4
    out = 2 * block_rows * block_f * jnp.dtype(out_dtype).itemsize
    return slab + ell + out + 2 * tau * block_rows * block_k * 4


def _resident_kernel(kb_ids_ref, starts_ref, *refs, block_k, scaled):
    """One row block: build its expansion tables once, then run its
    visits ``kb_ids[starts[rb]:starts[rb + 1]]`` in the list's order,
    ``_VISITS_PER_ITER`` at a time and the remainder one by one."""
    scales_ref, (cols_ref, vals_ref, dense_ref, out_ref, offs_ref,
                 vtab_ref) = _split_scales(refs, scaled)
    rb = pl.program_id(1)
    tau = cols_ref.shape[1]
    acc = _acc_dtype(out_ref.dtype)
    scale = None if scales_ref is None else scales_ref[rb].astype(acc)
    offs, vals = _expansion_tables(cols_ref[...], vals_ref[...], block_k, acc)
    for t in range(tau):
        offs_ref[t], vtab_ref[t] = offs[t], vals[t]
    out_ref[...] = jnp.zeros_like(out_ref)

    def product(s):
        kb = kb_ids_ref[s]
        a_blk = _expand_tile(offs_ref, vtab_ref, kb * block_k, tau)
        if scale is not None:
            a_blk = a_blk * scale
        tile = dense_ref[pl.ds(pl.multiple_of(kb * block_k, block_k),
                               block_k), :]
        return jax.lax.dot_general(
            a_blk, tile.astype(acc), (((1,), (0,)), ((), ())),
            preferred_element_type=out_ref.dtype,
        )

    def visits(i, carry):
        s = start + i * _VISITS_PER_ITER
        for p in [product(s + j) for j in range(_VISITS_PER_ITER)]:
            out_ref[...] += p
        return carry

    def visit(s, carry):
        out_ref[...] += product(s)
        return carry

    start, stop = starts_ref[rb], starts_ref[rb + 1]
    n_iter = (stop - start) // _VISITS_PER_ITER
    jax.lax.fori_loop(0, n_iter, visits, 0)
    jax.lax.fori_loop(start + n_iter * _VISITS_PER_ITER, stop, visit, 0)


def sparse_grid_resident(cols, vals, dense, rb_ids, kb_ids, *, block_rows,
                         block_k, block_f, out_dtype, interpret,
                         scales) -> jax.Array:
    """Resident launch: grid (f-tile, row block).  The whole ``(K,
    block_f)`` dense slab is a single-buffered block, DMA'd once per
    f-tile and kept in VMEM; each step runs its row block's visits of the
    pair list in order, slicing each k-tile from the slab.  The run
    offsets ``starts`` come from ``rb_ids`` on the host when it is
    concrete (inside ``shard_map`` it is not, and they are searched on
    the device)."""
    r, tau = cols.shape
    k, f = dense.shape
    with jax.ensure_compile_time_eval():
        starts = jnp.searchsorted(
            rb_ids, jnp.arange(r // block_rows + 1)).astype(jnp.int32)
    ell_spec = pl.BlockSpec((block_rows, tau), lambda fi, rb, kb, st: (rb, 0))
    dense_spec = pl.BlockSpec((k, block_f), lambda fi, rb, kb, st: (0, fi),
                              pipeline_mode=pl.Buffered(1))
    in_specs, args = _with_scales(
        [ell_spec, ell_spec, dense_spec], (cols, vals, dense), scales, r,
        block_rows,
    )
    acc = _acc_dtype(out_dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(f // block_f, r // block_rows),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (block_rows, block_f), lambda fi, rb, kb, st: (rb, fi)
        ),
        scratch_shapes=[pltpu.VMEM((tau, block_rows, block_k), jnp.int32),
                        pltpu.VMEM((tau, block_rows, block_k), acc)],
    )
    vmem = resident_vmem_bytes(
        k, tau, block_rows=block_rows, block_k=block_k, block_f=block_f,
        dtype=dense.dtype, out_dtype=out_dtype)
    return pl.pallas_call(
        functools.partial(
            _resident_kernel, block_k=block_k, scaled=scales is not None
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, f), out_dtype),
        interpret=_default_interpret(interpret),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem + _DEFAULT_SCOPED_VMEM),
        name="flexvector_sparse_grid_resident",
    )(kb_ids, starts, *args)


def _combine_tile(x_ref, w_ref, b_ref, kb, block_k, k_real, cast_xw):
    """In-VMEM dense combination for one k-tile: ``x_tile @ w + b``.

    Replicates ``exec.quant.affine`` per tile (bf16 inputs arrive
    pre-cast, accumulation is f32, bias added in f32), then zeroes the
    rows past ``k_real`` so the tile matches the padded activation the
    unfused path would have read from HBM.  ``cast_xw`` rounds through
    the storage dtype (bf16 under bf16/int8 plans) the way
    ``quant.cast_dense`` does between the two unfused launches.
    """
    xw = jax.lax.dot_general(
        x_ref[...],
        w_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    xw = xw + b_ref[...].astype(jnp.float32)
    rows = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, xw.shape, 0)
    xw = jnp.where(rows < k_real, xw, 0.0)
    if cast_xw is not None:
        xw = xw.astype(cast_xw)
    return xw


def _fused_accumulate(cols_ref, vals_ref, scales_ref, xw, out_ref, kb_base,
                      *, block_rows, block_k):
    """Aggregate one combined k-tile into the resident output slab.

    Per row block the expansion + dot shapes are exactly those of the
    unfused kernels — (BR, tau) -> (BR, BK) @ (BK, BF) — so each output
    element accumulates through the same sequence of partial products.
    The row blocks run in a loop that writes each product straight into
    its slice of the slab: unrolled, the products of a large graph would
    all be live at once and spill far past VMEM.
    """
    acc = _acc_dtype(out_ref.dtype)
    xw = xw.astype(acc)

    def body(rb, carry):
        rows = pl.ds(pl.multiple_of(rb * block_rows, block_rows), block_rows)
        a_blk = _expand_block(
            cols_ref[rows, :], vals_ref[rows, :], kb_base, block_k, acc
        )
        if scales_ref is not None:
            a_blk = a_blk * scales_ref[rb].astype(acc)
        out_ref[rows, :] += jax.lax.dot_general(
            a_blk, xw, (((1,), (0,)), ((), ())),
            preferred_element_type=out_ref.dtype,
        )
        return carry

    jax.lax.fori_loop(0, cols_ref.shape[0] // block_rows, body, 0)


def _fused_dense_kernel(*refs, block_rows, block_k, k_real, cast_xw, scaled):
    scales_ref, (cols_ref, vals_ref, x_ref, w_ref, b_ref, out_ref) = (
        _split_scales(refs, scaled))
    kb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    xw = _combine_tile(x_ref, w_ref, b_ref, kb, block_k, k_real, cast_xw)
    _fused_accumulate(
        cols_ref, vals_ref, scales_ref, xw, out_ref, kb * block_k,
        block_rows=block_rows, block_k=block_k,
    )


def spmm_ell_fused_dense_grid(
    cols: jax.Array,   # (R, tau) int32, PAD_COL = -1 padding
    vals: jax.Array,   # (R, tau)
    x: jax.Array,      # (K, F_in) layer input, padded to k % block_k == 0
    w: jax.Array,      # (F_in, F_out) layer weight, F_out % block_f == 0
    b: jax.Array,      # (1, F_out) layer bias
    *,
    block_rows: int = 128,
    block_k: int = 128,
    block_f: int = 128,
    k_real: Optional[int] = None,   # rows of x that are real (rest padding)
    out_dtype=None,
    interpret: Optional[bool] = None,
    scales: Optional[jax.Array] = None,  # (r // block_rows,) f32 dequant
    cast_xw=None,                        # storage round-trip dtype (bf16)
) -> jax.Array:
    """One launch per layer: combination ``x @ w + b`` fused with the
    masked full-grid aggregation schedule.

    The grid is (f-tile, k-tile); the whole (R, block_f) output slab is
    the out block for every step of one f-tile, so it stays VMEM-resident
    across the k sweep and the intermediate activation never exists in
    HBM.  Per k-tile the kernel computes the (block_k, block_f) slice of
    ``x @ w + b`` in VMEM and immediately feeds it to the row-wise
    product expansion — the paper's two-stage formulation in one pass.
    """
    r, tau = cols.shape
    k, f_in = x.shape
    f_out = w.shape[1]
    if r % block_rows or k % block_k or f_out % block_f:
        raise ValueError("operands must be padded to block multiples")
    ell_spec = pl.BlockSpec((r, tau), lambda fi, kb: (0, 0))
    in_specs, args = _with_scales(
        [
            ell_spec,
            ell_spec,
            pl.BlockSpec((block_k, f_in), lambda fi, kb: (kb, 0)),
            pl.BlockSpec((f_in, block_f), lambda fi, kb: (0, fi)),
            pl.BlockSpec((1, block_f), lambda fi, kb: (0, fi)),
        ],
        (cols, vals, x, w, b), scales, r, block_rows,
    )
    return pl.pallas_call(
        functools.partial(
            _fused_dense_kernel, block_rows=block_rows, block_k=block_k,
            k_real=k if k_real is None else k_real, cast_xw=cast_xw,
            scaled=scales is not None,
        ),
        grid=(f_out // block_f, k // block_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((r, block_f), lambda fi, kb: (0, fi)),
        out_shape=jax.ShapeDtypeStruct((r, f_out), out_dtype or jnp.float32),
        interpret=_default_interpret(interpret),
        name="flexvector_fused_dense_grid",
    )(*args)


def _fused_sparse_kernel(kb_ids_ref, *refs, block_rows, block_k, k_real,
                         cast_xw, scaled):
    scales_ref, (cols_ref, vals_ref, x_ref, w_ref, b_ref, out_ref) = (
        _split_scales(refs, scaled))
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(kb_ids_ref[s] >= 0)
    def _step():
        kb = kb_ids_ref[s]
        xw = _combine_tile(x_ref, w_ref, b_ref, kb, block_k, k_real, cast_xw)
        _fused_accumulate(
            cols_ref, vals_ref, scales_ref, xw, out_ref, kb * block_k,
            block_rows=block_rows, block_k=block_k,
        )


def spmm_ell_fused_sparse_grid(
    cols: jax.Array,
    vals: jax.Array,
    x: jax.Array,
    w: jax.Array,
    b: jax.Array,
    kb_ids: jax.Array,   # (n_steps,) int32 k-tile per grid step, -1 = no-op
    *,
    block_rows: int = 128,
    block_k: int = 128,
    block_f: int = 128,
    k_real: Optional[int] = None,
    out_dtype=None,
    interpret: Optional[bool] = None,
    scales: Optional[jax.Array] = None,
    cast_xw=None,
) -> jax.Array:
    """Fused launch over a scalar-prefetched occupied-k-tile list.

    ``kb_ids`` comes from :func:`repro.core.dataflow.plan_fused_k_schedule`
    — every k-tile occupied anywhere, in the same global hot-first order
    the unfused sparse grid applies per row block.  ``-1`` entries are
    no-op steps (used to equalize per-shard schedule lengths under
    ``shard_map``); their index maps clamp to tile 0 and the step body is
    skipped entirely.
    """
    r, tau = cols.shape
    k, f_in = x.shape
    f_out = w.shape[1]
    if r % block_rows or k % block_k or f_out % block_f:
        raise ValueError("operands must be padded to block multiples")
    ell_spec = pl.BlockSpec((r, tau), lambda fi, s, kb: (0, 0))
    in_specs, args = _with_scales(
        [
            ell_spec,
            ell_spec,
            pl.BlockSpec(
                (block_k, f_in), lambda fi, s, kb: (jnp.maximum(kb[s], 0), 0)
            ),
            pl.BlockSpec((f_in, block_f), lambda fi, s, kb: (0, fi)),
            pl.BlockSpec((1, block_f), lambda fi, s, kb: (0, fi)),
        ],
        (cols, vals, x, w, b), scales, r, block_rows,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(f_out // block_f, int(kb_ids.shape[0])),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((r, block_f), lambda fi, s, kb: (0, fi)),
    )
    return pl.pallas_call(
        functools.partial(
            _fused_sparse_kernel, block_rows=block_rows, block_k=block_k,
            k_real=k if k_real is None else k_real, cast_xw=cast_xw,
            scaled=scales is not None,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, f_out), out_dtype or jnp.float32),
        interpret=_default_interpret(interpret),
        name="flexvector_fused_sparse_grid",
    )(kb_ids, *args)


def _default_interpret(interpret: Optional[bool]) -> bool:
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def pad_operands(
    cols,
    vals,
    dense,
    block_rows: int,
    block_k: int,
    block_f: int,
) -> Tuple[jax.Array, jax.Array, jax.Array, Tuple[int, int]]:
    """Pad to block multiples; ELL pad slots use PAD_COL so they mask out.

    Pure jnp on static shapes, so it is trace-safe — the serving path calls
    it on tracers inside a compiled step.
    """
    cols, vals, dense = jnp.asarray(cols), jnp.asarray(vals), jnp.asarray(dense)
    r, tau = cols.shape
    k, f = dense.shape
    rp = -(-r // block_rows) * block_rows
    kp = -(-k // block_k) * block_k
    fp = -(-f // block_f) * block_f
    if rp != r:
        cols = jnp.pad(cols, ((0, rp - r), (0, 0)), constant_values=-1)
        vals = jnp.pad(vals, ((0, rp - r), (0, 0)))
    dense = jnp.pad(dense, ((0, kp - k), (0, fp - f)))
    return cols, vals, dense, (r, f)
