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

* ``spmm_ell_sparse_grid`` (``flexvector_sparse_grid_rows``) —
  block-skipping schedule over the non-empty (row_block, k_tile) cells, the
  grid-level analogue of never issuing MV_Dyn for absent rows.  One grid
  step per row block builds the row block's expansion tables once (the
  broadcasts of its ELL slabs, which do not depend on the k-tile) and
  loops over its visits, hot k-tiles first (``hot_k_first``).  Only the
  per-row-block run offsets ride in SMEM; the visit list stays in HBM and
  each row block's window of it is copied into SMEM a step ahead, so any
  list fits.  Where the dense operand lives follows from its size and
  dtype (``sparse_grid_residency``), the flexible VRF deciding what the
  register file holds:

  - ``resident``: the (K, BF) column slab fits ``RESIDENT_VMEM_BUDGET``
    and is one single-buffered VMEM block, DMA'd once per f-tile (pubmed);
  - ``resident_bf16``: an f32 slab that fits only in the form the MXU
    consumes (reddit's 119 MB).  At the first row block of each f-tile the
    kernel copies the f32 slab in from HBM a chunk at a time, two chunks
    in flight, and rounds it to bf16 (round to nearest even) into a VMEM
    scratch.  The MXU rounds f32 operands to bf16 the same way at the
    default precision, so the products do not change;
  - ``streamed``: past both, each visit's (BK, BF) tile is copied in from
    HBM, double-buffered.

  All three add the same products in the same order, so on the chip their
  outputs are bitwise equal (``resident_bf16`` equals the others run on
  the bf16-rounded operand wherever products are exact f32, as on the CPU).

VMEM budget per grid step (dtype bytes b): BR*128*(4+b) sparse table
(the tau lanes pad to 128) + BK*BF*b dense tile + BR*BF*4 accumulator +
BR*BK*4 scratch.  The defaults (BR=BK=BF=128, f32) total about 0.5 MiB
with double-buffered inputs, well inside the 16 MiB of scoped VMEM a v5e
kernel gets by default.  The sparse grid holds, besides 1.1 MB of
double-buffered ELL slabs and out block and two (tau, BR, BK) expansion
tables, its dense operand (``sparse_grid_vmem_bytes``): the K*BF*b slab
resident (10.2 MB at pubmed f32); the K*BF*2 bf16 slab and two
(8*BK, BF) f32 staging chunks for ``resident_bf16`` (59.7 MB and 1 MiB
at reddit); or eight streamed tiles.
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


# A v5e TensorCore has 128 MiB of VMEM and a kernel gets a 16 MiB scope of
# it unless it asks for more.  The sparse grid asks for its footprint plus
# that scope, and keeps the dense slab resident while its footprint stays
# within half the VMEM (``tests/test_tpu_compile.py`` compiles one at each
# residency's edge): pubmed's f32 dense slab (10.2 MB) fits, reddit's
# (119 MB) fits only rounded to bf16 (59.7 MB).
RESIDENT_VMEM_BUDGET = 64 * 2**20
_DEFAULT_SCOPED_VMEM = 16 * 2**20
# Visits the sparse grid's loop expands and multiplies before it adds
# their products, in order, into the out block: the MXU work of one
# overlaps the next one's expansion (and, streamed, the next tiles' DMA).
_VISITS_PER_ITER = 4
# The tiling of a 1-D int32 array, to which a DMA'd slice must align.
_KB_ALIGN = 1024
# k-tiles of the f32 slab a ``resident_bf16`` launch copies in at a time
# to round into its bf16 slab (512 KiB at 128 x 128), two in flight.
_FILL_TILES = 8


def _round_up(x: int, q: int) -> int:
    return -(-x // q) * q


def _fill_rows(k, block_k) -> int:
    return min(_FILL_TILES * block_k, k)


def sparse_grid_vmem_bytes(residency, k, tau, *, dtype, out_dtype,
                           block_rows, block_k, block_f) -> int:
    """VMEM of :func:`spmm_ell_sparse_grid` in ``residency`` with a
    ``(k, ·)`` dense operand of ``dtype`` and ``tau`` ELL slots: the dense
    operand's buffers (the single-buffered ``(k, block_f)`` slab; the bf16
    slab and two f32 staging chunks; or ``2 * _VISITS_PER_ITER`` streamed
    tiles), the double-buffered ELL slabs (the tau lanes pad to 128) and
    out block, and the two ``(tau, block_rows, block_k)`` expansion
    tables."""
    b = jnp.dtype(dtype).itemsize
    if residency == "resident":
        dense = k * block_f * b
    elif residency == "resident_bf16":
        dense = k * block_f * 2 + 2 * _fill_rows(k, block_k) * block_f * b
    else:
        dense = 2 * _VISITS_PER_ITER * block_k * block_f * b
    ell = 2 * 2 * block_rows * _round_up(tau, 128) * 4
    out = 2 * block_rows * block_f * jnp.dtype(out_dtype).itemsize
    return dense + ell + out + 2 * tau * block_rows * block_k * 4


def sparse_grid_residency(k, tau, *, dtype, out_dtype, block_rows, block_k,
                          block_f) -> str:
    """Where :func:`spmm_ell_sparse_grid` keeps a ``(k, ·)`` dense operand
    of ``dtype``: ``"resident"`` while its slab fits
    ``RESIDENT_VMEM_BUDGET``; ``"resident_bf16"`` for an f32 slab under a
    float accumulator that fits only rounded to bf16 (the MXU's operand
    form at the default precision); else ``"streamed"``."""
    def fits(residency):
        return sparse_grid_vmem_bytes(
            residency, k, tau, dtype=dtype, out_dtype=out_dtype,
            block_rows=block_rows, block_k=block_k,
            block_f=block_f) <= RESIDENT_VMEM_BUDGET

    if fits("resident"):
        return "resident"
    if (jnp.dtype(dtype) == jnp.float32
            and _acc_dtype(out_dtype) == jnp.float32
            and fits("resident_bf16")):
        return "resident_bf16"
    return "streamed"


def _fill_slab(dense_hbm, slab, stage, sems, col0, block_k):
    """Copy the f32 column slab ``dense_hbm[:, col0:col0 + BF]`` into the
    bf16 VMEM ``slab`` a chunk at a time, the next chunk in flight while
    this one is rounded (to nearest even) and stored.  The last chunk ends
    at the slab's end, so it may store some rows a second time."""
    k, chunk, bf = slab.shape[0], stage.shape[1], slab.shape[1]
    n = -(-k // chunk)

    def copy(i):
        row0 = pl.multiple_of(jnp.minimum(i * chunk, k - chunk), block_k)
        slot = jax.lax.rem(i, 2)
        return row0, slot, pltpu.make_async_copy(
            dense_hbm.at[pl.ds(row0, chunk), pl.ds(col0, bf)],
            stage.at[slot], sems.at[slot])

    copy(0)[2].start()

    def body(i, carry):
        @pl.when(i + 1 < n)
        def _next():
            copy(i + 1)[2].start()

        row0, slot, chunk_copy = copy(i)
        chunk_copy.wait()
        slab[pl.ds(row0, chunk), :] = stage[slot].astype(slab.dtype)
        return carry

    jax.lax.fori_loop(0, n, body, 0)


def _sparse_grid_kernel(starts_ref, *refs, block_k, block_f, scaled,
                        residency, per_iter):
    """One row block: build its expansion tables once, then run its
    visits ``kb_ids[starts[rb]:starts[rb + 1]]`` in the list's order,
    ``per_iter`` at a time and the remainder one by one.

    The row block's k-tile ids come from the list in HBM: a window of it
    is copied into SMEM one grid step ahead.  A resident dense slab is
    sliced in VMEM (a ``resident_bf16`` one is filled at the f-tile's
    first row block, and each tile widened back to f32, exactly);
    otherwise each visit's ``(block_k, block_f)`` tile is copied in from
    HBM, the next ``per_iter`` tiles while these multiply.
    """
    scales_ref, (cols_ref, vals_ref, dense_ref, kb_hbm, out_ref, kb_win,
                 kb_sem, offs_ref, vtab_ref, *extra) = _split_scales(
                     refs, scaled)
    fi, rb = pl.program_id(0), pl.program_id(1)
    n_rb = pl.num_programs(1)
    step, n_steps = fi * n_rb + rb, pl.num_programs(0) * n_rb
    n_list = kb_hbm.shape[0]
    width = kb_win.shape[0] // 2

    def window(r, slot):
        base = jnp.minimum(starts_ref[r] // _KB_ALIGN * _KB_ALIGN,
                           n_list - width)
        return base, pltpu.make_async_copy(
            kb_hbm.at[pl.ds(base, width)],
            kb_win.at[pl.ds(slot * width, width)], kb_sem.at[slot])

    slot = jax.lax.rem(step, 2)

    @pl.when(step == 0)
    def _first_window():
        window(rb, slot)[1].start()

    @pl.when(step + 1 < n_steps)
    def _next_window():
        window(jax.lax.rem(rb + 1, n_rb), 1 - slot)[1].start()

    if residency == "resident_bf16":
        slab = extra[0]

        @pl.when(rb == 0)
        def _fill():
            _fill_slab(dense_ref, *extra,
                       pl.multiple_of(fi * block_f, block_f), block_k)

    tau = cols_ref.shape[1]
    acc = _acc_dtype(out_ref.dtype)
    scale = None if scales_ref is None else scales_ref[rb].astype(acc)
    offs, vals = _expansion_tables(cols_ref[...], vals_ref[...], block_k, acc)
    for t in range(tau):
        offs_ref[t], vtab_ref[t] = offs[t], vals[t]
    out_ref[...] = jnp.zeros_like(out_ref)
    base, copy = window(rb, slot)
    copy.wait()
    start, stop = starts_ref[rb], starts_ref[rb + 1]

    def kb_of(s):
        return kb_win[slot * width + s - base]

    def rows_of(s):
        return pl.ds(pl.multiple_of(kb_of(s) * block_k, block_k), block_k)

    if residency == "streamed":
        tiles, tile_sem = extra
        n_buf = 2 * per_iter

        def tile_copy(s):
            buf = jax.lax.rem(s - start, n_buf)
            return buf, pltpu.make_async_copy(
                dense_ref.at[rows_of(s),
                             pl.ds(pl.multiple_of(fi * block_f, block_f),
                                   block_f)],
                tiles.at[buf], tile_sem.at[buf])

        def fetch(first):
            for j in range(per_iter):
                @pl.when(first + j < stop)
                def _start():
                    tile_copy(first + j)[1].start()

        fetch(start)

    def product(s):
        if residency == "resident":
            tile = dense_ref[rows_of(s), :]
        elif residency == "resident_bf16":
            tile = slab[rows_of(s), :]
        else:
            buf, copy = tile_copy(s)
            copy.wait()
            tile = tiles[buf]
        a_blk = _expand_tile(offs_ref, vtab_ref, kb_of(s) * block_k, tau)
        if scale is not None:
            a_blk = a_blk * scale
        return jax.lax.dot_general(
            a_blk, tile.astype(acc), (((1,), (0,)), ((), ())),
            preferred_element_type=out_ref.dtype,
        )

    def visits(i, carry):
        s = start + i * per_iter
        if residency == "streamed":
            fetch(s + per_iter)
        for p in [product(s + j) for j in range(per_iter)]:
            out_ref[...] += p
        return carry

    def visit(s, carry):
        out_ref[...] += product(s)
        return carry

    n_iter = (stop - start) // per_iter
    jax.lax.fori_loop(0, n_iter, visits, 0)
    jax.lax.fori_loop(start + n_iter * per_iter, stop, visit, 0)


def spmm_ell_sparse_grid(
    cols: jax.Array,
    vals: jax.Array,
    dense: jax.Array,
    starts: jax.Array,   # (r // block_rows + 1,) int32 run offsets
    kb_ids: jax.Array,   # (n_visits,) int32 k-tile of each visit
    *,
    block_rows: int = 128,
    block_k: int = 128,
    block_f: int = 128,
    out_dtype=None,
    interpret: Optional[bool] = None,
    scales: Optional[jax.Array] = None,  # (r // block_rows,) f32 dequant
) -> jax.Array:
    """Block-skipping schedule: grid (f-tile, row block), each step
    running its row block's visits ``kb_ids[starts[rb]:starts[rb + 1]]``
    (``plan_kernel_grid``: every row block at least once, hot k-tiles
    first) into its output block.

    Only the run offsets ride in SMEM (scalar prefetch); the visit list
    stays in HBM and each row block's window of it is copied into SMEM.
    The dense operand's residency is :func:`sparse_grid_residency`'s: the
    whole ``(K, block_f)`` slab as one single-buffered VMEM block, DMA'd
    once per f-tile; an f32 slab rounded to bf16 into VMEM scratch once
    per f-tile; or each visit's tile copied in from HBM, double-buffered.
    Every visit goes through the same expansion and dot, added in list
    order, so the residencies give bitwise-equal sums wherever the MXU
    rounds f32 operands to bf16, as a TPU does at the default precision.
    ``scales`` enables int8 dequantize-on-load, as in
    :func:`spmm_ell_dense_grid`.
    """
    r, tau = cols.shape
    k, f = dense.shape
    if r % block_rows or k % block_k or f % block_f:
        raise ValueError("operands must be padded to block multiples")
    if starts.shape != (r // block_rows + 1,):
        raise ValueError(f"starts must hold {r // block_rows + 1} run "
                         f"offsets, not {starts.shape}")
    out_dtype = out_dtype or _acc_dtype(dense.dtype)
    acc = _acc_dtype(out_dtype)
    blocks = dict(dtype=dense.dtype, out_dtype=out_dtype,
                  block_rows=block_rows, block_k=block_k, block_f=block_f)
    residency = sparse_grid_residency(k, tau, **blocks)
    vmem = sparse_grid_vmem_bytes(residency, k, tau, **blocks)
    per_iter = _VISITS_PER_ITER
    # A window of the visit list is aligned to its (1024) tiling and
    # covers any row block's run of at most K / block_k visits.
    width = _round_up(k // block_k + _KB_ALIGN - 1, _KB_ALIGN)
    n_list = max(_round_up(int(kb_ids.shape[0]), _KB_ALIGN), width)
    if n_list != kb_ids.shape[0]:
        kb_ids = jnp.pad(kb_ids, (0, n_list - kb_ids.shape[0]))
    ell_spec = pl.BlockSpec((block_rows, tau), lambda fi, rb, st: (rb, 0))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    scratch = [pltpu.SMEM((2 * width,), jnp.int32),
               pltpu.SemaphoreType.DMA((2,)),
               pltpu.VMEM((tau, block_rows, block_k), jnp.int32),
               pltpu.VMEM((tau, block_rows, block_k), acc)]
    dense_spec = any_spec
    if residency == "resident":
        dense_spec = pl.BlockSpec((k, block_f), lambda fi, rb, st: (0, fi),
                                  pipeline_mode=pl.Buffered(1))
    elif residency == "resident_bf16":
        scratch += [pltpu.VMEM((k, block_f), jnp.bfloat16),
                    pltpu.VMEM((2, _fill_rows(k, block_k), block_f),
                               dense.dtype),
                    pltpu.SemaphoreType.DMA((2,))]
    else:
        scratch += [pltpu.VMEM((2 * per_iter, block_k, block_f), dense.dtype),
                    pltpu.SemaphoreType.DMA((2 * per_iter,))]
    in_specs, args = _with_scales(
        [ell_spec, ell_spec, dense_spec, any_spec],
        (cols, vals, dense, kb_ids), scales, r, block_rows,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(f // block_f, r // block_rows),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (block_rows, block_f), lambda fi, rb, st: (rb, fi)
        ),
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        functools.partial(
            _sparse_grid_kernel, block_k=block_k, block_f=block_f,
            scaled=scales is not None, residency=residency,
            per_iter=per_iter,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, f), out_dtype),
        interpret=_default_interpret(interpret),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem + _DEFAULT_SCOPED_VMEM),
        name="flexvector_sparse_grid_rows",
    )(starts, *args)


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
