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

* ``spmm_ell_sparse_grid`` — block-skipping schedule over the non-empty
  (row_block, k_tile) cells, the grid-level analogue of never issuing
  MV_Dyn for absent rows, grid (f-tile, row block).  Which body it runs,
  and where the dense operand lives, follow from the operands' static
  shapes and dtypes alone (``sparse_grid_launch``), the flexible VRF
  deciding what the register file holds:

  - the *row gather* (``flexvector_gather_rows``), wherever the (K, BF)
    column slab fits ``RESIDENT_VMEM_BUDGET`` under a float accumulator.
    Each sub-row holds at most ``tau`` nonzeros, so a row block's product
    is ``sum_t vals[:, t] * slab[cols[:, t], :]``: each step copies the
    ``block_rows * tau`` slab rows its column indices (in SMEM) name into
    VMEM and adds the slots' products in order on the VPU, whatever the
    graph's visit count.  A 32-bit slab is one single-buffered VMEM block,
    DMA'd once per f-tile and gathered where it lies (``gather``: pubmed,
    citeseer).  A bf16 row is no unit the TPU loads at a dynamic row, so a
    16-bit slab (a bf16 operand, or an f32 one that fits only rounded to
    bf16, as reddit's 119 MB) is held packed two rows to a uint32 word, row
    i in the low half and row i + K/2 in the high half, filled from HBM at
    each f-tile's first row block, two chunks in flight, rounded to nearest
    even (``gather_bf16``);
  - the *visit loop* (``flexvector_sparse_grid_rows``), where no gather
    exists: a slab past the budget, or an integer accumulator.  One grid
    step per row block builds the row block's expansion tables once (the
    broadcasts of its ELL slabs, which do not depend on the k-tile) and
    loops over its visits, hot k-tiles first (``hot_k_first``), each an
    expansion and a (BR, BK) x (BK, BF) dot.  Only the per-row-block run
    offsets ride in SMEM; the visit list stays in HBM and each row block's
    window of it is copied into SMEM a step ahead, so any list fits.  Each
    visit's (BK, BF) tile is copied in from HBM, double-buffered
    (``streamed``).  (Under an integer accumulator it runs interpreted
    only: Mosaic refuses the int32 dot on a v5e.)

  Both bodies multiply the factors ``_mxu_factor`` gives: on the chip an
  f32 factor rounded to bf16, the MXU's operand form at the default
  precision; interpreted, where the CPU multiplies f32 exactly, as it is.
  The visit loop adds its products in the list's order, the row gather per
  sub-row in slot order.

VMEM budget per grid step (dtype bytes b): BR*128*(4+b) sparse table
(the tau lanes pad to 128) + BK*BF*b dense tile + BR*BF*4 accumulator +
BR*BK*4 scratch.  The defaults (BR=BK=BF=128, f32) total about 0.5 MiB
with double-buffered inputs, well inside the 16 MiB of scoped VMEM a v5e
kernel gets by default.  The sparse grid holds, besides 1.1 MB of
double-buffered ELL slabs and out block, the (tau, BR, BF) gathered rows
(row gather) or two (tau, BR, BK) expansion tables (visit loop), and its
dense operand (``sparse_grid_vmem_bytes``): the K*BF*b slab resident
(10.2 MB at pubmed f32); the K*BF*2 packed slab and 1 MiB of f32 staging
chunks (59.7 MB and 1 MiB at reddit); or eight streamed tiles.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.sparse_formats import PAD_COL


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
# k-tiles of the f32 slab a ``gather_bf16`` launch copies in at a time to
# round and pack into its uint32 slab (512 KiB at 128 x 128), two in
# flight.
_FILL_TILES = 8


def _round_up(x: int, q: int) -> int:
    return -(-x // q) * q


def _pack_rows(k, block_k) -> int:
    return min(_FILL_TILES * block_k // 2, k // 2)


def sparse_grid_vmem_bytes(launch, k, tau, *, dtype, out_dtype,
                           block_rows, block_k, block_f) -> int:
    """VMEM of :func:`spmm_ell_sparse_grid`'s ``launch`` body with a
    ``(k, ·)`` dense operand of ``dtype`` and ``tau`` ELL slots: the dense
    operand's buffers (the single-buffered ``(k, block_f)`` slab of
    ``gather``; the packed slab and its staging chunks of ``gather_bf16``;
    or the visit loop's ``2 * _VISITS_PER_ITER`` streamed tiles), the
    double-buffered ELL slabs (the tau lanes pad to 128) and out block,
    and the gather's ``(tau, block_rows, block_f)`` gathered rows or the
    visit loop's two ``(tau, block_rows, block_k)`` expansion tables."""
    b = jnp.dtype(dtype).itemsize
    if launch == "gather":
        dense = k * block_f * b
    elif launch == "gather_bf16":
        dense = k * block_f * 2 + 4 * _pack_rows(k, block_k) * block_f * b
    else:
        dense = 2 * _VISITS_PER_ITER * block_k * block_f * b
    if launch.startswith("gather"):
        work = tau * block_rows * block_f * 4
    else:
        work = 2 * tau * block_rows * block_k * 4
    ell = 2 * 2 * block_rows * _round_up(tau, 128) * 4
    out = 2 * block_rows * block_f * jnp.dtype(out_dtype).itemsize
    return dense + ell + out + work


def sparse_grid_launch(k, tau, *, dtype, out_dtype, block_rows, block_k,
                       block_f) -> str:
    """The body :func:`spmm_ell_sparse_grid` runs with ``tau`` ELL slots
    and a ``(k, ·)`` dense operand of ``dtype``, from those static shapes
    alone.  Under a float accumulator a slab that fits
    ``RESIDENT_VMEM_BUDGET`` is row-gathered: ``"gather"`` for an f32 slab
    where it lies, ``"gather_bf16"`` for a bf16 one, or an f32 one that
    fits only rounded to bf16 (the MXU's operand form at the default
    precision), packed two rows to a 32-bit word.  Otherwise, past the
    budget or under an integer accumulator, the visit loop runs with each
    visit's tile streamed from HBM: ``"streamed"``."""
    def fits(launch):
        return sparse_grid_vmem_bytes(
            launch, k, tau, dtype=dtype, out_dtype=out_dtype,
            block_rows=block_rows, block_k=block_k,
            block_f=block_f) <= RESIDENT_VMEM_BUDGET

    dtype = jnp.dtype(dtype)
    if _acc_dtype(out_dtype) == jnp.float32:
        if dtype == jnp.float32 and fits("gather"):
            return "gather"
        if dtype in (jnp.float32, jnp.bfloat16) and fits("gather_bf16"):
            return "gather_bf16"
    return "streamed"


def _round_bf16(x):
    """``x`` rounded to bf16 (to nearest even), back in f32."""
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _mxu_factor(x, interpret):
    """A factor of the sparse grid's products as both bodies multiply it:
    compiled for the chip, an f32 factor rounded to bf16 (to nearest
    even), the form the MXU takes an f32 operand in at the default
    precision; interpreted, where the CPU's dot multiplies f32 exactly,
    and for any other dtype, as it is."""
    if interpret or x.dtype != jnp.float32:
        return x
    return _round_bf16(x)


def _sparse_grid_kernel(starts_ref, *refs, block_k, block_f, scaled,
                        per_iter, interpret):
    """One row block: build its expansion tables once, then run its
    visits ``kb_ids[starts[rb]:starts[rb + 1]]`` in the list's order,
    ``per_iter`` at a time and the remainder one by one.

    The row block's k-tile ids come from the list in HBM: a window of it
    is copied into SMEM one grid step ahead.  Each visit's
    ``(block_k, block_f)`` tile is copied in from HBM, the next
    ``per_iter`` tiles while these multiply.
    """
    scales_ref, (cols_ref, vals_ref, dense_ref, kb_hbm, out_ref, kb_win,
                 kb_sem, offs_ref, vtab_ref, tiles,
                 tile_sem) = _split_scales(refs, scaled)
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

    def tile_copy(s):
        buf = jax.lax.rem(s - start, 2 * per_iter)
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
        buf, copy = tile_copy(s)
        copy.wait()
        tile = tiles[buf]
        a_blk = _expand_tile(offs_ref, vtab_ref, kb_of(s) * block_k, tau)
        if scale is not None:
            a_blk = a_blk * scale
        return jax.lax.dot_general(
            _mxu_factor(a_blk, interpret),
            _mxu_factor(tile.astype(acc), interpret),
            (((1,), (0,)), ((), ())), preferred_element_type=out_ref.dtype,
        )

    def visits(i, carry):
        s = start + i * per_iter
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


def _bf16_bits(x):
    """``x`` rounded to bf16 as the high half of 32-bit words whose low
    half is zero: the f32 bits of the rounded value."""
    return jax.lax.bitcast_convert_type(_round_bf16(x), jnp.uint32)


def _fill_packed(dense_hbm, slab, stage, sems, col0):
    """Copy the column slab ``dense_hbm[:, col0:col0 + BF]`` into the
    uint32 VMEM ``slab`` rounded to bf16, two rows to a word: row ``i`` in
    the low half and row ``i + K/2`` in the high half.  Each chunk is two
    runs of rows, one from each half, the next chunk in flight while this
    one is packed; the last chunk ends at the halves' ends, so it may
    store some words a second time."""
    half, chunk, bf = slab.shape[0], stage.shape[2], slab.shape[1]
    n = -(-half // chunk)

    def copies(i):
        row0 = pl.multiple_of(jnp.minimum(i * chunk, half - chunk),
                              int(np.gcd(chunk, half)))
        slot = jax.lax.rem(i, 2)
        return row0, slot, [pltpu.make_async_copy(
            dense_hbm.at[pl.ds(row0 + h * half, chunk), pl.ds(col0, bf)],
            stage.at[slot, h], sems.at[2 * slot + h]) for h in (0, 1)]

    for c in copies(0)[2]:
        c.start()

    def body(i, carry):
        @pl.when(i + 1 < n)
        def _next():
            for c in copies(i + 1)[2]:
                c.start()

        row0, slot, chunk_copies = copies(i)
        for c in chunk_copies:
            c.wait()
        slab[pl.ds(row0, chunk), :] = (_bf16_bits(stage[slot, 1])
                                       | (_bf16_bits(stage[slot, 0]) >> 16))
        return carry

    jax.lax.fori_loop(0, n, body, 0)


def _gather_rows(slab, idx_ref, g_ref):
    """``g[t, r] = slab[idx[t, r]]`` for every slot: one row load at a
    dynamic row and one row store each, eight sub-rows an iteration."""
    tau, br = idx_ref.shape
    unroll = np.gcd(br, 8)

    def gather(j, carry):
        for u in range(unroll):
            r = j * unroll + u
            for t in range(tau):
                g_ref[t, pl.ds(r, 1), :] = slab[pl.ds(idx_ref[t, r], 1), :]
        return carry

    jax.lax.fori_loop(0, br // unroll, gather, 0)


def _gather_kernel(*refs, block_f, scaled, half, interpret):
    """One row block by row gather: copy each ELL slot's dense row
    ``slab[cols[r, t], :]`` into the gathered rows ``g[t, r]``, then add
    ``where(cols[:, t] != PAD_COL, v_t, 0)[:, None] * g[t]`` over the
    slots in order to +0, in f32 on the VPU (``v_t`` widened, times the
    row block's scale under int8), each factor as :func:`_mxu_factor`
    gives it, so the gather's products are the visit loop's and only the
    order of the f32 adds differs.  A pad slot gathers row 0 and adds a
    zero; a sub-row of pads alone is +0.

    ``half`` is None for a 32-bit slab, gathered where it lies (the
    single-buffered dense block); else the slab is the packed uint32
    scratch of :func:`_fill_packed`, filled at each f-tile's first row
    block, and a slot's word holds its row in the low half below ``half``
    and in the high half from it.  The row indices (``cols`` with pads at
    0 and, packed, folded below ``half``) arrive transposed in SMEM."""
    scales_ref, (cols_ref, vals_ref, dense_ref, idx_ref, out_ref, g_ref,
                 *fill) = _split_scales(refs, scaled)
    fi, rb = pl.program_id(0), pl.program_id(1)
    slab = dense_ref
    if half is not None:
        slab = fill[0]

        @pl.when(rb == 0)
        def _fill():
            _fill_packed(dense_ref, *fill,
                         pl.multiple_of(fi * block_f, block_f))

    _gather_rows(slab, idx_ref, g_ref)
    tau = idx_ref.shape[0]
    cols, vals = cols_ref[...], vals_ref[...]
    out = jnp.zeros(out_ref.shape, jnp.float32)
    for t in range(tau):
        v = jnp.where(cols[:, t] != PAD_COL, vals[:, t].astype(jnp.float32),
                      0)
        if scales_ref is not None:
            v = v * scales_ref[rb]
        g = g_ref[t]
        if half is None:
            g = _mxu_factor(g, interpret)
        else:       # a packed row is bf16 already
            g = jax.lax.bitcast_convert_type(
                jnp.where((cols[:, t] >= half)[:, None],
                          g & jnp.uint32(0xFFFF0000), g << 16), jnp.float32)
        out = out + _mxu_factor(v, interpret)[:, None] * g
    real = jnp.max(cols, axis=1, keepdims=True) != PAD_COL
    out_ref[...] = jnp.where(real, out, 0).astype(out_ref.dtype)


def _gather_call(cols, vals, dense, scales, launch, *, block_rows, block_k,
                 block_f, out_dtype, interpret, vmem):
    """The sparse grid's ``gather`` or ``gather_bf16`` body
    (:func:`_gather_kernel`), one grid step per (f-tile, row block)."""
    r, tau = cols.shape
    k, f = dense.shape
    rows = jnp.maximum(cols, 0)
    half = None
    scratch = [pltpu.VMEM((tau, block_rows, block_f), dense.dtype)]
    dense_spec = pl.BlockSpec((k, block_f), lambda fi, rb: (0, fi),
                              pipeline_mode=pl.Buffered(1))
    if launch == "gather_bf16":
        half = k // 2
        rows = jnp.where(rows >= half, rows - half, rows)
        scratch = [pltpu.VMEM((tau, block_rows, block_f), jnp.uint32),
                   pltpu.VMEM((half, block_f), jnp.uint32),
                   pltpu.VMEM((2, 2, _pack_rows(k, block_k), block_f),
                              dense.dtype),
                   pltpu.SemaphoreType.DMA((4,))]
        dense_spec = pl.BlockSpec(memory_space=pl.ANY)
    ell_spec = pl.BlockSpec((block_rows, tau), lambda fi, rb: (rb, 0))
    idx_spec = pl.BlockSpec((tau, block_rows), lambda fi, rb: (0, rb),
                            memory_space=pltpu.SMEM)
    in_specs, args = _with_scales(
        [ell_spec, ell_spec, dense_spec, idx_spec],
        (cols, vals, dense, rows.T), scales, r, block_rows)
    return pl.pallas_call(
        functools.partial(_gather_kernel, block_f=block_f,
                          scaled=scales is not None, half=half,
                          interpret=interpret),
        grid=(f // block_f, r // block_rows),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_rows, block_f),
                               lambda fi, rb: (rb, fi)),
        out_shape=jax.ShapeDtypeStruct((r, f), out_dtype),
        scratch_shapes=scratch,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem + _DEFAULT_SCOPED_VMEM),
        name="flexvector_gather_rows",
    )(*args)


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
    """Block-skipping schedule: grid (f-tile, row block), the body
    :func:`sparse_grid_launch` picks from the operands' static shapes.

    Where the dense slab fits VMEM under a float accumulator, each step
    row-gathers its row block (:func:`_gather_kernel`): it reads neither
    ``starts`` nor ``kb_ids`` and adds each sub-row's products in slot
    order.  Otherwise each step runs its row block's visits
    ``kb_ids[starts[rb]:starts[rb + 1]]`` (``plan_kernel_grid``: every row
    block at least once, hot k-tiles first) in the list's order
    (:func:`_sparse_grid_kernel`): only the run offsets ride in SMEM
    (scalar prefetch), the visit list stays in HBM and each row block's
    window of it is copied into SMEM, and each visit's tile is copied in
    from HBM.  ``scales`` enables int8 dequantize-on-load, as in
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
    launch = sparse_grid_launch(k, tau, **blocks)
    vmem = sparse_grid_vmem_bytes(launch, k, tau, **blocks)
    interpret = _default_interpret(interpret)
    if launch != "streamed":
        return _gather_call(
            cols, vals, dense, scales, launch, block_rows=block_rows,
            block_k=block_k, block_f=block_f, out_dtype=out_dtype,
            interpret=interpret, vmem=vmem)
    # the visit loop, each visit's tile streamed from HBM
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
               pltpu.VMEM((tau, block_rows, block_k), acc),
               pltpu.VMEM((2 * per_iter, block_k, block_f), dense.dtype),
               pltpu.SemaphoreType.DMA((2 * per_iter,))]
    in_specs, args = _with_scales(
        [ell_spec, ell_spec, any_spec, any_spec],
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
            scaled=scales is not None, per_iter=per_iter,
            interpret=interpret,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, f), out_dtype),
        interpret=interpret,
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
