"""Pallas kernel validation: shape/dtype sweeps against the ref.py oracle.

Runs in interpret mode on CPU (the kernel body executes in Python); on a
real TPU the same tests exercise the lowered kernel.
"""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # seeded-sweep fallback, tests/_propcheck.py
    from tests._propcheck import given, settings, strategies as st

from repro.core import preprocess, random_power_law_csr, spmm_ell
from repro.core.dataflow import plan_kernel_grid
from repro.core.spmm import spmm_dense_oracle
from repro.kernels import ops
from repro.kernels.ref import expand_block_ref, spmm_ell_ref
from repro.kernels.flexvector_spmm import pad_operands


def _problem(n, nnz, tau, fdim, seed, dtype=np.float32):
    adj = random_power_law_csr(n, n, nnz, seed=seed, dtype=dtype)
    res = preprocess(adj, tau=tau, tile_rows=16, edge_cut="rcm", dtype=dtype)
    rng = np.random.default_rng(seed + 1)
    dense = rng.standard_normal((n, fdim)).astype(np.float32)
    return res, dense


BLOCKS = [(16, 16, 8), (32, 32, 16), (8, 64, 32)]


@pytest.mark.parametrize("blocks", BLOCKS)
@pytest.mark.parametrize("impl", ["pallas", "pallas_sparse"])
def test_kernel_matches_oracle_f32(blocks, impl):
    br, bk, bf = blocks
    res, dense = _problem(100, 900, 6, 40, seed=0)
    out = spmm_ell(res.ell, jnp.asarray(dense), impl=impl,
                   block_rows=br, block_k=bk, block_f=bf)
    oracle = spmm_dense_oracle(res.ell, dense)
    np.testing.assert_allclose(np.asarray(out, np.float64), oracle,
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("impl", ["pallas", "pallas_sparse"])
def test_kernel_int8_exact(impl):
    import dataclasses

    res, dense = _problem(64, 500, 4, 24, seed=1)
    ell8 = dataclasses.replace(
        res.ell,
        vals=np.clip(np.round(res.ell.vals * 12), -127, 127).astype(np.int8),
    )
    dense8 = np.random.default_rng(2).integers(-9, 9, (64, 24)).astype(np.int8)
    out = spmm_ell(ell8, jnp.asarray(dense8), impl=impl,
                   block_rows=16, block_k=16, block_f=8)
    assert out.dtype == jnp.int32
    oracle = spmm_dense_oracle(ell8, dense8.astype(np.float64))
    assert np.array_equal(np.asarray(out, np.float64), oracle)


def test_kernel_bf16():
    res, dense = _problem(48, 300, 5, 16, seed=3)
    out = ops.flexvector_spmm(
        res.ell, jnp.asarray(dense, jnp.bfloat16),
        block_rows=16, block_k=16, block_f=8,
    )
    ref = spmm_ell_ref(jnp.asarray(res.ell.cols),
                       jnp.asarray(res.ell.vals, jnp.bfloat16),
                       jnp.asarray(dense, jnp.bfloat16))
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=5e-2, atol=5e-2,
    )


@settings(max_examples=10, deadline=None)
@given(
    n=st.integers(16, 96),
    nnz=st.integers(1, 700),
    tau=st.integers(1, 8),
    fdim=st.integers(1, 48),
    seed=st.integers(0, 500),
)
def test_kernel_property_sweep(n, nnz, tau, fdim, seed):
    """Hypothesis sweep: sparse-grid kernel == oracle for random problems."""
    res, dense = _problem(n, nnz, tau, fdim, seed)
    out = spmm_ell(res.ell, jnp.asarray(dense), impl="pallas_sparse",
                   block_rows=16, block_k=16, block_f=16)
    oracle = spmm_dense_oracle(res.ell, dense)
    np.testing.assert_allclose(np.asarray(out, np.float64), oracle,
                               rtol=1e-4, atol=1e-4)


def test_expand_block_matches_ref():
    res, _ = _problem(32, 250, 6, 8, seed=5)
    cols = jnp.asarray(res.ell.cols[:16])
    vals = jnp.asarray(res.ell.vals[:16])
    from repro.kernels.flexvector_spmm import _expand_block

    got = _expand_block(cols, vals, 0, 32, jnp.float32)
    want = expand_block_ref(cols, vals, 0, 32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_sparse_grid_skips_empty_blocks():
    """Block-skipping must visit strictly fewer cells on sparse operands."""
    res, dense = _problem(128, 400, 4, 16, seed=6)
    grid = plan_kernel_grid(res.ell, 16, block_rows=16, block_k=16, block_f=16)
    assert grid.density < 1.0
    assert len(grid.pairs) < grid.n_row_blocks * grid.n_k_tiles
    # row blocks visited consecutively (output-stationary contract)
    rbs = grid.pairs[:, 0]
    changes = (np.diff(rbs) != 0).sum()
    assert changes == len(np.unique(rbs)) - 1


def test_pad_operands_alignment():
    res, dense = _problem(50, 200, 4, 20, seed=7)
    cols, vals, dense_p, (r, f) = pad_operands(
        res.ell.cols, res.ell.vals, jnp.asarray(dense), 32, 32, 16
    )
    assert cols.shape[0] % 32 == 0
    assert dense_p.shape[0] % 32 == 0 and dense_p.shape[1] % 16 == 0
    assert (np.asarray(cols[res.ell.padded_rows:]) == -1).all()


def _parity_operands(case, br, bk, f):
    """``[(cols, vals, starts, kb_ids, k)]`` for the launch-parity test:
    an ELL with all-empty row blocks, or each shard of a two-way split
    whose shorter visit list is padded at its end, as the sharded path
    pads it."""
    import dataclasses

    from repro.core.sparse_formats import PAD_COL
    from repro.exec import SpmmPlan
    from repro.exec.operands import SpmmOperands, shard_operands
    from repro.exec.sharded import _shard_schedules

    res, _ = _problem(96, 800, 5, f, seed=11)
    ell = res.ell
    if case == "empty_row_blocks":
        cols, vals = ell.cols.copy(), ell.vals.copy()
        cols[br:3 * br], vals[br:3 * br] = PAD_COL, 0
        ell = dataclasses.replace(ell, cols=cols, vals=vals)
        g = plan_kernel_grid(ell, f, block_rows=br, block_k=bk, block_f=bk)
        assert (ell.block_occupancy(br, bk).sum(axis=1) == 0).sum() >= 2
        return [(ell.cols, ell.vals, g.starts, g.kb_ids, ell.n_dense_rows)]
    sh = shard_operands(SpmmOperands.from_ell(ell), 2, block_rows=br)
    plan = SpmmPlan(block_rows=br, block_k=bk, block_f=bk)
    starts, kb = _shard_schedules(plan, sh)
    n, per = len(kb) // 2, sh.rows_per_shard
    m = per // br + 1
    shards = [(sh.cols[s * per:(s + 1) * per], sh.vals[s * per:(s + 1) * per],
               starts[s * m:(s + 1) * m], kb[s * n:(s + 1) * n],
               ell.n_dense_rows) for s in range(2)]
    assert any(sd[2][-1] < n for sd in shards), "no shard list was padded"
    return shards


def _schedule_oracle(cols, vals, dense, starts, kb_ids, br, bk, bf,
                     scales=None):
    """The sparse grid's sums, one row block at a time in plain jnp:
    each visit's block expanded as the kernels expand it and multiplied
    by its dense tile, the products added in the list's order."""
    from repro.kernels.flexvector_spmm import _acc_dtype, _expand_block

    r, f = cols.shape[0], dense.shape[1]
    out_dtype = _acc_dtype(dense.dtype)
    blocks = []
    for rb in range(r // br):
        rows = slice(rb * br, (rb + 1) * br)
        tiles = []
        for fi in range(f // bf):
            acc = jnp.zeros((br, bf), out_dtype)
            for kb in kb_ids[starts[rb]:starts[rb + 1]]:
                a = _expand_block(cols[rows], vals[rows], int(kb) * bk, bk,
                                  out_dtype)
                if scales is not None:
                    a = a * scales[rb].astype(out_dtype)
                tile = dense[int(kb) * bk:(int(kb) + 1) * bk,
                             fi * bf:(fi + 1) * bf]
                acc = acc + jax.lax.dot_general(
                    a, tile.astype(out_dtype), (((1,), (0,)), ((), ())),
                    preferred_element_type=out_dtype)
            tiles.append(acc)
        blocks.append(jnp.concatenate(tiles, axis=1))
    return np.asarray(jnp.concatenate(blocks, axis=0))


def _slot_order_oracle(cols, vals, dense, br, scales=None,
                       bf16_products=False):
    """The row gather's sums in plain jnp f32: per sub-row, over its ELL
    slots in order, ``where(col != PAD_COL, v, 0) * dense[col]`` added to
    +0, where ``v`` is the value widened to f32 and, under int8, times its
    row block's scale; a pad slot gathers row 0, and a sub-row of pads
    alone is +0.  With ``bf16_products`` both factors are rounded to bf16
    first, as the gather compiled for the chip rounds them.  Jitted, as
    the interpreted kernel is, so the CPU compiler fuses each multiply
    and add alike on both sides."""
    from repro.core.sparse_formats import PAD_COL
    from repro.kernels.flexvector_spmm import _block_scales

    @jax.jit
    def sums(cols, vals, dense, scales):
        r, tau = cols.shape
        rows = dense.astype(jnp.float32)[jnp.maximum(cols, 0)]
        v = vals.astype(jnp.float32)
        out = jnp.zeros((r, rows.shape[-1]), jnp.float32)
        for t in range(tau):
            w = jnp.where(cols[:, t] != PAD_COL, v[:, t], 0)
            if scales is not None:
                w = w * jnp.repeat(_block_scales(scales, r, br), br)
            g = rows[:, t]
            if bf16_products:
                w, g = (a.astype(jnp.bfloat16).astype(jnp.float32)
                        for a in (w, g))
            out = out + w[:, None] * g
        real = (cols != PAD_COL).any(axis=1)
        return jnp.where(real[:, None], out, 0)

    return np.asarray(sums(jnp.asarray(cols), jnp.asarray(vals),
                           jnp.asarray(dense), scales))


def _run_body(fv, monkeypatch, body, c, v, d, starts, kb, br, bk,
              scales=None):
    """The sparse grid on these operands, running ``body``: the streamed
    visit loop by no VMEM budget, else the row gather the shape rule
    picks at the default budget (``gather``, or ``gather_bf16`` for a
    16-bit slab)."""
    if body == "streamed":
        monkeypatch.setattr(fv, "RESIDENT_VMEM_BUDGET", 0)
    blocks = dict(dtype=d.dtype, out_dtype=fv._acc_dtype(d.dtype),
                  block_rows=br, block_k=bk, block_f=bk)
    assert fv.sparse_grid_launch(d.shape[0], c.shape[1], **blocks) == body
    out = np.asarray(fv.spmm_ell_sparse_grid(
        c, v, d, jnp.asarray(starts), jnp.asarray(kb), block_rows=br,
        block_k=bk, block_f=bk, interpret=True, scales=scales))
    monkeypatch.undo()
    return out


def _agree(got, want, precision):
    """Two summation orders of one product set agree within the kernels'
    bound against the reference impl."""
    from tests.test_exec import KERNEL_VS_REFERENCE

    np.testing.assert_allclose(
        got, want, rtol=0,
        atol=KERNEL_VS_REFERENCE[precision] * np.abs(want).max())


def _integer_operands(vals, k, f, seed):
    """int8 values and an int8 dense operand, whose sums accumulate in
    int32, where no gather exists."""
    vals = np.clip(np.round(np.asarray(vals, np.float32) * 12), -127, 127)
    dense = np.random.default_rng(seed).integers(-9, 9, (k, f))
    return vals.astype(np.int8), jnp.asarray(dense, jnp.int8)


@pytest.mark.parametrize("case", ["empty_row_blocks", "shard_padding"])
@pytest.mark.parametrize("precision", ["f32", "bf16", "int8", "integer"])
def test_resident_and_streamed_launches_bitwise_equal(precision, case,
                                                      monkeypatch):
    """Each body of the sparse grid equals its own summation order's oracle
    bitwise: the visit loop, which a slab past the budget streams, visits
    the tiles in the list's order through the same expansion and dot (the
    schedule done one visit at a time); the row gather a resident float
    slab takes adds each sub-row's slots in slot order (a 16-bit slab
    packed two rows to a word).  The two orders agree within the kernels'
    bound against the reference impl.  Under an integer accumulator the
    visit loop runs whatever the slab's size, to the exact sums."""
    from repro.exec import quant
    from repro.kernels import flexvector_spmm as fv

    br = bk = 16
    f = 32
    for cols, vals, starts, kb, k in _parity_operands(case, br, bk, f):
        dense = np.random.default_rng(k).standard_normal((k, f))
        scales = None
        if precision == "integer":
            vals, dense = _integer_operands(vals, k, f, k)
        elif precision == "int8":
            vals, scales = quant.quantize_values(vals, br)
            scales = jnp.asarray(scales)
        elif precision == "bf16":
            vals = jnp.asarray(vals, jnp.bfloat16)
        if precision != "integer":
            dense = quant.cast_dense(jnp.asarray(dense, jnp.float32),
                                     precision)
        c, v, d, _ = pad_operands(cols, vals, dense, br, bk, bk)
        run = functools.partial(_run_body, fv, monkeypatch, c=c, v=v, d=d,
                                starts=starts, kb=kb, br=br, bk=bk,
                                scales=scales)
        streamed = run("streamed")
        assert np.abs(streamed).max() > 0
        np.testing.assert_array_equal(streamed, _schedule_oracle(
            c, v, d, starts, kb, br, bk, bk, scales))
        if precision == "integer":
            assert fv.sparse_grid_launch(
                d.shape[0], c.shape[1], dtype=d.dtype, out_dtype=jnp.int32,
                block_rows=br, block_k=bk, block_f=bk) == "streamed"
            continue
        gathered = run("gather" if precision == "f32" else "gather_bf16")
        np.testing.assert_array_equal(gathered, _slot_order_oracle(
            c, v, d, br, scales))
        _agree(gathered, streamed, precision)


@pytest.mark.parametrize("visits_per_iter", [1, 2, 3])
def test_resident_launch_keeps_the_visit_order(visits_per_iter,
                                               monkeypatch):
    """However many visits one loop iteration runs, their products are
    added in the visit list's order, float or integer: the result is
    bitwise equal to the schedule done one visit at a time, on row blocks
    with odd numbers of visits and on lists longer than one window.  The
    row gather on the same float operands is its slot-order oracle
    bitwise, and agrees with the visit loop within the kernels' bound."""
    from repro.kernels import flexvector_spmm as fv

    res, dense = _problem(96, 900, 6, 16, seed=4)
    g = plan_kernel_grid(res.ell, 16, block_rows=16, block_k=16, block_f=16)
    assert (np.diff(g.starts) % 2 == 1).any()

    def run(body, vals, dense):
        c, v, d, _ = pad_operands(res.ell.cols, vals, dense, 16, 16, 16)
        monkeypatch.setattr(fv, "_VISITS_PER_ITER", visits_per_iter)
        monkeypatch.setattr(fv, "_KB_ALIGN", 8)
        got = _run_body(fv, monkeypatch, body, c, v, d, g.starts, g.kb_ids,
                        16, 16)
        return got, _schedule_oracle(c, v, d, g.starts, g.kb_ids, 16, 16, 16)

    streamed, want = run("streamed", res.ell.vals, jnp.asarray(dense))
    np.testing.assert_array_equal(streamed, want)
    np.testing.assert_array_equal(*run("streamed", *_integer_operands(
        res.ell.vals, dense.shape[0], 16, 4)))
    gathered, _ = run("gather", res.ell.vals, jnp.asarray(dense))
    c, v, d, _ = pad_operands(res.ell.cols, res.ell.vals, jnp.asarray(dense),
                              16, 16, 16)
    np.testing.assert_array_equal(gathered, _slot_order_oracle(c, v, d, 16))
    _agree(gathered, want, "f32")


@pytest.mark.parametrize("resident", [True, False])
@pytest.mark.parametrize("precision", ["f32", "bf16"])
def test_sparse_grid_matches_the_reference_impl(precision, resident,
                                                monkeypatch):
    """On a power-law graph, both residencies of the sparse grid give the
    ``reference`` impl's sums within the precision's tolerance."""
    from repro.exec import SpmmPlan, sub_row_products
    from repro.kernels import flexvector_spmm as fv

    res, dense = _problem(300, 4000, 6, 40, seed=9)
    if not resident:
        monkeypatch.setattr(fv, "RESIDENT_VMEM_BUDGET", 0)
    dt = jnp.float32 if precision == "f32" else jnp.bfloat16
    vals, x = jnp.asarray(res.ell.vals, dt), jnp.asarray(dense, dt)
    outs = {}
    for impl in ("reference", "pallas_sparse"):
        plan = SpmmPlan(impl=impl, block_rows=32, block_k=32, block_f=16,
                        interpret=True, precision=precision
                        ).resolve(schedulable=True)
        outs[impl] = np.asarray(sub_row_products(
            plan, jnp.asarray(res.ell.cols), vals, x, ell=res.ell),
            np.float32)
    tol = 1e-5 if precision == "f32" else 2e-2
    np.testing.assert_allclose(outs["pallas_sparse"], outs["reference"],
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("fill_tiles", [1, 2, 3])
def test_bf16_slab_launch_is_the_launch_on_the_rounded_operand(
        fill_tiles, monkeypatch):
    """An f32 slab that fits the budget only rounded to bf16 is row-gathered
    packed two rows to a word, filled from both halves of the slab a chunk
    at a time (``fill_tiles`` k-tiles a chunk, the last one clamped to the
    halves' ends where it overhangs) and rounded on the way.  It is
    bitwise the 32-bit gather and the slot-order oracle on the
    bf16-rounded operand, with every product exact as on the CPU, and
    agrees within the kernels' bound with the visit loop on that operand,
    itself the one-visit-at-a-time schedule.  Two f-tiles."""
    from repro.kernels import flexvector_spmm as fv

    res, dense = _problem(300, 3000, 6, 32, seed=4)
    g = plan_kernel_grid(res.ell, 16, block_rows=16, block_k=16, block_f=16)
    c, v, d, _ = pad_operands(res.ell.cols, res.ell.vals,
                              jnp.asarray(dense), 16, 16, 16)
    rounded = d.astype(jnp.bfloat16).astype(jnp.float32)
    monkeypatch.setattr(fv, "_FILL_TILES", fill_tiles)
    k = d.shape[0]
    assert ((k // 2) % (fill_tiles * 16 // 2) > 0) == (fill_tiles > 1)
    blocks = dict(dtype=jnp.float32, out_dtype=jnp.float32, block_rows=16,
                  block_k=16, block_f=16)

    def run(dense, budget):
        monkeypatch.setattr(fv, "RESIDENT_VMEM_BUDGET", budget)
        return np.asarray(fv.spmm_ell_sparse_grid(
            c, v, dense, jnp.asarray(g.starts), jnp.asarray(g.kb_ids),
            block_rows=16, block_k=16, block_f=16, interpret=True))

    packed_need = fv.sparse_grid_vmem_bytes("gather_bf16", k, 6, **blocks)
    assert packed_need < fv.sparse_grid_vmem_bytes("gather", k, 6, **blocks)
    monkeypatch.setattr(fv, "RESIDENT_VMEM_BUDGET", packed_need)
    assert fv.sparse_grid_launch(k, 6, **blocks) == "gather_bf16"
    packed = run(d, packed_need)
    assert np.abs(packed).max() > 0
    np.testing.assert_array_equal(packed, run(rounded, 2**30))
    np.testing.assert_array_equal(packed, _slot_order_oracle(c, v, rounded,
                                                             16))
    visit_loop = run(rounded, 0)
    np.testing.assert_array_equal(visit_loop, _schedule_oracle(
        c, v, rounded, g.starts, g.kb_ids, 16, 16, 16))
    _agree(packed, visit_loop, "f32")


@pytest.mark.parametrize("launch,dtype", [
    ("gather", jnp.float32), ("gather_bf16", jnp.float32),
    ("gather_bf16", jnp.bfloat16)])
def test_gather_reads_the_pair_packing_edges_and_pads(launch, dtype,
                                                      monkeypatch):
    """Columns 0, K/2 - 1, K/2 and K - 1, the rows at either edge of each
    half of a packed slab, come back exactly (the bf16-rounded slab's
    rows); a sub-row of pad slots sums to +0 though its pads gather a row
    of negatives; and the launch agrees with the visit loop."""
    from repro.core.sparse_formats import PAD_COL
    from repro.kernels import flexvector_spmm as fv

    br = bk = 16
    k, r, tau = 256, 32, 4
    edges = [0, k // 2 - 1, k // 2, k - 1]
    rng = np.random.default_rng(3)
    cols = np.full((r, tau), PAD_COL, np.int32)
    cols[0], cols[1], cols[17] = edges, edges[::-1], [edges[2], PAD_COL,
                                                      5, edges[1]]
    cols[2:5, 0] = edges[1:]
    vals = np.where(cols != PAD_COL, rng.standard_normal((r, tau)), 0)
    dense = rng.standard_normal((k, 2 * br))
    dense[0] = -np.abs(dense[0]) - 1
    c, v = jnp.asarray(cols), jnp.asarray(vals, jnp.float32)
    d = jnp.asarray(dense, dtype)
    starts = np.array([0, k // bk, 2 * k // bk], np.int32)
    kb = np.tile(np.arange(k // bk, dtype=np.int32), 2)
    blocks = dict(dtype=d.dtype, out_dtype=jnp.float32, block_rows=br,
                  block_k=bk, block_f=br)
    monkeypatch.setattr(fv, "_FILL_TILES", 1)
    if launch == "gather_bf16" and dtype == jnp.float32:
        monkeypatch.setattr(fv, "RESIDENT_VMEM_BUDGET",
                            fv.sparse_grid_vmem_bytes("gather_bf16", k, tau,
                                                      **blocks))
    assert fv.sparse_grid_launch(k, tau, **blocks) == launch
    got = np.asarray(fv.spmm_ell_sparse_grid(
        c, v, d, jnp.asarray(starts), jnp.asarray(kb), block_rows=br,
        block_k=bk, block_f=br, interpret=True))
    rounded = d if launch == "gather" else d.astype(jnp.bfloat16)
    np.testing.assert_array_equal(got, _slot_order_oracle(c, v, rounded, br))
    single = np.asarray(rounded, np.float32)
    for i, col in zip(range(2, 5), edges[1:]):
        np.testing.assert_array_equal(got[i], vals[i, 0].astype(np.float32)
                                      * single[col])
    pads = np.all(cols == PAD_COL, axis=1)
    assert pads.sum() > 1
    assert (got[pads] == 0).all() and not np.signbit(got[pads]).any()
    monkeypatch.setattr(fv, "RESIDENT_VMEM_BUDGET", 0)
    visit_loop = np.asarray(fv.spmm_ell_sparse_grid(
        c, v, d if dtype == jnp.bfloat16 else rounded.astype(jnp.float32),
        jnp.asarray(starts), jnp.asarray(kb), block_rows=br, block_k=bk,
        block_f=br, interpret=True))
    _agree(got, visit_loop, "f32")


@pytest.mark.parametrize("precision", ["f32", "int8"])
def test_gather_multiplies_bf16_operands_on_the_chip(precision,
                                                     monkeypatch):
    """Under the chip's precision rule (``_mxu_factor`` as compiled: both
    factors of each product rounded to bf16, the MXU's operand form at the
    default precision), ``spmm_ell_sparse_grid`` run interpreted
    row-gathers to the slot-order oracle of rounded factors bitwise, and
    away from the f32 products it forms interpreted.  At f32 it is the
    gather of f32 products on pre-rounded operands, and the visit loop
    under the same rule, the schedule on those operands, agrees with it
    within the kernels' bound."""
    from repro.exec import quant
    from repro.kernels import flexvector_spmm as fv

    br = bk = 16
    res, dense = _problem(96, 900, 6, 32, seed=8)
    g = plan_kernel_grid(res.ell, 32, block_rows=br, block_k=bk,
                         block_f=bk)
    vals, scales = res.ell.vals, None
    if precision == "int8":
        vals, scales = quant.quantize_values(vals, br)
        scales = jnp.asarray(scales)
    c, v, d, _ = pad_operands(res.ell.cols, vals, jnp.asarray(dense), br, bk,
                              bk)
    factor = fv._mxu_factor

    def run(v, d, *, on_chip, budget=None):
        if on_chip:
            monkeypatch.setattr(fv, "_mxu_factor",
                                lambda x, interpret: factor(x, False))
        if budget is not None:
            monkeypatch.setattr(fv, "RESIDENT_VMEM_BUDGET", budget)
        out = np.asarray(fv.spmm_ell_sparse_grid(
            c, v, d, jnp.asarray(g.starts), jnp.asarray(g.kb_ids),
            block_rows=br, block_k=bk, block_f=bk, interpret=True,
            scales=scales))
        monkeypatch.undo()
        return out

    assert fv.sparse_grid_launch(d.shape[0], c.shape[1], dtype=d.dtype,
                                 out_dtype=jnp.float32, block_rows=br,
                                 block_k=bk, block_f=bk) == "gather"
    got = run(v, d, on_chip=True)
    np.testing.assert_array_equal(got, _slot_order_oracle(
        c, v, d, br, scales, bf16_products=True))
    assert not np.array_equal(got, run(v, d, on_chip=False))
    if precision == "f32":
        rv, rd = (a.astype(jnp.bfloat16).astype(jnp.float32) for a in (v, d))
        np.testing.assert_array_equal(got, run(rv, rd, on_chip=False))
        visit_loop = run(v, d, on_chip=True, budget=0)
        np.testing.assert_array_equal(visit_loop, _schedule_oracle(
            c, rv, rd, g.starts, g.kb_ids, br, bk, bk))
        _agree(got, visit_loop, "f32")


@pytest.mark.parametrize("nodes,dtype,launch,need", [
    (19_717, jnp.float32, "gather", 10_158_080 + 786_432),       # pubmed
    (3_327, jnp.float32, "gather", 1_703_936 + 786_432),         # citeseer
    (232_965, jnp.float32, "gather_bf16",                        # reddit
     59_670_528 + 1_048_576 + 786_432),
    (19_717, jnp.bfloat16, "gather_bf16", 5_079_040 + 524_288 + 786_432),
    (2_449_029, jnp.float32, "streamed", 524_288 + 1_179_648),   # products
    (19_717, jnp.int8, "streamed", 131_072 + 1_179_648),
])
def test_sparse_grid_launch_follows_the_dense_slab(nodes, dtype, launch,
                                                   need):
    """Under a float accumulator every slab that fits the VMEM budget is
    row-gathered: pubmed's and citeseer's f32 slabs where they lie,
    reddit's 119 MB one only rounded to bf16 and packed (60 MB, with two
    pairs of 256 KiB staging chunks), as a bf16 operand is.  A slab past
    the budget (ogbn-products), or any under an integer accumulator, has
    the visit loop stream its tiles.  The bytes are the module
    docstring's figures."""
    from repro.kernels import flexvector_spmm as fv

    k = -(-nodes // 128) * 128
    blocks = dict(dtype=dtype, out_dtype=fv._acc_dtype(dtype),
                  block_rows=128, block_k=128, block_f=128)
    assert fv.sparse_grid_launch(k, 6, **blocks) == launch
    assert fv.sparse_grid_vmem_bytes(launch, k, 6, **blocks) == need
    assert need <= fv.RESIDENT_VMEM_BUDGET
