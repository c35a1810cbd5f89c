"""Pallas kernel validation: shape/dtype sweeps against the ref.py oracle.

Runs in interpret mode on CPU (the kernel body executes in Python); on a
real TPU the same tests exercise the lowered kernel.
"""

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


@pytest.mark.parametrize("case", ["empty_row_blocks", "shard_padding"])
@pytest.mark.parametrize("precision", ["f32", "bf16", "int8"])
def test_resident_and_streamed_launches_bitwise_equal(precision, case,
                                                      monkeypatch):
    """Whether the dense slab is resident in VMEM or each visit's tile is
    copied in, the sparse grid visits the same tiles in the same order
    through the same expansion and dot: its sub-row products are bitwise
    equal, to each other and to the schedule done one visit at a time."""
    from repro.exec import quant
    from repro.kernels import flexvector_spmm as fv

    br = bk = 16
    f = 32
    for cols, vals, starts, kb, k in _parity_operands(case, br, bk, f):
        dense = np.random.default_rng(k).standard_normal((k, f))
        scales = None
        if precision == "int8":
            vals, scales = quant.quantize_values(vals, br)
            scales = jnp.asarray(scales)
        elif precision == "bf16":
            vals = jnp.asarray(vals, jnp.bfloat16)
        dense = quant.cast_dense(jnp.asarray(dense, jnp.float32), precision)
        c, v, d, _ = pad_operands(cols, vals, dense, br, bk, bk)
        run = lambda: np.asarray(fv.spmm_ell_sparse_grid(  # noqa: E731
            c, v, d, jnp.asarray(starts), jnp.asarray(kb), block_rows=br,
            block_k=bk, block_f=bk, interpret=True, scales=scales))
        resident = run()
        monkeypatch.setattr(fv, "RESIDENT_VMEM_BUDGET", 0)
        streamed = run()
        monkeypatch.undo()
        assert np.abs(streamed).max() > 0
        np.testing.assert_array_equal(resident, streamed)
        np.testing.assert_array_equal(resident, _schedule_oracle(
            c, v, d, starts, kb, br, bk, bk, scales))


@pytest.mark.parametrize("visits_per_iter", [1, 2, 3])
def test_resident_launch_keeps_the_visit_order(visits_per_iter,
                                               monkeypatch):
    """However many visits one loop iteration runs, resident or streamed,
    their products are added in the visit list's order: the result is
    bitwise equal to the schedule done one visit at a time, on row blocks
    with odd numbers of visits and on lists longer than one window."""
    from repro.kernels import flexvector_spmm as fv

    res, dense = _problem(96, 900, 6, 16, seed=4)
    g = plan_kernel_grid(res.ell, 16, block_rows=16, block_k=16, block_f=16)
    assert (np.diff(g.starts) % 2 == 1).any()
    c, v, d, _ = pad_operands(res.ell.cols, res.ell.vals,
                              jnp.asarray(dense), 16, 16, 16)
    want = _schedule_oracle(c, v, d, g.starts, g.kb_ids, 16, 16, 16)
    monkeypatch.setattr(fv, "_VISITS_PER_ITER", visits_per_iter)
    monkeypatch.setattr(fv, "_KB_ALIGN", 8)
    for budget in (fv.RESIDENT_VMEM_BUDGET, 0):
        monkeypatch.setattr(fv, "RESIDENT_VMEM_BUDGET", budget)
        got = np.asarray(fv.spmm_ell_sparse_grid(
            c, v, d, jnp.asarray(g.starts), jnp.asarray(g.kb_ids),
            block_rows=16, block_k=16, block_f=16, interpret=True))
        np.testing.assert_array_equal(got, want)


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


@pytest.mark.parametrize("visits_per_iter", [1, 2, 3])
def test_bf16_slab_launch_is_the_launch_on_the_rounded_operand(
        visits_per_iter, monkeypatch):
    """An f32 slab that fits the budget only rounded to bf16 is filled
    into VMEM in chunks (the last one clamped to the slab's end) and
    rounded on the way: the launch is bitwise the ``resident`` and
    ``streamed`` launches, and the one-visit-at-a-time schedule, run on
    the bf16-rounded operand, with every product exact as on the CPU.
    Row blocks with odd numbers of visits, two f-tiles, lists longer than
    one window."""
    from repro.kernels import flexvector_spmm as fv

    res, dense = _problem(300, 3000, 6, 32, seed=4)
    g = plan_kernel_grid(res.ell, 16, block_rows=16, block_k=16, block_f=16)
    assert (np.diff(g.starts) % 2 == 1).any()
    c, v, d, _ = pad_operands(res.ell.cols, res.ell.vals,
                              jnp.asarray(dense), 16, 16, 16)
    rounded = d.astype(jnp.bfloat16).astype(jnp.float32)
    monkeypatch.setattr(fv, "_VISITS_PER_ITER", visits_per_iter)
    monkeypatch.setattr(fv, "_KB_ALIGN", 8)
    monkeypatch.setattr(fv, "_FILL_TILES", 3)
    k = d.shape[0]
    assert len(g.kb_ids) > fv._round_up(k // 16 + 8 - 1, 8)   # one window
    assert k % (3 * 16)                # the last chunk is clamped
    blocks = dict(dtype=jnp.float32, out_dtype=jnp.float32, block_rows=16,
                  block_k=16, block_f=16)

    def run(dense, budget):
        monkeypatch.setattr(fv, "RESIDENT_VMEM_BUDGET", budget)
        return np.asarray(fv.spmm_ell_sparse_grid(
            c, v, dense, jnp.asarray(g.starts), jnp.asarray(g.kb_ids),
            block_rows=16, block_k=16, block_f=16, interpret=True))

    bf16_need = fv.sparse_grid_vmem_bytes("resident_bf16", k, 6, **blocks)
    assert bf16_need < fv.sparse_grid_vmem_bytes("resident", k, 6, **blocks)
    monkeypatch.setattr(fv, "RESIDENT_VMEM_BUDGET", bf16_need)
    assert fv.sparse_grid_residency(k, 6, **blocks) == "resident_bf16"
    got = run(d, bf16_need)
    assert np.abs(got).max() > 0
    np.testing.assert_array_equal(got, run(rounded, 2**30))
    np.testing.assert_array_equal(got, run(rounded, 0))
    np.testing.assert_array_equal(got, _schedule_oracle(
        c, v, rounded, g.starts, g.kb_ids, 16, 16, 16))


@pytest.mark.parametrize("nodes,residency,need", [
    (19_717, "resident", 10_158_080 + 1_179_648),           # pubmed
    (232_965, "resident_bf16", 59_670_528 + 1_048_576 + 1_179_648),  # reddit
    (2_449_029, "streamed", 524_288 + 1_179_648),       # ogbn-products
])
def test_sparse_grid_launch_follows_the_dense_slab(nodes, residency, need):
    """pubmed's f32 dense slab fits the VMEM budget and runs resident;
    reddit's 119 MB slab fits only rounded to bf16 (60 MB, with two 512
    KiB staging chunks); a slab past both streams its tiles.  The bytes
    are the module docstring's figures."""
    from repro.kernels import flexvector_spmm as fv

    k = -(-nodes // 128) * 128
    blocks = dict(dtype=jnp.float32, out_dtype=jnp.float32, block_rows=128,
                  block_k=128, block_f=128)
    assert fv.sparse_grid_residency(k, 6, **blocks) == residency
    assert fv.sparse_grid_vmem_bytes(residency, k, 6, **blocks) == need
    assert need <= fv.RESIDENT_VMEM_BUDGET
    # an integer accumulator, or a slab already bf16, never takes the
    # bf16 residency
    assert fv.sparse_grid_residency(
        k, 6, **dict(blocks, out_dtype=jnp.int32)) != "resident_bf16"
    assert fv.sparse_grid_residency(
        k, 6, **dict(blocks, dtype=jnp.bfloat16)) != "resident_bf16"