"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Interpret mode on the CPU cannot show what the TPU compiler refuses: a
block that breaks the (8, 128) tiling rule, a list past SMEM, a kernel
past VMEM.  These tests compile each kernel for a described (not
attached) v5e chip, at pubmed's Table III size cut into the serving
config's tiles: 25,856 vertex-cut rows of ``tau=6``, 19,717 dense rows
padded to 19,840, 500 input features, 128-wide feature tiles, and the
14,281-visit block-skipping list pubmed's ELL gets; the sparse grid also
at reddit's: 4,205,568 rows, 233,088 dense rows, 17,685,868 visits over
32,856 row blocks.  Both graphs' dense slabs fit VMEM, so the sparse
grid row-gathers them; its visit loop, which streams a slab past the
budget, is compiled at both sizes too.

The topology is described inside a fixture, never at import, so only
the test worker that runs this file loads the TPU compiler; the
persistent compile cache is off around these compiles (an entry written
for a described chip cannot be read back without one).
"""

import os

import pytest

ROWS, TAU, K, K_REAL, F, F_IN = 25_856, 6, 19_840, 19_717, 128, 500
VISITS, BLOCK = 14_281, 128
# reddit at Table III size (graph seed 0, tau 6, 128-row blocks)
REDDIT_ROWS, REDDIT_K, REDDIT_VISITS = 4_205_568, 233_088, 17_685_868
PRECISIONS = ("f32", "bf16", "int8")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def shape(topo):
    """``shape(dims, dtype)`` -> an abstract array on one described chip."""
    import jax
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype,
                                                    sharding=one_chip)


def _dtypes(precision):
    import jax.numpy as jnp

    vals = {"f32": jnp.float32, "bf16": jnp.bfloat16, "int8": jnp.int8}
    act = jnp.float32 if precision == "f32" else jnp.bfloat16
    return vals[precision], act


def _compile(fn, *args):
    """Compile for the described chip; returns the compiled text."""
    import jax

    args = [a for a in args if a is not None]
    return jax.jit(fn).lower(*args).compile().as_text()


def _scales(shape, precision, rows=ROWS):
    import jax.numpy as jnp

    return shape((rows // BLOCK,), jnp.float32) if precision == "int8" \
        else None


@pytest.mark.parametrize("precision", PRECISIONS)
def test_dense_grid_compiles(shape, precision):
    import jax.numpy as jnp

    from repro.kernels import flexvector_spmm as fv

    vdt, adt = _dtypes(precision)
    sc = _scales(shape, precision)
    text = _compile(
        lambda c, v, d, *s: fv.spmm_ell_dense_grid(
            c, v, d, interpret=False, scales=s[0] if s else None),
        shape((ROWS, TAU), jnp.int32), shape((ROWS, TAU), vdt),
        shape((K, F), adt), sc)
    assert "tpu_custom_call" in text


def _sparse_grid(shape, precision, k=K, rows=ROWS, visits=VISITS):
    import jax.numpy as jnp

    from repro.kernels import flexvector_spmm as fv

    vdt, adt = _dtypes(precision)
    return _compile(
        lambda c, v, d, st, kb, *s: fv.spmm_ell_sparse_grid(
            c, v, d, st, kb, interpret=False, scales=s[0] if s else None),
        shape((rows, TAU), jnp.int32), shape((rows, TAU), vdt),
        shape((k, F), adt), shape((rows // BLOCK + 1,), jnp.int32),
        shape((visits,), jnp.int32), _scales(shape, precision, rows))


@pytest.mark.parametrize("precision", PRECISIONS)
def test_sparse_grid_compiles(shape, precision, monkeypatch):
    """The streamed residency (dense slabs past the resident VMEM budget,
    reached here by a zero budget): each visit's tile copied in."""
    from repro.kernels import flexvector_spmm as fv

    monkeypatch.setattr(fv, "RESIDENT_VMEM_BUDGET", 0)
    assert "%flexvector_sparse_grid_rows" in _sparse_grid(shape, precision)


def _launch(precision, k=K, acc=None):
    import jax.numpy as jnp

    from repro.kernels import flexvector_spmm as fv

    return fv.sparse_grid_launch(
        k, TAU, block_rows=BLOCK, block_k=BLOCK, block_f=F,
        dtype=_dtypes(precision)[1], out_dtype=acc or jnp.float32)


@pytest.mark.parametrize("precision", PRECISIONS)
def test_sparse_grid_resident_compiles(shape, precision):
    """At pubmed size the dense slab fits VMEM and the sparse grid
    row-gathers it: the f32 slab single-buffered and gathered where it
    lies, a bf16 one (bf16 and int8 storage) packed two rows to a 32-bit
    word."""
    assert _launch(precision) == ("gather" if precision == "f32"
                                  else "gather_bf16")
    assert "%flexvector_gather_rows" in _sparse_grid(shape, precision)


@pytest.mark.parametrize("precision,launch,budget", [
    ("f32", "gather_bf16", None), ("bf16", "gather_bf16", None),
    ("f32", "streamed", 0)])
def test_sparse_grid_compiles_at_reddit_size(shape, precision, launch,
                                             budget, monkeypatch):
    """At reddit's size the f32 slab (119 MB) fits VMEM only rounded to
    bf16 and packed two rows to a word (60 MB), as the bf16 one does, and
    the row gather compiles; with no budget the f32 slab streams its tiles
    through the visit loop, whose run offsets fit SMEM while the visit
    list stays in HBM.  Each fits VMEM."""
    from repro.kernels import flexvector_spmm as fv

    if budget is not None:
        monkeypatch.setattr(fv, "RESIDENT_VMEM_BUDGET", budget)
    assert _launch(precision, k=REDDIT_K) == launch
    name = "flexvector_gather_rows" if launch.startswith("gather") else (
        "flexvector_sparse_grid_rows")
    assert f"%{name}" in _sparse_grid(shape, precision, k=REDDIT_K,
                                      rows=REDDIT_ROWS, visits=REDDIT_VISITS)


def test_a_16_bit_slab_is_gathered_as_32_bit_words(shape):
    """Mosaic refuses a one-row load at a dynamic row of a bf16 ref (it
    cannot prove the row a multiple of 8), so a 16-bit slab is gathered
    from its packed uint32 words: in the launch for a bf16 operand every
    load of a 16-bit ref reads whole tiles (the fill's staging chunks),
    and the launch compiles."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import flexvector_spmm as fv

    assert _launch("bf16") == "gather_bf16"
    args = [jax.ShapeDtypeStruct(a, t) for a, t in [
        ((ROWS, TAU), jnp.int32), ((ROWS, TAU), jnp.bfloat16),
        ((K, F), jnp.bfloat16), ((ROWS // BLOCK + 1,), jnp.int32),
        ((VISITS,), jnp.int32)]]
    closed = jax.make_jaxpr(lambda *a: fv.spmm_ell_sparse_grid(
        *a, interpret=False))(*args)

    def loads(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "get":
                yield eqn.invars[0].aval, eqn.outvars[0].aval
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from loads(sub)

    (call,) = [e for e in closed.jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    found = list(loads(call.params["jaxpr"]))
    assert any(ref.dtype == jnp.uint32 and out.shape[-2] == 1
               for ref, out in found), "no row of the packed slab is loaded"
    for ref, out in found:
        if jnp.dtype(ref.dtype).itemsize == 2:
            assert out.shape[-2:] == ref.shape[-2:], (ref, out)
    assert "%flexvector_gather_rows" in _sparse_grid(shape, "bf16")


def _largest_k(launch, k):
    """The largest dense operand (in 128-row steps from ``k``) that takes
    ``launch`` at f32."""
    assert _launch("f32", k=k) == launch
    while _launch("f32", k=k + BLOCK) == launch:
        k += BLOCK
    return k


def test_largest_resident_slab_compiles(shape):
    """``RESIDENT_VMEM_BUDGET`` is sound at its edge: the largest f32
    dense operand it admits whole compiles with the row gather."""
    k = _largest_k("gather", K)
    assert k > 4 * K
    assert "%flexvector_gather_rows" in _sparse_grid(
        shape, "f32", k=k, rows=8 * BLOCK)


def test_largest_bf16_slab_compiles(shape):
    """The budget is sound at the packed slab's edge too: the largest f32
    operand that fits only rounded to bf16 (254,976 rows, the packed slab
    and its staging chunks within 64 MiB) compiles with the packed row
    gather, and one k-tile more streams."""
    k = _largest_k("gather_bf16", REDDIT_K)
    assert k == 254_976
    assert _launch("f32", k=k + BLOCK) == "streamed"
    assert "%flexvector_gather_rows" in _sparse_grid(
        shape, "f32", k=k, rows=8 * BLOCK)


def test_steps_name_their_kernels_and_scopes(shape):
    """The full-graph step (block-skipping grid, row-gathering on a toy
    graph and at pubmed) and a served bucket step (masked dense grid)
    carry the kernels' names and the steps' scopes into the compiled HLO,
    where the profiler's op names come from."""
    import jax
    import numpy as np

    from repro.graphs.datasets import (
        DatasetSpec,
        gcn_normalize,
        load_dataset,
        synthesize_adjacency,
    )
    from repro.exec import plan_for_config
    from repro.models.gcn import GCNConfig, init_params
    from repro.serve import ServeEngine

    spec = DatasetSpec("toy", nodes=400, edges=1_600, feature_dim=32,
                       classes=5)
    adj = gcn_normalize(synthesize_adjacency(spec, seed=7))
    feats = np.zeros((spec.nodes, spec.feature_dim), np.float32)
    cfg = GCNConfig(in_dim=32, hidden_dim=16, out_dim=5,
                    spmm_impl="pallas_sparse")
    engine = ServeEngine(adj, feats, cfg, interpret=False, fanout=4,
                         max_seeds=4, max_batch=2, base_bucket_nodes=128)
    on = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: shape(a.shape, a.dtype), tree)

    full = engine.registry.forward_step(adj, cfg, plan=engine.full_plan)
    text = full.lower(on(engine.params), shape(feats.shape, feats.dtype)
                      ).compile().as_text()
    assert full.residency == ("gather", "gather")
    assert "%flexvector_gather_rows" in text
    assert "gcn_full_step/" in text
    assert "gcn_full_step/combine/" in text
    assert "/aggregate/" in text and "/fold/" in text

    ds = load_dataset("pubmed", with_features=False)
    cfg = GCNConfig(in_dim=F_IN, hidden_dim=16, out_dim=3,
                    spmm_impl="pallas_sparse")
    full = engine.registry.forward_step(
        ds.adj_norm, cfg, plan=plan_for_config(cfg, interpret=False))
    params = on(jax.eval_shape(lambda: init_params(cfg,
                                                   jax.random.PRNGKey(0))))
    text = full.lower(params, shape((K_REAL, F_IN), np.float32)
                      ).compile().as_text()
    assert full.residency == ("gather", "gather")
    assert "%flexvector_gather_rows" in text
    assert "%flexvector_sparse_grid_rows" not in text

    batcher = engine.batcher
    bucket = batcher.ladder.entries[0]
    avals = batcher._avals(engine.params, bucket, 2, spec.feature_dim)
    text = _compile(batcher._make_forward(bucket, spec.feature_dim),
                    *on(avals))
    assert "%flexvector_dense_grid" in text
    assert "gcn_bucket_step/" in text
    assert "/aggregate/" in text and "/fold/" in text


def test_sharded_full_step_compiles(topo, monkeypatch):
    """The full-graph step sharded over a described 2x2 v5e (a 4-wide
    data mesh): each shard's SpMM runs the sparse grid under
    ``shard_map``, row-gathering its slab, or, past the VMEM budget (here
    none), running the visit loop on its own run offsets and visit
    list."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro.exec import plan_for_config
    from repro.graphs.datasets import (
        DatasetSpec,
        gcn_normalize,
        synthesize_adjacency,
    )
    from repro.models.gcn import GCNConfig, GCNGraph, gcn_forward, \
        init_params

    spec = DatasetSpec("toy", nodes=1_000, edges=4_000, feature_dim=32,
                       classes=5)
    adj = gcn_normalize(synthesize_adjacency(spec, seed=7))
    cfg = GCNConfig(in_dim=32, hidden_dim=16, out_dim=5,
                    spmm_impl="pallas_sparse")
    graph = GCNGraph.build(adj, cfg)
    mesh = Mesh(np.asarray(topo.devices), ("data",))
    plan = plan_for_config(cfg, mesh=mesh, interpret=False)
    on = lambda a: jax.ShapeDtypeStruct(  # noqa: E731
        a.shape, a.dtype, sharding=NamedSharding(mesh, PartitionSpec()))
    params = jax.tree.map(on, jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))))
    feats = on(jax.ShapeDtypeStruct((spec.nodes, 32), np.float32))
    from repro.kernels import flexvector_spmm as fv

    for budget, name in [(fv.RESIDENT_VMEM_BUDGET, "flexvector_gather_rows"),
                         (0, "flexvector_sparse_grid_rows")]:
        monkeypatch.setattr(fv, "RESIDENT_VMEM_BUDGET", budget)
        text = _compile(   # a new function each time: no trace is reused
            lambda p, x: gcn_forward(p, graph, x, cfg, plan=plan), params,
            feats)
        assert f"%{name}" in text
