"""Tests for the `repro.exec` execution-plan layer.

Covers the PR's acceptance criteria: `segment_accumulate` edge cases, the
recorded (not silent) pallas_sparse degradation, one dispatch path behind
both SpMM entry points, and sharded-vs-reference parity.  The sharded
parametrization adapts to the available device count: the root
``conftest.py`` gives the suite four virtual devices, so every cell runs
in-process, and a subprocess test provides 2-/4-device coverage that
does not depend on the parent's device count.
"""

import os
import subprocess
import sys
import warnings

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (
    preprocess,
    random_power_law_csr,
    segment_accumulate,
    spmm_ell,
)
from repro.core.spmm import spmm_dense_oracle, spmm_ell_arrays
from repro.exec import (
    SpmmOperands,
    SpmmPlan,
    execute,
    plan_for_config,
    shard_operands,
)
from repro.exec import plan as plan_mod


def _problem(n, nnz, tau, fdim, seed):
    adj = random_power_law_csr(n, n, nnz, seed=seed)
    res = preprocess(adj, tau=tau, tile_rows=16, edge_cut="rcm")
    rng = np.random.default_rng(seed + 1)
    dense = jnp.asarray(rng.standard_normal((n, fdim)), jnp.float32)
    return res, dense


def _data_mesh(n_dev):
    return jax.sharding.Mesh(np.array(jax.devices()[:n_dev]), ("data",))


# ---------------------------------------------------------------------------
# segment_accumulate edge cases
# ---------------------------------------------------------------------------


def test_segment_accumulate_empty_row_map():
    out = segment_accumulate(
        jnp.zeros((0, 4), jnp.float32), jnp.zeros((0,), jnp.int32), 3
    )
    np.testing.assert_array_equal(np.asarray(out), np.zeros((3, 4)))


def test_segment_accumulate_all_padding():
    sub = jnp.asarray(np.random.default_rng(0).standard_normal((5, 3)),
                      jnp.float32)
    row_map = jnp.full((5,), -1, jnp.int32)
    out = segment_accumulate(sub, row_map, 4)
    np.testing.assert_array_equal(np.asarray(out), np.zeros((4, 3)))


def test_segment_accumulate_duplicate_sub_rows():
    sub = jnp.asarray([[1.0, 2.0], [10.0, 20.0], [100.0, 200.0], [5.0, 5.0]])
    row_map = jnp.asarray([0, 0, 2, -1], jnp.int32)
    out = np.asarray(segment_accumulate(sub, row_map, 3))
    np.testing.assert_allclose(out, [[11.0, 22.0], [0.0, 0.0], [100.0, 200.0]])


# ---------------------------------------------------------------------------
# plan resolution: validation + recorded degradation
# ---------------------------------------------------------------------------


def test_plan_rejects_unknown_impl():
    with pytest.raises(ValueError, match="unknown impl"):
        SpmmPlan(impl="cusparse")


def test_pallas_sparse_degradation_recorded_and_warned_once():
    # the autouse fixture in conftest.py already reset the registry; the
    # explicit call documents the dependency and covers direct invocation
    plan_mod.reset_degradation_warnings()
    plan = SpmmPlan(impl="pallas_sparse", block_rows=16, block_k=16,
                    block_f=16)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        resolved = plan.resolve(schedulable=False)
        again = SpmmPlan(impl="pallas_sparse").resolve(schedulable=False)
    degr = [w for w in caught if issubclass(w.category, RuntimeWarning)]
    assert len(degr) == 1, "degradation must warn exactly once"
    assert resolved.effective_impl == "pallas" and resolved.degraded
    assert "pallas_sparse" in resolved.degraded_reason
    assert again.degraded  # still recorded even when the warning is muted
    # with the host container available there is no degradation
    ok = SpmmPlan(impl="pallas_sparse").resolve(schedulable=True)
    assert ok.effective_impl == "pallas_sparse" and not ok.degraded


def test_batcher_exposes_effective_impl(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_CACHE", str(tmp_path / "cache"))
    from repro.graphs.datasets import (DatasetSpec, gcn_normalize,
                                       synthesize_adjacency)
    from repro.models.gcn import GCNConfig
    from repro.serve import ServeEngine

    spec = DatasetSpec("toy", nodes=96, edges=400, feature_dim=8, classes=3)
    adj = gcn_normalize(synthesize_adjacency(spec, seed=3))
    feats = np.random.default_rng(3).standard_normal(
        (spec.nodes, spec.feature_dim)).astype(np.float32)
    cfg = GCNConfig(in_dim=spec.feature_dim, hidden_dim=8,
                    out_dim=spec.classes, spmm_impl="pallas_sparse",
                    block_rows=16, block_k=16, block_f=16)
    engine = ServeEngine(adj, feats, cfg, fanout=None, max_seeds=4,
                         base_bucket_nodes=32)
    assert engine.batcher.plan.effective_impl == "pallas"
    assert engine.batcher.plan.degraded


# ---------------------------------------------------------------------------
# one dispatch path behind both entry points
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("impl", ["reference", "pallas", "pallas_sparse"])
def test_entry_points_share_dispatch(impl):
    res, dense = _problem(80, 600, 5, 24, seed=2)
    oracle = spmm_dense_oracle(res.ell, np.asarray(dense))
    via_ell = spmm_ell(res.ell, dense, impl=impl,
                       block_rows=16, block_k=16, block_f=16)
    via_arrays = spmm_ell_arrays(
        jnp.asarray(res.ell.cols), jnp.asarray(res.ell.vals),
        jnp.asarray(res.ell.row_map), dense, n_out_rows=res.ell.n_orig_rows,
        impl=impl, block_rows=16, block_k=16, block_f=16,
    )
    np.testing.assert_allclose(np.asarray(via_ell, np.float64), oracle,
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(via_arrays, np.float64), oracle,
                               rtol=1e-4, atol=1e-4)


def test_plan_override_wins_over_kwargs():
    res, dense = _problem(48, 300, 4, 16, seed=4)
    plan = SpmmPlan(impl="pallas", block_rows=16, block_k=16, block_f=16)
    out = spmm_ell(res.ell, dense, impl="reference", plan=plan)
    np.testing.assert_allclose(
        np.asarray(out, np.float64), spmm_dense_oracle(res.ell, np.asarray(dense)),
        rtol=1e-4, atol=1e-4,
    )


# ---------------------------------------------------------------------------
# shard splitting
# ---------------------------------------------------------------------------


def test_shard_operands_partitions_rows():
    res, _ = _problem(64, 500, 4, 8, seed=5)
    ops = SpmmOperands.from_ell(res.ell)
    sh = shard_operands(ops, n_shards=4, block_rows=16)
    assert sh.cols.shape[0] == 4 * sh.rows_per_shard
    assert sh.rows_per_shard % 16 == 0
    # every original sub-row appears exactly once, in order per shard
    kept = sh.row_map[sh.row_map >= 0]
    np.testing.assert_array_equal(
        np.sort(kept), np.sort(res.ell.row_map[res.ell.row_map >= 0])
    )
    assert len(sh.shard_ells) == 4


def test_shard_operands_rejects_tracers():
    def traced(cols):
        ops = SpmmOperands.from_arrays(
            cols, jnp.zeros_like(cols, jnp.float32),
            jnp.zeros((cols.shape[0],), jnp.int32), 4)
        with pytest.raises(TypeError, match="concrete"):
            shard_operands(ops, 2, 16)
        return cols

    jax.jit(traced)(jnp.zeros((8, 3), jnp.int32))


# ---------------------------------------------------------------------------
# sharded-vs-reference parity (device-count adaptive)
# ---------------------------------------------------------------------------

IMPLS = ["reference", "pallas", "pallas_sparse"]


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("n_dev", [1, 2, 4])
def test_sharded_parity(impl, n_dev):
    if jax.device_count() < n_dev:
        pytest.skip(f"needs {n_dev} devices, have {jax.device_count()} "
                    f"(run under XLA_FLAGS=--xla_force_host_platform_"
                    f"device_count=8)")
    res, dense = _problem(96, 700, 5, 24, seed=0)
    ref = np.asarray(spmm_ell(res.ell, dense, impl="reference"))
    plan = SpmmPlan(impl=impl, block_rows=16, block_k=16, block_f=16,
                    mesh=_data_mesh(n_dev))
    out = execute(plan, SpmmOperands.from_ell(res.ell), dense)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-5)


def test_spmm_ell_mesh_kwarg_routes_same_path():
    res, dense = _problem(64, 400, 4, 16, seed=6)
    ref = np.asarray(spmm_ell(res.ell, dense, impl="reference"))
    out = spmm_ell(res.ell, dense, impl="pallas", block_rows=16, block_k=16,
                   block_f=16, mesh=_data_mesh(1))
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-5)


_SUBPROCESS_PARITY = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax, jax.numpy as jnp
from repro.core import preprocess, random_power_law_csr, spmm_ell
from repro.exec import SpmmOperands, SpmmPlan, execute

assert jax.device_count() == 4, jax.device_count()
adj = random_power_law_csr(96, 96, 700, seed=0)
res = preprocess(adj, tau=5, tile_rows=16, edge_cut="rcm")
dense = jnp.asarray(
    np.random.default_rng(1).standard_normal((96, 24)), jnp.float32)
ref = np.asarray(spmm_ell(res.ell, dense, impl="reference"))
for impl in ("reference", "pallas", "pallas_sparse"):
    for n_dev in (2, 4):
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:n_dev]), ("data",))
        plan = SpmmPlan(impl=impl, block_rows=16, block_k=16, block_f=16,
                        mesh=mesh)
        out = np.asarray(execute(plan, SpmmOperands.from_ell(res.ell), dense))
        err = np.abs(out - ref).max()
        assert err < 1e-5, (impl, n_dev, err)
        print(f"ok {impl} x{n_dev} err={err:.2e}")
"""


def test_sharded_parity_multidevice_subprocess():
    """Real 2-/4-device parity for all three impls, independent of the
    parent process's device count (jax pins it at first init)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = (
        os.path.join(os.path.dirname(__file__), "..", "src")
        + os.pathsep + env.get("PYTHONPATH", "")
    )
    r = subprocess.run([sys.executable, "-c", _SUBPROCESS_PARITY], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert r.stdout.count("ok ") == 6


# ---------------------------------------------------------------------------
# plan threading through the GCN forward
# ---------------------------------------------------------------------------


def test_gcn_forward_plan_matches_default():
    from repro.graphs.datasets import (DatasetSpec, gcn_normalize,
                                       synthesize_adjacency)
    from repro.models.gcn import GCNConfig, GCNGraph, gcn_forward, init_params

    spec = DatasetSpec("toy", nodes=80, edges=320, feature_dim=12, classes=4)
    adj = gcn_normalize(synthesize_adjacency(spec, seed=5))
    cfg = GCNConfig(in_dim=spec.feature_dim, hidden_dim=8,
                    out_dim=spec.classes, block_rows=16, block_k=16,
                    block_f=16)
    graph = GCNGraph.build(adj, cfg)
    params = init_params(cfg, jax.random.PRNGKey(0))
    feats = jnp.asarray(
        np.random.default_rng(5).standard_normal(
            (spec.nodes, spec.feature_dim)), jnp.float32)
    base = gcn_forward(params, graph, feats, cfg)
    planned = gcn_forward(params, graph, feats, cfg,
                          plan=plan_for_config(cfg, mesh=_data_mesh(1)))
    np.testing.assert_allclose(np.asarray(planned), np.asarray(base),
                               rtol=1e-6, atol=1e-6)


#: Bounds of a kernel forward against the reference impl, as fractions of
#: the output's largest magnitude.  f32: the compiler blocks each f32
#: contraction by its shapes, so sums may round differently (1e-5, about
#: 80 ulps; measured 1.7e-7 at most).  bf16 and int8: the products and
#: dequantization are the same, and only the order of the f32 adds
#: differs, which moves the largest logit by under two ulps (2.5e-7;
#: measured 7.4e-8 at most, over four graph seeds).
KERNEL_VS_REFERENCE = {"f32": 1e-5, "bf16": 2.5e-7, "int8": 2.5e-7}


@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("precision", ["f32", "bf16", "int8"])
@pytest.mark.parametrize("impl", ["pallas", "pallas_sparse"])
def test_kernel_forward_matches_reference_impl(impl, precision, jitted):
    """``gcn_forward`` through a kernel at each storage precision equals
    the ``reference`` impl at that precision, eager and compiled."""
    import dataclasses

    from repro.models.gcn import GCNConfig, GCNGraph, gcn_forward, init_params

    n = 96
    adj = random_power_law_csr(n, n, 700, seed=0)
    feats = jnp.asarray(
        np.random.default_rng(1).standard_normal((n, 12)), jnp.float32)
    outs = {}
    for which in ("reference", impl):
        cfg = GCNConfig(in_dim=12, hidden_dim=64, out_dim=8, n_layers=2,
                        tau=6, spmm_impl=which, block_rows=16, block_k=16,
                        block_f=16)
        graph = GCNGraph.build(adj, cfg)
        params = init_params(cfg, jax.random.PRNGKey(0))
        plan = dataclasses.replace(plan_for_config(cfg), precision=precision)

        def fwd(p, x, graph=graph, cfg=cfg, plan=plan):
            return gcn_forward(p, graph, x, cfg, plan=plan)

        outs[which] = np.asarray(
            (jax.jit(fwd) if jitted else fwd)(params, feats))
    ref, got = outs["reference"], outs[impl]
    assert np.isfinite(got).all()
    scale = float(np.abs(ref).max())
    np.testing.assert_allclose(
        got, ref, rtol=0, atol=KERNEL_VS_REFERENCE[precision] * scale)
