"""Parity + planning tests for the fused combination+aggregation layer.

The fused path's contract is equality with the classic two-launch path
(combination matmul, intermediate activation, SpMM) at the same plan:
bitwise at bf16 and int8, and to f32 rounding at f32 (see
:func:`assert_fused_parity`).  This suite pins that contract
across all three impls (the reference oracle must *route* unfused — a
gather has no launch to fuse), all three storage precisions, and 1/2/4
devices (in-process virtual devices plus one subprocess cell that does
not depend on the parent's pinned device count).  It also pins the
planner obligations: a fused candidate may never make the chosen plan
cost more than the static unfused default, ``fused_viable`` gates on
VMEM, fused layers ledger an explicit 0-byte activation writeback, and
the autoplanned batcher stays zero-recompile with fused plans.
"""

import dataclasses
import os
import subprocess
import sys

import numpy as np
import pytest

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax
import jax.numpy as jnp

from repro.core import random_power_law_csr
from repro.dist.collectives import LEDGER
from repro.exec import pipeline_forward, plan_for_config, static_pipeline
from repro.models.gcn import GCNConfig, GCNGraph, gcn_forward, init_params
from repro.plan import cost as cost_mod
from repro.plan.autoplan import choose_plan

PRECISIONS = ("f32", "bf16", "int8")

#: HBM-starved compute-rich device: the fused launch's DRAM savings
#: dominate its extra per-k-tile combination FLOPs, so the planner fuses.
MEMBOUND = cost_mod.DeviceModel(name="membound", peak_flops=1e15,
                                hbm_bw=1e9)


def _cfg(impl="pallas", **kw):
    base = dict(in_dim=12, hidden_dim=64, out_dim=8, n_layers=2, tau=6,
                spmm_impl=impl, block_rows=16, block_k=16, block_f=16)
    base.update(kw)
    return GCNConfig(**base)


def _case(impl="pallas", n=96, nnz=700, seed=0):
    adj = random_power_law_csr(n, n, nnz, seed=seed)
    cfg = _cfg(impl)
    graph = GCNGraph.build(adj, cfg)
    params = init_params(cfg, jax.random.PRNGKey(0))
    feats = jnp.asarray(
        np.random.default_rng(1).standard_normal((n, cfg.in_dim)),
        jnp.float32)
    return graph, cfg, params, feats


def _forward(graph, cfg, params, feats, *, precision, fused):
    plan = dataclasses.replace(plan_for_config(cfg), precision=precision,
                               fused=fused)
    return np.asarray(gcn_forward(params, graph, feats, cfg, plan=plan))


#: f32 bound, as a fraction of the output's largest magnitude (~80 ulps).
F32_REL_TOL = 1e-5


def assert_fused_parity(fused, unfused, precision="f32"):
    """Fused equals unfused: bitwise at bf16/int8, to rounding at f32.

    The fused kernel computes ``X @ W`` per ``(block_k, F_in)`` tile in
    VMEM, the unfused path as one XLA dot over all of ``X``.  Compilers
    block an f32 contraction by its shapes (XLA:CPU does, and so does the
    TPU, whose MXU runs f32 as several bf16 passes), so the two sums round
    differently: at most 1.5e-5 on logits of magnitude 200 on the CPU.
    At bf16 and int8 both paths round the combination through bf16
    before the aggregation, and the outputs agree bit for bit.
    """
    if precision == "f32":
        scale = float(np.abs(unfused).max())
        np.testing.assert_allclose(fused, unfused, rtol=0,
                                   atol=F32_REL_TOL * scale)
    else:
        np.testing.assert_array_equal(fused, unfused)


def _data_mesh(n_dev):
    return jax.sharding.Mesh(np.array(jax.devices()[:n_dev]), ("data",))


# ---------------------------------------------------------------------------
# parity: impls x precisions, single device
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("impl", ["pallas", "pallas_sparse"])
@pytest.mark.parametrize("precision", PRECISIONS)
def test_fused_bitwise_parity(impl, precision):
    graph, cfg, params, feats = _case(impl)
    unfused = _forward(graph, cfg, params, feats, precision=precision,
                       fused=False)
    fused = _forward(graph, cfg, params, feats, precision=precision,
                     fused=True)
    assert_fused_parity(fused, unfused, precision)
    assert np.isfinite(fused).all()


@pytest.mark.parametrize("impl", ["pallas", "pallas_sparse"])
def test_fused_bitwise_parity_jit(impl):
    # serving runs the jitted trace; parity must survive compilation
    graph, cfg, params, feats = _case(impl)
    plan_u = plan_for_config(cfg)
    plan_f = dataclasses.replace(plan_u, fused=True)
    f_u = jax.jit(lambda p, x: gcn_forward(p, graph, x, cfg, plan=plan_u))
    f_f = jax.jit(lambda p, x: gcn_forward(p, graph, x, cfg, plan=plan_f))
    assert_fused_parity(np.asarray(f_f(params, feats)),
                        np.asarray(f_u(params, feats)))


def test_reference_impl_routes_unfused():
    """``fused=True`` on the reference oracle is a no-op routing-wise:
    identical output, and the ledger shows the classic two-launch
    records, never a ``fused_dram`` one."""
    graph, cfg, params, feats = _case("reference")
    unfused = _forward(graph, cfg, params, feats, precision="f32",
                       fused=False)
    LEDGER.reset()
    fused_flag = _forward(graph, cfg, params, feats, precision="f32",
                          fused=True)
    np.testing.assert_array_equal(fused_flag, unfused)
    assert LEDGER.count("fused_dram") == 0
    assert LEDGER.count("combination_dram") == cfg.n_layers
    assert LEDGER.count("spmm_dram") == cfg.n_layers


# ---------------------------------------------------------------------------
# ledger: explicit 0-byte writeback records, honest byte totals
# ---------------------------------------------------------------------------


def test_fused_ledger_zero_writeback_records():
    graph, cfg, params, feats = _case("pallas")
    LEDGER.reset()
    _forward(graph, cfg, params, feats, precision="f32", fused=False)
    unfused_dram = LEDGER.total_bytes("spmm_dram", "combination_dram")

    LEDGER.reset()
    _forward(graph, cfg, params, feats, precision="f32", fused=True)
    fused_dram = LEDGER.total_bytes("fused_dram")
    # every fused layer ledgers an *explicit* 0-byte activation
    # writeback record — not a silently missing one — so record counts
    # stay comparable across fused/unfused bench runs
    assert LEDGER.count("fused_dram") == cfg.n_layers
    assert LEDGER.count("activation_dram") == cfg.n_layers
    assert LEDGER.total_bytes("activation_dram") == 0.0
    assert LEDGER.total_bytes("fused_writeback_saved") > 0.0
    assert 0.0 < fused_dram < unfused_dram


# ---------------------------------------------------------------------------
# multi-device parity (virtual devices; subprocess covers tier-1)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_dev", [1, 2, 4])
def test_fused_parity_sharded_pipeline(n_dev):
    if jax.device_count() < n_dev:
        pytest.skip(f"needs {n_dev} devices (subprocess test covers tier-1)")
    graph, cfg, params, feats = _case("pallas")
    mesh = _data_mesh(n_dev) if n_dev > 1 else None
    outs = {}
    for fused in (False, True):
        pplan = static_pipeline(cfg, mesh, fused=fused)
        outs[fused] = np.asarray(
            pipeline_forward(params, graph, feats, pplan))
    assert_fused_parity(outs[True], outs[False])


@pytest.mark.parametrize("precision", ["bf16", "int8"])
def test_fused_parity_sharded_quantized(precision):
    if jax.device_count() < 2:
        pytest.skip("needs 2 devices (subprocess test covers tier-1)")
    graph, cfg, params, feats = _case("pallas")
    mesh = _data_mesh(2)
    outs = {}
    for fused in (False, True):
        pplan = static_pipeline(cfg, mesh, precision=precision, fused=fused)
        outs[fused] = np.asarray(
            pipeline_forward(params, graph, feats, pplan))
    np.testing.assert_array_equal(outs[True], outs[False])


_SUBPROCESS_FUSED = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses
import numpy as np, jax, jax.numpy as jnp
from repro.core import random_power_law_csr
from repro.dist.collectives import LEDGER
from repro.exec import pipeline_forward, static_pipeline
from repro.launch.mesh import make_data_mesh
from repro.models.gcn import GCNConfig, GCNGraph, gcn_forward, init_params

assert jax.device_count() == 4, jax.device_count()
n = 96
adj = random_power_law_csr(n, n, 700, seed=0)
cfg = GCNConfig(in_dim=12, hidden_dim=64, out_dim=8, n_layers=2, tau=6,
                spmm_impl="pallas", block_rows=16, block_k=16, block_f=16)
graph = GCNGraph.build(adj, cfg)
params = init_params(cfg, jax.random.PRNGKey(0))
feats = jnp.asarray(
    np.random.default_rng(1).standard_normal((n, 12)), jnp.float32)

for n_dev in (2, 4):
    mesh = make_data_mesh(n_dev)
    outs = {}
    for fused in (False, True):
        LEDGER.reset()
        outs[fused] = np.asarray(pipeline_forward(
            params, graph, feats, static_pipeline(cfg, mesh, fused=fused)))
    # f32: equal to rounding, as assert_fused_parity states
    scale = float(np.abs(outs[False]).max())
    np.testing.assert_allclose(outs[True], outs[False], rtol=0,
                               atol=1e-5 * scale)
    print(f"ok x{n_dev}")
"""


def test_fused_parity_multidevice_subprocess():
    """Real 2-/4-device fused-vs-unfused f32 parity, independent of the
    parent process's pinned device count."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = (
        os.path.join(os.path.dirname(__file__), "..", "src")
        + os.pathsep + env.get("PYTHONPATH", "")
    )
    r = subprocess.run([sys.executable, "-c", _SUBPROCESS_FUSED], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert r.stdout.count("ok ") == 2


# ---------------------------------------------------------------------------
# planner: never-worse regression + VMEM gate
# ---------------------------------------------------------------------------


def _layer_seconds(stats, plan, f_in, f_out, device):
    """Whole-layer seconds of ``plan`` — autoplan's own scoring rule."""
    impl = plan.effective_impl or plan.impl
    blocks = dict(block_rows=plan.block_rows, block_k=plan.block_k,
                  block_f=plan.block_f)
    if plan.fused:
        return cost_mod.fused_layer_cost(
            stats, f_in, f_out, impl=impl, n_shards=plan.n_shards,
            precision=plan.precision, device=device, **blocks).seconds
    spmm = cost_mod.spmm_cost(
        stats, f_out, impl=impl, n_shards=plan.n_shards,
        precision=plan.precision, device=device, **blocks).seconds
    comb = cost_mod.combination_seconds(
        stats.n_dense_rows, f_in, f_out, n_shards=plan.n_shards,
        precision=plan.precision, device=device)
    return spmm + comb


@pytest.mark.parametrize("device", [cost_mod.TPU_V5E, MEMBOUND],
                         ids=["compute-rich", "memory-bound"])
def test_autoplan_fusion_never_worse(device):
    graph, cfg, params, feats = _case("pallas")
    ell = graph.pre.ell
    stats = cost_mod.graph_stats_from_ell(ell)
    fdim = cfg.hidden_dim
    choice = choose_plan(ell, fdim, cfg, f_in=cfg.in_dim, device=device)
    static_plan = dataclasses.replace(choice.static_plan, fused=False)
    chosen_s = _layer_seconds(stats, choice.plan, cfg.in_dim, fdim, device)
    static_s = _layer_seconds(stats, static_plan, cfg.in_dim, fdim, device)
    assert chosen_s <= static_s * (1 + 1e-9), (
        f"fused search made the chosen plan worse than static unfused: "
        f"{chosen_s:.3e}s > {static_s:.3e}s ({choice.describe()})")


def test_autoplan_fuses_only_when_memory_bound():
    graph, cfg, params, feats = _case("pallas")
    ell = graph.pre.ell
    fdim = cfg.hidden_dim
    # the memory-bound device fuses (DRAM savings dominate the per-k-tile
    # combination recompute); without f_in the fusion dimension is off
    membound = choose_plan(ell, fdim, cfg, f_in=cfg.in_dim, device=MEMBOUND)
    assert membound.plan.fused
    no_fin = choose_plan(ell, fdim, cfg, device=MEMBOUND)
    assert not no_fin.plan.fused


def test_fused_viable_vmem_gate():
    graph, cfg, params, feats = _case("pallas")
    stats = cost_mod.graph_stats_from_ell(graph.pre.ell)
    assert cost_mod.fused_viable(stats, cfg.in_dim, block_rows=16,
                                 block_k=16, block_f=16)
    # a layer whose weight slab alone exceeds VMEM can never fuse
    assert not cost_mod.fused_viable(stats, 1 << 22, block_rows=16,
                                     block_k=16, block_f=16)
    # footprint is monotone in f_in at fixed blocks
    sizes = [cost_mod.fused_vmem_bytes(stats.padded_rows, stats.tau, f,
                                       block_rows=16, block_k=16, block_f=16)
             for f in (16, 64, 256)]
    assert sizes == sorted(sizes)


# ---------------------------------------------------------------------------
# serving: fused plans stay zero-recompile after warmup
# ---------------------------------------------------------------------------


def test_fused_batcher_zero_recompiles(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_CACHE", str(tmp_path / "cache"))
    from repro.graphs.datasets import (DatasetSpec, gcn_normalize,
                                       synthesize_adjacency)
    from repro.serve import ServeEngine

    spec = DatasetSpec("toy", nodes=128, edges=600, feature_dim=12, classes=4)
    adj = gcn_normalize(synthesize_adjacency(spec, seed=7))
    feats = np.random.default_rng(7).standard_normal(
        (spec.nodes, spec.feature_dim)).astype(np.float32)
    cfg = GCNConfig(in_dim=spec.feature_dim, hidden_dim=16,
                    out_dim=spec.classes, n_layers=2, tau=6,
                    spmm_impl="pallas", block_rows=16, block_k=16,
                    block_f=16)
    engine = ServeEngine(adj, feats, cfg, fanout=4, max_seeds=4, max_batch=4,
                         base_bucket_nodes=64, autoplan=True, fused=True)
    built = engine.warmup()
    assert built > 0
    # the forced-fused decision is baked into every rung's layer plans
    bucket = engine.batcher.ladder.entries[0]
    assert all(p.fused for p in engine.batcher.layer_plans_for_bucket(
        bucket, spec.feature_dim))

    rng = np.random.default_rng(8)
    requests = [
        rng.choice(spec.nodes, size=int(rng.integers(1, 5)), replace=False)
        for _ in range(24)
    ]
    for seeds in requests[:8]:
        engine.query(seeds)
    engine.query_batch(requests[8:])
    assert engine.compile_count == built, (
        f"{engine.compile_count - built} post-warmup compilations with "
        f"fused per-layer plans")
