"""Tests for the multi-layer pipeline planner and sharded activations.

Covers the PR's acceptance criteria: the layout-cost terms
(reduce-scatter / all-gather / activation writeback), the exact layout DP
(never costed worse than the static per-layer default, deterministic),
hot-k-first and width selection in autoplan, bitwise parity of the
pipelined chain against the per-layer-psum path on 1/2/4 devices for all
three impls at all three storage precisions, the row-sharded ``gcn_forward`` output layout, the
collective ledger, and the zero-recompile invariant of the autoplanned
batcher.  Like ``test_exec``, multi-device cells adapt to the available
device count (four virtual devices, from the root ``conftest.py``) and a
subprocess test supplies 2-/4-device coverage of its own.
"""

import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import preprocess, random_power_law_csr
from repro.exec import (
    SpmmPlan,
    chain_layouts,
    pipeline_forward,
    plan_for_config,
    plan_pipeline,
    static_pipeline,
)
from repro.exec.pipeline import _layer_dims
from repro.models.gcn import GCNConfig, GCNGraph, gcn_forward, init_params
from repro.plan import cost as cost_mod

IMPLS = ["reference", "pallas", "pallas_sparse"]

#: Interconnect-rich compute-poor device: per-device work dominates, so
#: the planner shards and chains reduce-scatter epilogues even on toy
#: graphs (the forcing knob the ledger/byte assertions need).
SLOW = cost_mod.DeviceModel(name="slow", peak_flops=1e9, hbm_bw=1e9,
                            ici_bw=1e13, step_overhead_s=0.0)


def _cfg(**kw):
    base = dict(in_dim=12, hidden_dim=64, out_dim=8, n_layers=2, tau=6,
                spmm_impl="reference", block_rows=16, block_k=16, block_f=16)
    base.update(kw)
    return GCNConfig(**base)


def _graph(n=96, nnz=700, seed=0, tau=6):
    adj = random_power_law_csr(n, n, nnz, seed=seed)
    cfg = _cfg(tau=tau)
    return GCNGraph.build(adj, cfg), cfg


def _data_mesh(n_dev):
    return jax.sharding.Mesh(np.array(jax.devices()[:n_dev]), ("data",))


# ---------------------------------------------------------------------------
# cost-model layout terms
# ---------------------------------------------------------------------------


def test_reduce_scatter_bytes_matches_psum_ratio():
    # reduce-scatter moves (n-1)/n of the buffer once; psum moves it twice
    rs = cost_mod.reduce_scatter_bytes(128, 32, 4)
    ps = cost_mod.psum_bytes(128, 32, 4)
    assert rs == pytest.approx(128 * 32 * 4 * 3 / 4)
    assert ps == pytest.approx(2 * rs)
    assert cost_mod.reduce_scatter_bytes(128, 32, 1) == 0.0
    # non-divisible row counts round up to the shard grid
    assert cost_mod.reduce_scatter_bytes(130, 32, 4) == pytest.approx(
        132 * 32 * 4 * 3 / 4)


def test_all_gather_bytes_symmetric_with_reduce_scatter():
    assert cost_mod.all_gather_bytes(96, 24, 4) == pytest.approx(
        cost_mod.reduce_scatter_bytes(96, 24, 4))
    assert cost_mod.all_gather_bytes(96, 24, 1) == 0.0


def test_activation_writeback_replication_factor():
    # replicated: every device writes every row; row-sharded: the padded
    # buffer is written exactly once across the mesh
    rep = cost_mod.activation_writeback_bytes(100, 16, 4, "replicated")
    rs = cost_mod.activation_writeback_bytes(100, 16, 4, "row_sharded")
    assert rep == pytest.approx(4 * 100 * 16 * 4)
    assert rs == pytest.approx(100 * 16 * 4)  # 100 divides evenly by 4
    assert rs < rep
    one = cost_mod.activation_writeback_bytes(100, 16, 1, "replicated")
    assert one == pytest.approx(100 * 16 * 4)


def test_spmm_cost_layout_kwargs_shift_collectives_only():
    g, cfg = _graph()
    stats = cost_mod.graph_stats_from_ell(g.pre.ell)
    base = cost_mod.spmm_cost(stats, 32, n_shards=4)
    rs = cost_mod.spmm_cost(stats, 32, n_shards=4, out_layout="row_sharded")
    assert rs.collective_bytes < base.collective_bytes
    ag = cost_mod.spmm_cost(stats, 32, n_shards=4,
                            dense_layout="row_sharded")
    assert ag.collective_bytes > rs.collective_bytes
    # defaults preserve the historical arithmetic exactly
    again = cost_mod.spmm_cost(stats, 32, n_shards=4,
                               out_layout="replicated",
                               dense_layout="replicated",
                               shard_imbalance=1.0)
    assert again.seconds == base.seconds
    assert again.collective_bytes == base.collective_bytes


# ---------------------------------------------------------------------------
# pipeline planner: DP, determinism, never-worse guarantee
# ---------------------------------------------------------------------------


def test_layer_dims_funnel():
    cfg = _cfg(n_layers=3)
    assert _layer_dims(cfg) == ((12, 64), (64, 64), (64, 8))


def test_chain_layouts_single_final_all_reduce():
    chain = chain_layouts(3)
    assert chain == (
        ("replicated", "row_sharded"),
        ("row_sharded", "row_sharded"),
        ("row_sharded", "replicated"),
    )
    assert chain_layouts(1) == (("replicated", "replicated"),)


@pytest.mark.parametrize("device", [cost_mod.TPU_V5E, SLOW])
def test_plan_pipeline_never_worse_than_static(device):
    g, cfg = _graph()
    pp = plan_pipeline(cfg, g.pre.ell, n_devices=4, device=device)
    assert pp.cost_seconds <= pp.static_cost_seconds + 1e-12
    assert len(pp.layers) == cfg.n_layers
    # input and final output are pinned replicated
    assert pp.layers[0].in_layout == "replicated"
    assert pp.layers[-1].out_layout == "replicated"
    # interior boundaries are consistent: layer i's out is layer i+1's in
    for a, b in zip(pp.layers[:-1], pp.layers[1:]):
        assert a.out_layout == b.in_layout
        assert a.spmm.out_layout == a.out_layout
        assert b.spmm.dense_layout == b.in_layout


def test_plan_pipeline_deterministic():
    g, cfg = _graph()
    a = plan_pipeline(cfg, g.pre.ell, n_devices=4, device=SLOW)
    b = plan_pipeline(cfg, g.pre.ell, n_devices=4, device=SLOW)
    assert a.describe() == b.describe()
    assert a.cost_seconds == b.cost_seconds
    assert [(l.in_layout, l.out_layout) for l in a.layers] == \
           [(l.in_layout, l.out_layout) for l in b.layers]


def test_plan_pipeline_forced_sharded_chains_reduce_scatter():
    """On a device model where per-device compute dominates, the planner
    shards and the chain's only full all-reduce is the final epilogue."""
    if jax.device_count() < 2:
        pytest.skip("needs 2 devices (subprocess test covers tier-1)")
    g, cfg = _graph()
    pp = plan_pipeline(cfg, g.pre.ell, mesh=_data_mesh(2), device=SLOW)
    assert pp.n_shards == 2
    assert pp.layers[0].out_layout == "row_sharded"
    assert pp.n_collective_rounds == 1


def test_static_pipeline_layout_shapes():
    cfg = _cfg()
    flat = static_pipeline(cfg, mesh=None, pipelined=True)
    assert flat.n_shards == 1
    assert all(l.out_layout == "replicated" for l in flat.layers)
    assert flat.n_collective_rounds == 0


def test_plan_pipeline_out_layout_pins_final_boundary():
    if jax.device_count() < 2:
        pytest.skip("needs 2 devices (subprocess test covers tier-1)")
    g, cfg = _graph()
    pp = plan_pipeline(cfg, g.pre.ell, mesh=_data_mesh(2), device=SLOW,
                       out_layout="row_sharded")
    assert pp.layers[-1].out_layout == "row_sharded"
    assert pp.n_collective_rounds == 0


# ---------------------------------------------------------------------------
# autoplan: width pinning, imbalance pricing, hot-k-first
# ---------------------------------------------------------------------------


def test_choose_plan_widths_pin_placement():
    from repro.plan.autoplan import choose_plan

    g, cfg = _graph()
    pinned = choose_plan(g.pre.ell, 32, cfg, widths=(1,))
    assert pinned.plan.n_shards == 1 and pinned.plan.mesh is None


def test_choose_plan_imbalance_scales_width_score():
    """A graph whose best split is badly imbalanced must not be priced as
    a perfect n-way division of labor: the width's cost carries the
    achievable-split imbalance factor."""
    g, _ = _graph(n=128, nnz=1500, seed=3)
    stats = cost_mod.graph_stats_from_ell(g.pre.ell)
    bounds = cost_mod.balanced_split_points(stats.row_nnz, 4)
    imb = cost_mod.split_imbalance(stats.row_nnz, bounds)
    assert imb >= 1.0
    # SLOW's fast interconnect keeps per-device compute/memory dominant —
    # the terms the imbalance factor scales (collective bytes are fixed)
    even = cost_mod.spmm_cost(stats, 32, n_shards=4, shard_imbalance=1.0,
                              device=SLOW)
    skew = cost_mod.spmm_cost(stats, 32, n_shards=4, shard_imbalance=imb,
                              device=SLOW)
    if imb > 1.0:
        assert skew.seconds > even.seconds


def test_choose_hot_k_first_deterministic_and_threaded_into_plan():
    from repro.plan.autoplan import choose_hot_k_first, choose_plan

    g, cfg = _graph()
    pick = choose_hot_k_first(g.pre.ell, 32, block_rows=16, block_k=16,
                              block_f=16)
    assert pick == choose_hot_k_first(g.pre.ell, 32, block_rows=16,
                                      block_k=16, block_f=16)
    choice = choose_plan(g.pre.ell, 32,
                         _cfg(spmm_impl="pallas_sparse"),
                         impls=("pallas_sparse",))
    expected = choose_hot_k_first(
        g.pre.ell, 32, block_rows=choice.plan.block_rows,
        block_k=choice.plan.block_k, block_f=choice.plan.block_f)
    assert choice.plan.hot_k_first == expected


# ---------------------------------------------------------------------------
# collective ledger
# ---------------------------------------------------------------------------


def test_ledger_records_and_resets():
    from repro.dist.collectives import LEDGER

    LEDGER.reset()
    LEDGER.record("psum", 100.0)
    LEDGER.record("psum", 50.0)
    LEDGER.record("all_gather", 8.0)
    assert LEDGER.count("psum") == 2
    assert LEDGER.total_bytes("psum") == pytest.approx(150.0)
    snap = LEDGER.snapshot()
    assert snap["counts"]["psum"] == 2
    assert snap["bytes"]["all_gather"] == pytest.approx(8.0)
    LEDGER.reset()
    assert LEDGER.count("psum") == 0 and LEDGER.total_bytes() == 0.0


# ---------------------------------------------------------------------------
# bitwise parity: pipelined chain vs per-layer psum (device-adaptive)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("precision", ["f32", "bf16", "int8"])
@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("n_dev", [1, 2, 4])
def test_pipeline_parity_bitwise(impl, n_dev, precision):
    if jax.device_count() < n_dev:
        pytest.skip(f"needs {n_dev} devices, have {jax.device_count()} "
                    f"(run under XLA_FLAGS=--xla_force_host_platform_"
                    f"device_count=8)")
    g, cfg = _graph()
    cfg = _cfg(spmm_impl=impl)
    params = init_params(cfg, jax.random.PRNGKey(0))
    feats = jnp.asarray(
        np.random.default_rng(1).standard_normal((96, 12)), jnp.float32)
    mesh = _data_mesh(n_dev) if n_dev > 1 else None
    base = np.asarray(gcn_forward(
        params, g, feats, cfg,
        plan=static_pipeline(cfg, mesh, pipelined=False,
                             precision=precision)))
    pipe = np.asarray(gcn_forward(
        params, g, feats, cfg,
        plan=static_pipeline(cfg, mesh, pipelined=True,
                             precision=precision)))
    # the reduce-scatter epilogue performs the same per-row reduction as
    # the psum, so the chained stack is bitwise-identical, not just close,
    # at every storage precision
    assert np.isfinite(pipe).all()
    np.testing.assert_array_equal(pipe, base)


@pytest.mark.parametrize("n_dev", [1, 2, 4])
def test_gcn_forward_row_sharded_out_layout(n_dev):
    if jax.device_count() < n_dev:
        pytest.skip(f"needs {n_dev} devices, have {jax.device_count()}")
    n = 96
    g, cfg = _graph(n=n)
    params = init_params(cfg, jax.random.PRNGKey(0))
    feats = jnp.asarray(
        np.random.default_rng(1).standard_normal((n, 12)), jnp.float32)
    mesh = _data_mesh(n_dev) if n_dev > 1 else None
    plan = plan_for_config(cfg, mesh=mesh)
    rep = np.asarray(gcn_forward(params, g, feats, cfg, plan=plan))
    rs = np.asarray(gcn_forward(params, g, feats, cfg, plan=plan,
                                out_layout="row_sharded"))
    if n_dev == 1:
        # 1-wide: the layouts coincide, the replicated path is returned
        np.testing.assert_array_equal(rs, rep)
        return
    npad = -(-n // n_dev) * n_dev
    assert rs.shape[0] == npad
    # row-sharded output stays in permuted order, real rows first
    np.testing.assert_array_equal(rs[:n], rep[np.asarray(g.pre.perm)])
    np.testing.assert_array_equal(rs[n:], np.zeros_like(rs[n:]))


def test_gcn_forward_auto_routes_through_pipeline():
    g, cfg = _graph()
    params = init_params(cfg, jax.random.PRNGKey(0))
    feats = jnp.asarray(
        np.random.default_rng(1).standard_normal((96, 12)), jnp.float32)
    base = np.asarray(gcn_forward(params, g, feats, cfg))
    auto = np.asarray(gcn_forward(params, g, feats, cfg, plan="auto"))
    np.testing.assert_allclose(auto, base, rtol=1e-4, atol=1e-4)
    # an explicit pipeline plan object is accepted directly
    pp = plan_pipeline(cfg, g.pre.ell)
    again = np.asarray(gcn_forward(params, g, feats, cfg, plan=pp))
    np.testing.assert_allclose(again, base, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# 4-device subprocess: chained traffic strictly below per-layer psum
# ---------------------------------------------------------------------------

_SUBPROCESS_PIPELINE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax, jax.numpy as jnp
from repro.core import random_power_law_csr
from repro.dist.collectives import LEDGER
from repro.exec import (pipeline_forward, plan_for_config, plan_pipeline,
                        static_pipeline)
from repro.launch.mesh import make_data_mesh
from repro.models.gcn import GCNConfig, GCNGraph, gcn_forward, init_params
from repro.plan.cost import DeviceModel

assert jax.device_count() == 4, jax.device_count()
SLOW = DeviceModel(name="slow", peak_flops=1e9, hbm_bw=1e9, ici_bw=1e13,
                   step_overhead_s=0.0)
n = 96
adj = random_power_law_csr(n, n, 700, seed=0)
cfg = GCNConfig(in_dim=12, hidden_dim=64, out_dim=8, n_layers=2, tau=6,
                spmm_impl="reference", block_rows=16, block_k=16, block_f=16)
graph = GCNGraph.build(adj, cfg)
params = init_params(cfg, jax.random.PRNGKey(0))
feats = jnp.asarray(
    np.random.default_rng(1).standard_normal((n, 12)), jnp.float32)

def coll(s):
    return sum(s["bytes"].get(k, 0.0) for k in
               ("psum", "reduce_scatter", "all_gather"))

for n_dev in (2, 4):
    mesh = make_data_mesh(n_dev)
    # -- autoplanned: sharded reduce-scatter chain, never costed worse
    pp = plan_pipeline(cfg, graph.pre.ell, mesh=mesh, device=SLOW)
    assert pp.n_shards == n_dev, pp.describe()
    assert pp.n_collective_rounds == 1, pp.describe()
    assert pp.cost_seconds <= pp.static_cost_seconds + 1e-12
    auto_out = np.asarray(pipeline_forward(params, graph, feats, pp))
    ref = np.asarray(gcn_forward(params, graph, feats, cfg,
                                 plan=plan_for_config(cfg, mesh=mesh)))
    np.testing.assert_allclose(auto_out, ref, rtol=1e-4, atol=1e-4)
    # -- apples-to-apples (identical impl/blocks, layouts only): the
    # pipelined chain is bitwise-identical and moves strictly fewer bytes
    LEDGER.reset()
    pipe_out = np.asarray(pipeline_forward(
        params, graph, feats, static_pipeline(cfg, mesh, pipelined=True)))
    pipe = LEDGER.snapshot()
    assert LEDGER.count("psum") == 1, pipe          # final layer only
    assert LEDGER.count("reduce_scatter") == 1, pipe
    assert LEDGER.count("all_gather") == 1, pipe
    LEDGER.reset()
    base_out = np.asarray(pipeline_forward(
        params, graph, feats, static_pipeline(cfg, mesh, pipelined=False)))
    base = LEDGER.snapshot()
    assert LEDGER.count("psum") == cfg.n_layers, base
    np.testing.assert_array_equal(pipe_out, base_out)
    np.testing.assert_array_equal(base_out, ref)
    assert coll(pipe) < coll(base), (coll(pipe), coll(base))
    assert pipe["bytes"]["activation_dram"] < base["bytes"]["activation_dram"]
    print(f"ok x{n_dev} coll {coll(pipe):.0f}<{coll(base):.0f} "
          f"dram {pipe['bytes']['activation_dram']:.0f}"
          f"<{base['bytes']['activation_dram']:.0f}")
"""


def test_pipeline_traffic_multidevice_subprocess():
    """Real 2-/4-device run: one full all-reduce per stack, measured
    collective + activation-DRAM bytes strictly below per-layer psum, and
    bitwise parity — independent of the parent's pinned device count."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = (
        os.path.join(os.path.dirname(__file__), "..", "src")
        + os.pathsep + env.get("PYTHONPATH", "")
    )
    r = subprocess.run([sys.executable, "-c", _SUBPROCESS_PIPELINE], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert r.stdout.count("ok ") == 2


# ---------------------------------------------------------------------------
# serving: autoplanned pipelined batcher stays zero-recompile
# ---------------------------------------------------------------------------


def test_autoplanned_batcher_zero_recompiles(tmp_path, monkeypatch):
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
                    block_rows=16, block_k=16, block_f=16)
    engine = ServeEngine(adj, feats, cfg, fanout=4, max_seeds=4, max_batch=4,
                         base_bucket_nodes=64, autoplan=True)
    built = engine.warmup()
    assert built > 0

    rng = np.random.default_rng(8)
    requests = [
        rng.choice(spec.nodes, size=int(rng.integers(1, 5)), replace=False)
        for _ in range(32)
    ]
    for seeds in requests[:8]:
        engine.query(seeds)
    engine.query_batch(requests[8:])
    assert engine.compile_count == built, (
        f"{engine.compile_count - built} post-warmup compilations with "
        f"pipelined per-layer plans")
    # per-layer plans came from the pipeline planner, one per layer
    bucket = engine.batcher.ladder.entries[0]
    layer_plans = engine.batcher.layer_plans_for_bucket(
        bucket, spec.feature_dim)
    assert len(layer_plans) == cfg.n_layers
