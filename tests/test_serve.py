"""Tests for the repro.serve subsystem (registry, sampler, batcher, engine).

Uses a small synthetic community graph so the whole module stays fast; the
engine-level properties proved here are the acceptance criteria of the
serving PR: cache hits skip preprocessing, sampled queries are exact for
uncapped fanout, and a warmed engine never recompiles.
"""

import numpy as np
import jax
import pytest

from repro.core.sparse_formats import CSRMatrix, PAD_COL
from repro.graphs.datasets import DatasetSpec, gcn_normalize, synthesize_adjacency
from repro.graphs.sampling import induced_subgraph, sample_k_hop
from repro.models.gcn import GCNConfig, gcn_forward, init_params
from repro.serve import (
    ArtifactRegistry,
    BucketLadder,
    ServeEngine,
    SubgraphSampler,
    graph_key,
)


SPEC = DatasetSpec("toy", nodes=400, edges=1_600, feature_dim=32, classes=5)


@pytest.fixture(autouse=True)
def _isolated_cache(tmp_path, monkeypatch):
    """Keep registry persistence off the shared repo .cache: a stale
    artifact there could mask a preprocessing regression in these tests."""
    monkeypatch.setenv("REPRO_CACHE", str(tmp_path / "cache"))


@pytest.fixture(scope="module")
def toy_graph():
    adj = synthesize_adjacency(SPEC, seed=7)
    adj_norm = gcn_normalize(adj)
    rng = np.random.default_rng(7)
    feats = rng.standard_normal((SPEC.nodes, SPEC.feature_dim)).astype(np.float32)
    return adj_norm, feats


def _cfg(**kw):
    base = dict(in_dim=SPEC.feature_dim, hidden_dim=8, out_dim=SPEC.classes)
    base.update(kw)
    return GCNConfig(**base)


# ---------------------------------------------------------------------------
# (a) registry: second build of the same (graph, cfg) skips preprocessing
# ---------------------------------------------------------------------------


def test_registry_cache_hit_skips_preprocessing(toy_graph, tmp_path):
    adj_norm, _ = toy_graph
    cfg = _cfg()
    reg = ArtifactRegistry(cache_dir=str(tmp_path))
    g1 = reg.get_or_build(adj_norm, cfg)
    assert reg.stats.builds == 1 and reg.stats.mem_hits == 0
    g2 = reg.get_or_build(adj_norm, cfg)
    assert g2 is g1
    assert reg.stats.builds == 1 and reg.stats.mem_hits == 1

    # A fresh registry over the same cache dir loads from disk — no build.
    reg2 = ArtifactRegistry(cache_dir=str(tmp_path))
    g3 = reg2.get_or_build(adj_norm, cfg)
    assert reg2.stats.builds == 0 and reg2.stats.disk_hits == 1
    np.testing.assert_array_equal(g3.pre.ell.cols, g1.pre.ell.cols)
    np.testing.assert_array_equal(g3.inv, g1.inv)


def test_registry_lru_eviction_and_disk_refetch(toy_graph, tmp_path):
    """mem_capacity bounds the LRU; an evicted persisted artifact comes
    back from disk (no rebuild), an evicted memory-only one rebuilds."""
    adj_norm, _ = toy_graph
    cfgs = [_cfg(tau=t) for t in (3, 4, 5)]   # three distinct content keys
    reg = ArtifactRegistry(cache_dir=str(tmp_path), mem_capacity=2)
    graphs = [reg.get_or_build(adj_norm, c) for c in cfgs]
    assert reg.stats.builds == 3
    # capacity 2: building cfg[2] evicted cfg[0] (the LRU entry)
    assert len(reg._graphs) == 2
    assert graph_key(adj_norm, cfgs[0]) not in reg._graphs
    g0 = reg.get_or_build(adj_norm, cfgs[0])  # re-fetch after eviction
    assert reg.stats.builds == 3 and reg.stats.disk_hits == 1
    assert g0 is not graphs[0]                # a fresh unpickle, same content
    np.testing.assert_array_equal(g0.pre.ell.cols, graphs[0].pre.ell.cols)
    # the re-fetch evicted cfg[1] in turn (now the least recently used)
    assert graph_key(adj_norm, cfgs[1]) not in reg._graphs

    # a memory-only artifact has no disk fallback: eviction forces a build
    reg2 = ArtifactRegistry(cache_dir=str(tmp_path / "m"), mem_capacity=1)
    reg2.get_or_build(adj_norm, cfgs[0], persist=False)
    reg2.get_or_build(adj_norm, cfgs[1], persist=False)  # evicts cfgs[0]
    builds = reg2.stats.builds
    reg2.get_or_build(adj_norm, cfgs[0], persist=False)
    assert reg2.stats.builds == builds + 1 and reg2.stats.disk_hits == 0


def test_registry_eviction_drops_forward_steps(toy_graph, tmp_path):
    """Evicting a graph also drops its jitted forward steps, and a later
    forward_step call transparently re-fetches the operand from disk."""
    adj_norm, _ = toy_graph
    cfg_a, cfg_b = _cfg(tau=3), _cfg(tau=4)
    reg = ArtifactRegistry(cache_dir=str(tmp_path), mem_capacity=1)
    fwd_a = reg.forward_step(adj_norm, cfg_a)
    assert len(reg._forwards) == 1
    reg.forward_step(adj_norm, cfg_b)         # evicts graph A + its forward
    assert graph_key(adj_norm, cfg_a) not in reg._graphs
    assert all(k[0] != graph_key(adj_norm, cfg_a) for k in reg._forwards)
    fwd_a2 = reg.forward_step(adj_norm, cfg_a)
    assert fwd_a2 is not fwd_a                # rebuilt against the re-fetch
    assert reg.stats.disk_hits == 1 and reg.stats.builds == 2


def test_lru_dict_weighted_eviction_and_callbacks():
    """The LruDict contract the registry and fleet manager both rely on:
    weight-bounded capacity, recency on get/put, eviction callbacks for
    capacity evictions only, and never evicting the just-inserted entry."""
    from repro.serve.cache import LruDict

    evicted = []
    d = LruDict(3.0, on_evict=lambda k, v: evicted.append(k))
    d.put("a", 1)
    d.put("b", 2)
    d.put("c", 3)
    assert len(d) == 3 and d.total_weight == 3.0
    d.get("a")                       # a becomes MRU
    d.put("d", 4)                    # evicts b (LRU), not a
    assert "b" not in d and "a" in d and evicted == ["b"]
    # weighted: one 2-unit entry displaces two 1-unit ones
    d.put("big", 5, weight=2.0)
    assert evicted == ["b", "c", "a"] and "d" in d and "big" in d
    # a single over-budget entry still loads (never evict the new entry)
    d.put("huge", 6, weight=99.0)
    assert "huge" in d and len(d) == 1
    assert d.evictions == 5
    # explicit pop does NOT fire the eviction callback
    before = list(evicted)
    assert d.pop("huge") == 6 and evicted == before
    assert d.pop("ghost", "dflt") == "dflt"
    with pytest.raises(ValueError):
        LruDict(0)


def test_registry_multi_graph_churn_with_inflight_forward(toy_graph):
    """Multi-graph churn (satellite): LRU eviction + disk re-fetch while
    another graph's jitted forward_step is still in flight, with exact
    stats accounting across >= 3 graphs."""
    adj_norm, feats = toy_graph
    cfgs = [_cfg(tau=t) for t in (3, 4, 5)]
    reg = ArtifactRegistry(mem_capacity=2)

    # Hold a live forward step for graph 0 — the "in flight" servable.
    fwd0 = reg.forward_step(adj_norm, cfgs[0])
    params = init_params(cfgs[0], jax.random.PRNGKey(0))
    want0 = np.asarray(fwd0(params, feats))
    assert reg.stats.builds == 1

    # Churn graphs 1 and 2 through the capacity-2 LRU: graph 0 evicts.
    reg.forward_step(adj_norm, cfgs[1])
    reg.forward_step(adj_norm, cfgs[2])
    assert reg.stats.builds == 3
    assert graph_key(adj_norm, cfgs[0]) not in reg._graphs
    assert len(reg._graphs) == 2

    # The evicted graph's held step still serves — it closed over its
    # operand, so eviction frees the registry slot without breaking the
    # in-flight servable.
    np.testing.assert_array_equal(np.asarray(fwd0(params, feats)), want0)

    # Re-fetch after eviction: disk hit, not a rebuild; results identical.
    fwd0_again = reg.forward_step(adj_norm, cfgs[0])
    assert reg.stats.disk_hits == 1 and reg.stats.builds == 3
    np.testing.assert_allclose(np.asarray(fwd0_again(params, feats)),
                               want0, rtol=1e-5, atol=1e-5)

    # Exact stats across the whole churn: every graph re-requested from
    # memory afterwards is a mem hit, and the counters reconcile.
    reg.get_or_build(adj_norm, cfgs[0])
    reg.get_or_build(adj_norm, cfgs[2])
    assert reg.stats.mem_hits == 2
    assert (reg.stats.builds, reg.stats.disk_hits, reg.stats.mem_hits) \
        == (3, 1, 2)
    assert reg._graphs.evictions == 2       # graph0 then graph1


def test_registry_key_sensitivity(toy_graph):
    adj_norm, _ = toy_graph
    assert graph_key(adj_norm, _cfg()) != graph_key(adj_norm, _cfg(tau=4))
    # dims/impl don't change the preprocessed operand -> same key
    assert graph_key(adj_norm, _cfg()) == graph_key(
        adj_norm, _cfg(hidden_dim=64, spmm_impl="pallas")
    )


# ---------------------------------------------------------------------------
# sampler primitives
# ---------------------------------------------------------------------------


def test_sample_k_hop_exact_closure(toy_graph):
    adj_norm, _ = toy_graph
    seeds = [3, 17]
    nodes = sample_k_hop(adj_norm, seeds, hops=2, fanout=None)
    # scipy oracle: A_hat^2 reachability from the seeds
    m = adj_norm.to_scipy()
    x = np.zeros(SPEC.nodes)
    x[seeds] = 1.0
    want = np.flatnonzero((x + m @ x + m @ (m @ x)) > 0)
    np.testing.assert_array_equal(nodes, want)


def test_sample_k_hop_fanout_bounds_field(toy_graph):
    adj_norm, _ = toy_graph
    seeds = [0, 5, 9]
    capped = sample_k_hop(adj_norm, seeds, hops=2, fanout=3,
                          rng=np.random.default_rng(0))
    full = sample_k_hop(adj_norm, seeds, hops=2, fanout=None)
    assert set(capped) <= set(full)
    assert len(capped) <= len(seeds) * (1 + 3 + 9)


def test_induced_subgraph_values(toy_graph):
    adj_norm, _ = toy_graph
    nodes = np.array([1, 4, 40, 200])
    sub = induced_subgraph(adj_norm, nodes)
    want = adj_norm.to_scipy()[nodes][:, nodes].toarray()
    np.testing.assert_allclose(sub.to_scipy().toarray(), want)


def test_empty_query_rejected(toy_graph):
    adj_norm, _ = toy_graph
    sampler = SubgraphSampler(adj_norm, _cfg())
    with pytest.raises(ValueError, match="at least one seed"):
        sampler.extract([])


def test_sampler_meets_tau_bound(toy_graph):
    adj_norm, _ = toy_graph
    cfg = _cfg(tau=4)
    sampler = SubgraphSampler(adj_norm, cfg, fanout=None)
    sub = sampler.extract([11, 42, 99])
    ell = sub.graph.pre.ell
    assert ell.tau == 4
    assert int((ell.cols != PAD_COL).sum(axis=1).max()) <= 4


# ---------------------------------------------------------------------------
# (b) sampled-subgraph query == full-graph forward rows (fanout >= max deg)
# ---------------------------------------------------------------------------


def test_query_matches_full_forward(toy_graph):
    adj_norm, feats = toy_graph
    cfg = _cfg()
    engine = ServeEngine(adj_norm, feats, cfg, fanout=None, max_seeds=8,
                         base_bucket_nodes=64)
    full = engine.full_forward()
    oracle = np.asarray(
        gcn_forward(engine.params, engine.graph, feats, cfg), np.float64
    )
    np.testing.assert_allclose(full, oracle, rtol=1e-5, atol=1e-5)

    rng = np.random.default_rng(1)
    for _ in range(5):
        seeds = rng.choice(SPEC.nodes, size=int(rng.integers(1, 6)),
                           replace=False)
        out = engine.query(seeds)
        assert out.shape == (len(seeds), SPEC.classes)
        np.testing.assert_allclose(out, full[seeds], rtol=1e-4, atol=1e-4)


def test_query_batch_matches_single_queries(toy_graph):
    adj_norm, feats = toy_graph
    cfg = _cfg()
    engine = ServeEngine(adj_norm, feats, cfg, fanout=None, max_seeds=8,
                         max_batch=4, base_bucket_nodes=64)
    full = engine.full_forward()
    rng = np.random.default_rng(2)
    requests = [rng.choice(SPEC.nodes, size=3, replace=False) for _ in range(7)]
    outs = engine.query_batch(requests)
    assert len(outs) == len(requests)
    for seeds, out in zip(requests, outs):
        np.testing.assert_allclose(out, full[seeds], rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# (c) zero recompiles after warmup
# ---------------------------------------------------------------------------


def test_zero_recompiles_after_warmup(toy_graph):
    adj_norm, feats = toy_graph
    cfg = _cfg()
    engine = ServeEngine(adj_norm, feats, cfg, fanout=4, max_seeds=4,
                         max_batch=8, base_bucket_nodes=64)
    built = engine.warmup()
    assert built > 0 and engine.compile_count == built

    rng = np.random.default_rng(3)
    # 64-request mixed-size sweep: varying seed counts (1..4) and varying
    # receptive-field sizes, dispatched through both serving paths.
    requests = [
        rng.choice(SPEC.nodes, size=int(rng.integers(1, 5)), replace=False)
        for _ in range(64)
    ]
    for seeds in requests[:16]:
        engine.query(seeds)
    engine.query_batch(requests[16:])
    assert engine.compile_count == built, (
        f"{engine.compile_count - built} post-warmup compilations"
    )


def test_repeated_capped_query_is_deterministic_and_cached(toy_graph):
    """Fanout sampling is keyed on request contents: an identical repeated
    query draws the same subgraph, hits the registry instead of re-running
    the vertex-cut, and returns bit-identical logits."""
    adj_norm, feats = toy_graph
    cfg = _cfg()
    engine = ServeEngine(adj_norm, feats, cfg, fanout=3, max_seeds=4,
                         base_bucket_nodes=64)
    out1 = engine.query([5, 77])
    builds = engine.registry.stats.builds
    hits = engine.registry.stats.mem_hits
    out2 = engine.query([5, 77])
    assert engine.registry.stats.builds == builds
    assert engine.registry.stats.mem_hits == hits + 1
    np.testing.assert_array_equal(out1, out2)


def test_bucket_ladder_covers_full_graph(toy_graph):
    adj_norm, feats = toy_graph
    cfg = _cfg()
    reg = ArtifactRegistry()
    graph = reg.get_or_build(adj_norm, cfg, persist=False)
    ladder = BucketLadder.for_graph(graph, cfg, base_nodes=64)
    top = ladder.entries[-1]
    assert top.nodes >= graph.n_nodes
    assert top.rows >= graph.pre.ell.padded_rows
    # every rung fits some request; escalation never falls off the ladder
    b = ladder.bucket_for(graph.n_nodes, graph.pre.ell.padded_rows)
    assert b == top
    with pytest.raises(ValueError):
        ladder.bucket_for(top.nodes + 1, 1)


def test_bucket_ladder_fractional_growth(toy_graph):
    adj_norm, feats = toy_graph
    cfg = _cfg()
    reg = ArtifactRegistry()
    graph = reg.get_or_build(adj_norm, cfg, persist=False)
    coarse = BucketLadder.for_graph(graph, cfg, base_nodes=64, growth=4)
    fine = BucketLadder.for_graph(graph, cfg, base_nodes=64, growth=1.3)
    for ladder in (coarse, fine):
        nodes = [b.nodes for b in ladder.entries]
        assert nodes == sorted(set(nodes))               # strictly increasing
        assert all(n % cfg.block_k == 0 for n in nodes)  # quantized
        assert ladder.entries[-1].nodes >= graph.n_nodes  # covers the graph
    assert len(fine.entries) > len(coarse.entries)
    with pytest.raises(ValueError, match="growth"):
        BucketLadder.for_graph(graph, cfg, base_nodes=64, growth=1.0)


def test_auto_ladder_growth_is_deterministic_cost_choice(toy_graph):
    from repro.plan import cost
    from repro.plan.autoplan import GROWTH_CANDIDATES, choose_ladder_growth

    adj_norm, _ = toy_graph
    cfg = _cfg()
    reg = ArtifactRegistry()
    graph = reg.get_or_build(adj_norm, cfg, persist=False)
    auto1 = BucketLadder.for_graph(graph, cfg, base_nodes=64, growth="auto")
    auto2 = BucketLadder.for_graph(graph, cfg, base_nodes=64, growth="auto")
    assert auto1.entries == auto2.entries                # deterministic

    stats = cost.graph_stats_from_ell(graph.pre.ell)
    g = choose_ladder_growth(stats, cfg, base_nodes=64, top_nodes=512)
    assert g in GROWTH_CANDIDATES
    # a tiny request horizon makes warmup compiles dominate: the pick can
    # only move coarser (fewer rungs), never finer
    g_short = choose_ladder_growth(stats, cfg, base_nodes=64, top_nodes=512,
                                   horizon=1)
    g_long = choose_ladder_growth(stats, cfg, base_nodes=64, top_nodes=512,
                                  horizon=10**9)
    assert g_short >= g >= g_long


# ---------------------------------------------------------------------------
# bench harness smoke (acceptance: CSV with p50/p99 + tok-equiv throughput)
# ---------------------------------------------------------------------------


def test_bench_serve_smoke(monkeypatch, capsys):
    from benchmarks import bench_serve

    monkeypatch.setenv("REPRO_DATASETS", "cora")
    bench_serve.run(requests=6, max_batch=2, seeds_per_request=2, hidden=8,
                    fanout=8)
    out = capsys.readouterr().out
    assert "p50_ms,p99_ms" in out and "tok_equiv_per_s" in out
    lines = [l for l in out.strip().splitlines() if l.startswith("cora,")]
    assert {l.split(",")[1] for l in lines} == {"full", "query", "batch"}


_COMPILE_CACHE_CHILD = r"""
import jax, jax.numpy as jnp
from repro.serve.cache import enable_compile_cache
path = enable_compile_cache()
jax.block_until_ready(jax.jit(lambda x: jnp.tanh(x) * 3.0 + 1.0)(
    jnp.ones((8, 128))))
print(path)
print(jax.config.jax_compilation_cache_dir)
"""


@pytest.mark.parametrize("env_set", [True, False], ids=["env-set", "unset"])
def test_compile_cache_lands_in_env_dir_or_checkout(env_set, tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` is used as set; unset, the cache is
    the fixed ``<repo>/.cache/jax``, whatever ``REPRO_CACHE`` says."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               REPRO_CACHE=str(tmp_path / "artifacts"),
               PYTHONPATH=os.path.join(root, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = os.path.join(root, ".cache", "jax")
    if env_set:
        want = str(tmp_path / "jax-cache")
        env["JAX_COMPILATION_CACHE_DIR"] = want
    r = subprocess.run([sys.executable, "-c", _COMPILE_CACHE_CHILD],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    reported, configured = r.stdout.split()[-2:]
    assert reported == configured == want
    assert any(name.endswith("-cache") for name in os.listdir(want))
    assert not os.path.exists(tmp_path / "artifacts" / "jax")


# ---------------------------------------------------------------------------
# graph operands as arguments of the full-graph step; artifact keys
# ---------------------------------------------------------------------------


def test_a_changed_source_misses_the_persisted_artifact(toy_graph, tmp_path,
                                                        monkeypatch):
    """Persisted graphs are keyed by the source of the code that wrote
    them: a changed module gives another digest, and under another
    digest the pickle on disk is not read."""
    from repro.serve import registry as reg_mod

    mod = tmp_path / "artifact_writer.py"
    mod.write_text("SPLIT = 1\n")
    monkeypatch.syspath_prepend(str(tmp_path))
    before = reg_mod.source_digest(("artifact_writer",))
    mod.write_text("SPLIT = 2\n")
    reg_mod.source_digest.cache_clear()
    assert reg_mod.source_digest(("artifact_writer",)) != before

    adj_norm, _ = toy_graph
    cache = str(tmp_path / "artifacts")
    ArtifactRegistry(cache_dir=cache).get_or_build(adj_norm, _cfg())
    warm = ArtifactRegistry(cache_dir=cache)
    warm.get_or_build(adj_norm, _cfg())
    assert (warm.stats.disk_hits, warm.stats.builds) == (1, 0)
    monkeypatch.setattr(reg_mod, "source_digest", lambda: before)
    changed = ArtifactRegistry(cache_dir=cache)
    changed.get_or_build(adj_norm, _cfg())
    assert (changed.stats.disk_hits, changed.stats.builds) == (0, 1)


def _constant_bytes(hlo_text):
    """Bytes of each constant in a lowered module's text."""
    import re

    width = {"i1": 1, "i8": 1, "i16": 2, "bf16": 2, "f16": 2, "i32": 4,
             "f32": 4, "i64": 8, "f64": 8, "ui32": 4, "ui8": 1}
    out = []
    for dims in re.findall(r"stablehlo\.constant [^\n]*: tensor<([^>]*)>",
                           hlo_text):
        *shape, dtype = dims.split("x")
        out.append(int(np.prod([int(d) for d in shape])) * width[dtype])
    return out


def test_full_step_carries_no_graph_constant(tmp_path):
    """At pubmed the full-graph step takes the ELL, row map, permutations
    and visit list as arguments: its program's constants come to less
    than 1 MB together, where those operands as constants are 3 MB."""
    from repro.exec import plan_for_config
    from repro.graphs.datasets import load_dataset

    ds = load_dataset("pubmed", with_features=False)
    cfg = GCNConfig(in_dim=500, hidden_dim=16, out_dim=3,
                    spmm_impl="pallas_sparse")
    plan = plan_for_config(cfg, interpret=True).resolve(schedulable=True)
    step = ArtifactRegistry(cache_dir=str(tmp_path)).forward_step(
        ds.adj_norm, cfg, plan=plan)
    feats = jax.ShapeDtypeStruct((ds.spec.nodes, 500), np.float32)
    text = step.lower(init_params(cfg, jax.random.PRNGKey(0)),
                      feats).as_text()
    assert sum(_constant_bytes(text)) <= 2**20
    closed = jax.jit(lambda p, x: gcn_forward(
        p, step.arrays, x, cfg, plan=plan)).lower(
            init_params(cfg, jax.random.PRNGKey(0)), feats).as_text()
    assert sum(_constant_bytes(closed)) > 2**20   # the check sees them


@pytest.mark.parametrize("resident", [True, False])
def test_full_step_matches_a_plain_gcn(toy_graph, monkeypatch, resident):
    """The full-graph step through the sparse grid, its slab resident or
    streamed, gives a plain f32 jax.numpy GCN's logits on seeded
    weights and biases."""
    import jax.numpy as jnp

    from repro.exec import plan_for_config
    from repro.kernels import flexvector_spmm as fv

    adj_norm, feats = toy_graph
    cfg = _cfg(spmm_impl="pallas_sparse")
    params = init_params(cfg, jax.random.PRNGKey(3))
    rng = np.random.default_rng(3)
    for layer in params.values():
        layer["b"] = jnp.asarray(rng.normal(0, 0.1, layer["b"].shape),
                                 jnp.float32)
    if not resident:
        monkeypatch.setattr(fv, "RESIDENT_VMEM_BUDGET", 0)
    plan = plan_for_config(cfg, interpret=True).resolve(schedulable=True)
    got = np.asarray(ArtifactRegistry().forward_step(
        adj_norm, cfg, plan=plan)(params, feats))
    a = jnp.asarray(adj_norm.to_scipy().toarray())
    with jax.default_matmul_precision("highest"):
        h = jnp.asarray(feats)
        for i in range(cfg.n_layers):
            p = params[f"layer_{i}"]
            h = a @ (h @ p["w"] + p["b"])
            if i < cfg.n_layers - 1:
                h = jax.nn.relu(h)
    np.testing.assert_allclose(got, np.asarray(h), rtol=1e-4, atol=1e-4)


def test_full_step_records_resident_launches_at_pubmed(tmp_path,
                                                      monkeypatch):
    """pubmed's f32 slab fits VMEM: the full step's two layer launches
    row-gather it and read ``gather``.  Under no VMEM budget citeseer's
    slab streams through the visit loop instead.  The registry counts
    both kinds."""
    from repro.graphs.datasets import load_dataset
    from repro.kernels import flexvector_spmm as fv

    reg = ArtifactRegistry(cache_dir=str(tmp_path))
    for name, in_dim, classes, launch in [
            ("pubmed", 500, 3, "gather"), ("citeseer", 3703, 6, "streamed")]:
        if launch == "streamed":
            monkeypatch.setattr(fv, "RESIDENT_VMEM_BUDGET", 0)
        ds = load_dataset(name, with_features=False)
        cfg = GCNConfig(in_dim=in_dim, hidden_dim=16, out_dim=classes,
                        spmm_impl="pallas_sparse")
        step = reg.forward_step(ds.adj_norm, cfg)
        assert step.residency == (launch, launch)
    assert reg.stats.residency == {"gather": 2, "streamed": 2}


def test_full_forward_stamps_the_bf16_residency(toy_graph, monkeypatch):
    """Under a budget between the toy slab's packed-bf16 and f32
    footprints the full step's launches keep the slab in VMEM as bf16,
    packed two rows to a word for the row gather: the step and the
    registry say so, a full forward stamps it on its span, and its logits
    are those of the plain path within bf16 rounding."""
    import jax.numpy as jnp

    from repro.kernels import flexvector_spmm as fv
    from repro.obs import Tracer, use_span

    adj_norm, feats = toy_graph
    cfg = _cfg(spmm_impl="pallas_sparse", block_rows=32, block_k=32,
               block_f=32)
    monkeypatch.setattr(fv, "_FILL_TILES", 1)
    need = {r: fv.sparse_grid_vmem_bytes(
        r, -(-SPEC.nodes // 32) * 32, cfg.tau, dtype=jnp.float32,
        out_dtype=jnp.float32, block_rows=32, block_k=32, block_f=32)
        for r in ("gather", "gather_bf16")}
    assert need["gather_bf16"] < need["gather"]
    monkeypatch.setattr(fv, "RESIDENT_VMEM_BUDGET", need["gather_bf16"])
    engine = ServeEngine(adj_norm, feats, cfg, fanout=None, max_seeds=8,
                         base_bucket_nodes=64)
    assert engine._full_step.residency == ("gather_bf16",) * 2
    assert engine.registry.stats.residency == {"gather_bf16": 2}
    trace = Tracer().trace("request")
    with use_span(trace.root):
        got = engine.full_forward()
    (sp,) = trace.find("full_forward")
    assert sp.attributes["residency"] == ("gather_bf16",) * 2
    want = np.asarray(gcn_forward(engine.params, engine.graph, feats,
                                  _cfg()))
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
