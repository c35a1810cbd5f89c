"""Artifact registry: content-keyed preprocessed operands + forward steps.

The hybrid preprocessing pipeline (edge-cut + Algorithm 1 vertex-cut) is
the expensive, request-independent half of GCN serving.  The registry keys
``(adjacency contents, preprocessing-relevant GCNConfig fields)`` to the
preprocessed :class:`~repro.models.gcn.GCNGraph` so that cost is paid once
per graph, not once per request:

* an in-memory LRU holds hot artifacts (full graphs *and* sampled
  subgraphs — repeated queries over the same node set skip the vertex-cut
  entirely);
* full-graph artifacts are additionally persisted through the shared
  ``.cache`` pickle machinery (`repro.serve.cache`, the same path
  `benchmarks/common.py` uses) so they survive process restarts.

A persisted full graph carries the ``pallas_sparse`` schedule planned
for the config's blocks, and its key hashes the source of the modules
that write it, so a pickle left by other code is never read.

Jitted full-graph forward steps are cached per key in memory only
(executables are not picklable).  A static single-device step takes the
graph's operands as an argument (``GraphArrays``, copied to the device
once per graph), so none of them is a constant of its program.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import importlib.util
from typing import Callable, Dict, Optional, Tuple

import jax
import numpy as np

from repro.core.sparse_formats import CSRMatrix
from repro.models.gcn import GCNConfig, GCNGraph, GraphArrays, gcn_forward
from repro.obs.trace import span
from repro.serve import cache as disk_cache

# The modules whose code decides what a persisted artifact holds.
_ARTIFACT_MODULES = (
    "repro.core.preprocessing",
    "repro.core.sparse_formats",
    "repro.core.dataflow",
    "repro.models.gcn",
    "repro.exec.quant",
)


@functools.lru_cache(maxsize=None)
def source_digest(modules: Tuple[str, ...] = _ARTIFACT_MODULES) -> str:
    """SHA-256 over the source files of ``modules``."""
    h = hashlib.sha256()
    for name in modules:
        with open(importlib.util.find_spec(name).origin, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


@dataclasses.dataclass
class RegistryStats:
    """Counters proving where each artifact came from."""

    mem_hits: int = 0
    disk_hits: int = 0
    builds: int = 0          # preprocessing actually ran
    # The built full steps' sparse-grid launches, by the body each runs
    # (``FullStep.residency``: gather, gather_bf16 or streamed), and
    # their visits (``FullStep.visits``) summed by the same body.
    residency: Dict[str, int] = dataclasses.field(default_factory=dict)
    visits: Dict[str, int] = dataclasses.field(default_factory=dict)


def graph_key(adj: CSRMatrix, cfg: GCNConfig) -> str:
    """Content hash over the adjacency, the preprocessing-relevant config
    fields (dims/impl don't change the preprocessed operand) and the
    source of the code that preprocesses it."""
    h = hashlib.sha256()
    h.update(source_digest().encode())
    h.update(np.ascontiguousarray(adj.indptr).tobytes())
    h.update(np.ascontiguousarray(adj.indices).tobytes())
    h.update(np.ascontiguousarray(adj.data).tobytes())
    meta = (adj.shape, cfg.tau, cfg.tile_rows, cfg.edge_cut, cfg.block_rows,
            cfg.block_k)
    h.update(repr(meta).encode())
    return f"gcngraph_{h.hexdigest()[:24]}"


class ArtifactRegistry:
    """LRU + disk registry of preprocessed graphs and jitted forward steps."""

    def __init__(self, cache_dir: Optional[str] = None, mem_capacity: int = 512):
        self.cache_dir = cache_dir or disk_cache.default_cache_dir()
        self.mem_capacity = mem_capacity
        self.stats = RegistryStats()
        # A jitted step holds its operand; when the LRU drops the graph,
        # keeping the step or the operand's device copy would pin the
        # memory the eviction was supposed to release, so eviction
        # cascades into _forwards and _uploads.
        self._graphs = disk_cache.LruDict(
            mem_capacity, on_evict=self._drop_forwards)
        self._forwards: Dict[Tuple[str, GCNConfig], Callable] = {}
        self._uploads: Dict[Tuple[str, int, int, bool], GraphArrays] = {}

    def get_or_build(
        self,
        adj: CSRMatrix,
        cfg: GCNConfig,
        persist: bool = True,
        key: Optional[str] = None,
    ) -> GCNGraph:
        """Return the preprocessed graph for ``(adj, cfg)``, building it at
        most once per content key (``persist`` keeps full graphs on disk,
        with their sparse-grid schedule for the config's blocks; sampled
        subgraphs stay memory-only).  ``key`` lets callers that already
        hashed the adjacency skip a second content pass."""
        if key is None:
            key = graph_key(adj, cfg)
        graph = self._graphs.get(key)
        if graph is not None:
            self.stats.mem_hits += 1
            return graph
        if persist:
            graph, hit = disk_cache.load_pickle(key, self.cache_dir)
            if hit:
                self.stats.disk_hits += 1
                self._remember(key, graph)
                return graph
        with span("registry.preprocess"):
            graph = GCNGraph.build(adj, cfg)
        self.stats.builds += 1
        if persist:
            with span("registry.plan_grid"):
                graph.kernel_grid(cfg.block_rows, cfg.block_k)
            disk_cache.store_pickle(key, graph, self.cache_dir)
        self._remember(key, graph)
        return graph

    def forward_step(
        self, adj: CSRMatrix, cfg: GCNConfig, persist: bool = True,
        plan=None, precision: str = "f32", interpret: Optional[bool] = None,
    ) -> "FullStep":
        """Jitted full-graph forward ``step(params, features) -> logits``
        bound to the registered preprocessed operand.

        Keyed on ``(graph_key, cfg, precision)``: graph_key deliberately
        ignores forward-only fields (dims, spmm impl/blocks) so the
        *operand* is shared, but the jitted step must not be.  ``plan`` is
        forwarded to :func:`gcn_forward` — ``"auto"`` plans the whole
        stack through ``repro.exec.pipeline`` once at build time
        (host-side, so the traced step carries the already-chosen
        per-layer plans); a plan object keys the cache by identity.
        ``interpret`` pins Pallas interpret mode for an ``"auto"`` plan
        (``None``: from the backend); a plan object carries its own.
        A static single-device plan's step takes the graph's device
        arrays as an argument; the others plan on the host graph.
        """
        gkey = graph_key(adj, cfg)
        key = (gkey, cfg, precision, interpret,
               plan if (plan is None or isinstance(plan, str)) else id(plan))
        fwd = self._forwards.get(key)
        if fwd is not None:
            return fwd
        graph = self.get_or_build(adj, cfg, persist=persist, key=gkey)
        step_plan = plan
        if plan == "auto":
            # Plan once here, not per trace: the pipeline planner is pure
            # host-side arithmetic over the preprocessed operand.
            from repro.exec.pipeline import plan_pipeline

            step_plan = plan_pipeline(cfg, graph.pre.ell,
                                      precision=precision,
                                      interpret=interpret)
        arrays, residency, visits = None, (), ()
        if _takes_arrays(step_plan):
            from repro.exec import plan_for_config

            static = step_plan or plan_for_config(cfg)
            arrays = self._upload(gkey, graph, static)
            residency = _launch_residency(static, arrays, cfg.n_layers,
                                          precision)
            visits = (int(arrays.grid.kb_ids.shape[0]),) * len(residency)
            for r, v in zip(residency, visits):
                self.stats.residency[r] = self.stats.residency.get(r, 0) + 1
                self.stats.visits[r] = self.stats.visits.get(r, 0) + v

        def gcn_full_step(params, feats, arrays):
            with jax.named_scope("gcn_full_step"):
                return gcn_forward(params, graph if arrays is None else arrays,
                                   feats, cfg, plan=step_plan,
                                   precision=precision)

        fwd = FullStep(jax.jit(gcn_full_step), arrays, residency, visits)
        self._forwards[key] = fwd
        return fwd

    def _upload(self, gkey: str, graph: GCNGraph, plan) -> GraphArrays:
        """The graph's operands for ``plan`` on the device, copied once."""
        ukey = (gkey, plan.block_rows, plan.block_k, plan.hot_k_first)
        arrays = self._uploads.get(ukey)
        if arrays is None:
            with span("registry.upload"):
                arrays = jax.block_until_ready(
                    jax.device_put(graph.arrays(plan)))
            self._uploads[ukey] = arrays
        return arrays

    def quantized_ell(
        self, adj: CSRMatrix, cfg: GCNConfig, precision: str,
        persist: bool = True,
    ):
        """The graph's :class:`~repro.exec.quant.QuantizedELL` artifact,
        content-keyed by graph + precision + scale granularity.

        Quantization is cheap next to preprocessing but the artifact is
        what a serving replica actually ships to devices, so it rides the
        same memory LRU + disk pickle machinery as the graphs (the stats
        counters cover it too).  ``precision`` must be non-f32 — the f32
        artifact *is* the preprocessed TiledELL.
        """
        from repro.exec import quant

        gkey = graph_key(adj, cfg)
        qkey = f"{gkey}_q_{precision}_{cfg.block_rows}"
        art = self._graphs.get(qkey)
        if art is not None:
            self.stats.mem_hits += 1
            return art
        if persist:
            art, hit = disk_cache.load_pickle(qkey, self.cache_dir)
            if hit:
                self.stats.disk_hits += 1
                self._graphs.put(qkey, art)
                return art
        graph = self.get_or_build(adj, cfg, persist=persist, key=gkey)
        art = quant.quantize_ell(graph.pre.ell, precision, cfg.block_rows)
        self.stats.builds += 1
        if persist:
            disk_cache.store_pickle(qkey, art, self.cache_dir)
        self._graphs.put(qkey, art)
        return art

    def _remember(self, key: str, graph: GCNGraph) -> None:
        self._graphs.put(key, graph)

    def _drop_forwards(self, key: str, _graph: GCNGraph) -> None:
        for fkey in [k for k in self._forwards if k[0] == key]:
            del self._forwards[fkey]
        for ukey in [k for k in self._uploads if k[0] == key]:
            del self._uploads[ukey]


@dataclasses.dataclass(frozen=True)
class FullStep:
    """``step(params, features) -> logits``: a jitted full-graph forward
    ``jitted(params, features, arrays)`` with the graph's device arrays
    bound (``None`` where the step plans on the host graph).
    ``residency`` holds the body each layer's sparse-grid launch runs,
    ``sparse_grid_launch``'s name for it, and ``visits`` each launch's
    visits, the planned grid's (row block, k-tile) list before the
    kernel pads it (both empty where the step runs no sparse grid)."""

    jitted: Callable
    arrays: Optional[GraphArrays]
    residency: Tuple[str, ...] = ()
    visits: Tuple[int, ...] = ()

    def __call__(self, params, features):
        return self.jitted(params, features, self.arrays)

    def lower(self, params, features):
        """Lower for (abstract) ``params`` and ``features``; the graph's
        arrays are lowered as arguments of their shapes, placed as
        ``features`` is."""
        place = getattr(features, "sharding", None)
        arrays = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=place),
            self.arrays)
        return self.jitted.lower(params, features, arrays)


def _launch_residency(plan, arrays: GraphArrays, n_layers: int,
                      precision: str) -> Tuple[str, ...]:
    """The body each layer's sparse-grid launch runs
    (``sparse_grid_launch``: whether it row-gathers, and where it keeps its
    dense operand), from the uploaded operands' shapes: every layer's
    operand has the graph's node rows padded to ``block_k``
    (``pad_operands``), in f32 or, under bf16/int8, bf16
    (``quant.cast_dense``)."""
    if plan.resolve(schedulable=True).effective_impl != "pallas_sparse":
        return ()
    import jax.numpy as jnp

    from repro.kernels.flexvector_spmm import sparse_grid_launch

    prec = precision if precision != "f32" else plan.precision
    k = -(-len(arrays.perm) // plan.block_k) * plan.block_k
    launch = sparse_grid_launch(
        k, arrays.cols.shape[1],
        dtype=jnp.float32 if prec == "f32" else jnp.bfloat16,
        out_dtype=plan.out_dtype or jnp.float32, block_rows=plan.block_rows,
        block_k=plan.block_k, block_f=plan.block_f)
    return (launch,) * n_layers


def _takes_arrays(plan) -> bool:
    """Does a step under ``plan`` read the graph only as arrays?  The
    pipeline planner and sharded splits plan on the host graph."""
    from repro.exec import SpmmPlan

    if plan is None:
        return True
    return (isinstance(plan, SpmmPlan) and not plan.sharded
            and not plan.feature_sharded)
