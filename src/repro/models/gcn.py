"""GCN inference/training on top of the FlexVector SpMM core.

A GCN layer is X' = sigma(A_hat (X W)) — the paper's execution order
A x (X x W) (Section II-A1): the combination (dense X W) runs on the MXU
via jnp.dot, the aggregation (sparse A_hat times dense) runs through
``spmm_ell`` (reference path or the FlexVector Pallas kernel).

The adjacency is preprocessed once per graph (hybrid edge-cut +
vertex-cut, Section IV); model parameters are plain pytrees so the
training substrate (repro.train) and the distribution layer (repro.dist)
compose without a framework dependency.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import PreprocessResult, preprocess
from repro.core.dataflow import KernelGrid, plan_kernel_grid
from repro.core.sparse_formats import CSRMatrix


@dataclasses.dataclass(frozen=True)
class GCNConfig:
    in_dim: int
    hidden_dim: int
    out_dim: int
    n_layers: int = 2
    tau: int = 6
    tile_rows: int = 16
    edge_cut: str = "rcm"
    spmm_impl: str = "reference"   # reference | pallas | pallas_sparse
    block_rows: int = 128
    block_k: int = 128
    block_f: int = 128


@dataclasses.dataclass
class GCNGraph:
    """Preprocessed graph operand shared by all layers, with the
    ``pallas_sparse`` schedules planned for it so far (by block_rows,
    block_k, hot_k_first)."""

    pre: PreprocessResult
    n_nodes: int
    inv: Optional[np.ndarray] = None  # inverse edge-cut permutation
    grids: Dict[Tuple[int, int, bool], KernelGrid] = dataclasses.field(
        default_factory=dict)

    def __post_init__(self):
        # Precomputed once: the inverse permutation sits on the per-request
        # hot path of the serving engine, so it must not be rebuilt per call.
        if self.inv is None:
            perm = np.asarray(self.pre.perm)
            inv = np.empty_like(perm)
            inv[perm] = np.arange(perm.size)
            self.inv = inv

    @staticmethod
    def build(adj_norm: CSRMatrix, cfg: GCNConfig) -> "GCNGraph":
        pre = preprocess(
            adj_norm,
            tau=cfg.tau,
            tile_rows=cfg.tile_rows,
            edge_cut=cfg.edge_cut,
            pad_rows_to=cfg.block_rows,
        )
        return GCNGraph(pre=pre, n_nodes=adj_norm.rows)

    def kernel_grid(self, block_rows: int, block_k: int,
                    hot_k_first: bool = True) -> KernelGrid:
        """The sparse-grid schedule for these blocks, planned once."""
        key = (block_rows, block_k, hot_k_first)
        if key not in self.grids:
            self.grids[key] = plan_kernel_grid(
                self.pre.ell, block_k, block_rows=block_rows,
                block_k=block_k, block_f=block_k, hot_k_first=hot_k_first)
        return self.grids[key]

    def arrays(self, plan) -> "GraphArrays":
        """The operands a forward under ``plan`` reads, as host arrays."""
        ell = self.pre.ell
        return GraphArrays(
            cols=ell.cols, vals=ell.vals, row_map=ell.row_map,
            perm=np.asarray(self.pre.perm, np.int32),
            inv=np.asarray(self.inv, np.int32),
            grid=self.kernel_grid(plan.block_rows, plan.block_k,
                                  plan.hot_k_first),
            n_out_rows=ell.n_orig_rows)


@dataclasses.dataclass(frozen=True)
class GraphArrays:
    """A preprocessed graph's operands as arrays (``GCNGraph.arrays``).

    A pytree: a jitted forward takes it as an argument, so its operands
    live on the device once per graph and none of them becomes a constant
    of the compiled program.  It serves the static single-device plans;
    the pipeline planner and the sharded split plan on the host
    ``GCNGraph``.
    """

    cols: jax.typing.ArrayLike     # (R, tau) int32
    vals: jax.typing.ArrayLike     # (R, tau)
    row_map: jax.typing.ArrayLike  # (R,) int32
    perm: jax.typing.ArrayLike     # (N,) int32 edge-cut permutation
    inv: jax.typing.ArrayLike      # (N,) int32 its inverse
    grid: KernelGrid
    n_out_rows: int


jax.tree_util.register_dataclass(
    GraphArrays,
    data_fields=["cols", "vals", "row_map", "perm", "inv", "grid"],
    meta_fields=["n_out_rows"])


def init_params(cfg: GCNConfig, key: jax.Array) -> Dict[str, Dict[str, jax.Array]]:
    dims = [cfg.in_dim] + [cfg.hidden_dim] * (cfg.n_layers - 1) + [cfg.out_dim]
    params: Dict[str, Dict[str, jax.Array]] = {}
    for i, (d_in, d_out) in enumerate(zip(dims[:-1], dims[1:])):
        key, sub = jax.random.split(key)
        scale = jnp.sqrt(2.0 / d_in)
        params[f"layer_{i}"] = {
            "w": jax.random.normal(sub, (d_in, d_out), jnp.float32) * scale,
            "b": jnp.zeros((d_out,), jnp.float32),
        }
    return params


def gcn_forward(
    params: Dict[str, Dict[str, jax.Array]],
    graph: GCNGraph,
    features: jax.Array,
    cfg: GCNConfig,
    plan=None,
    mesh=None,
    out_layout: str = "replicated",
    precision: str = "f32",
) -> jax.Array:
    """Full-graph forward pass.

    ``features`` are in original node order; the edge-cut permutation is
    applied on entry and inverted on exit, so callers never see permuted
    node ids.  ``graph`` is the host :class:`GCNGraph`, or its
    :class:`GraphArrays` for a static single-device plan.

    ``plan`` (an :class:`~repro.exec.SpmmPlan`) or ``mesh`` place the
    aggregation step: a mesh whose ``data`` axis is wider than one device
    shards the SpMM row-tile grid over it, with the cross-shard
    segment-psum folding vertex-cut partials back into output rows.
    Without either, the plan is derived from ``cfg`` and runs
    single-device — the same dispatch path either way.  ``plan="auto"``
    hands the *whole stack* to the cost model: ``repro.exec.pipeline``
    jointly picks per-layer impl/block sizes, the data-mesh width and the
    activation layout at every layer boundary (``mesh`` then bounds the
    candidate widths), so consecutive sharded layers chain reduce-scatter
    epilogues instead of round-tripping activations through replicated
    form.  A :class:`~repro.exec.pipeline.GcnPipelinePlan` can also be
    passed directly as ``plan``.  ``out_layout="row_sharded"`` asks for
    the output activation left row-sharded (padded height
    ``round_up(n_nodes, width)``, no inverse permutation) — the form a
    following sharded stage consumes.

    ``precision`` (``f32`` | ``bf16`` | ``int8``, ``exec.quant``
    semantics) quantizes the layer weights and stamps the SpMM plans, so
    both halves of each layer — combination matmul and aggregation SpMM
    — run at the reduced storage width with f32 accumulation.  ``f32``
    (the default) leaves everything bitwise-untouched; a ``plan`` that
    already carries a non-f32 precision (autoplan's choice) is honored.
    """
    from repro.exec import quant
    from repro.exec.pipeline import GcnPipelinePlan, pipeline_forward

    quant.validate_precision(precision)
    if isinstance(plan, GcnPipelinePlan):
        return pipeline_forward(params, graph, features, plan)
    if isinstance(plan, str):
        if plan != "auto":
            raise ValueError(f"unknown plan: {plan!r} (expected 'auto')")
        from repro.exec.pipeline import plan_pipeline

        pplan = plan_pipeline(
            cfg, graph.pre.ell, mesh=mesh, n_layers=len(params),
            out_layout=out_layout, precision=precision,
        )
        return pipeline_forward(params, graph, features, pplan)
    if plan is None:
        from repro.exec import plan_for_config

        plan = plan_for_config(cfg, mesh=mesh)
    if precision != "f32" and plan.precision != precision:
        plan = dataclasses.replace(plan, precision=precision)
    prec = plan.precision
    if prec != "f32":
        params = quant.quantize_params(params, prec, plan.block_rows)
    # A static plan applies uniformly to every layer; a row-sharded output
    # request swaps only the final epilogue (meaningful on a >1-wide data
    # axis — on one device the layouts coincide and the standard replicated
    # output comes back).
    shard_out = out_layout == "row_sharded" and plan.n_shards > 1
    from repro.exec.dispatch import execute_layer
    from repro.exec.operands import SpmmOperands

    if isinstance(graph, GraphArrays):
        operands = SpmmOperands(
            cols=graph.cols, vals=graph.vals, row_map=graph.row_map,
            n_out_rows=graph.n_out_rows, grid=graph.grid)
        perm, inv = graph.perm, graph.inv
    else:
        operands = SpmmOperands.from_ell(graph.pre.ell)
        perm, inv = jnp.asarray(graph.pre.perm), jnp.asarray(graph.inv)
    x = features[perm]
    n_layers = len(params)
    for i in range(n_layers):
        p = params[f"layer_{i}"]
        layer_plan = plan
        if shard_out and i == n_layers - 1:
            layer_plan = dataclasses.replace(plan, out_layout="row_sharded")
        x = execute_layer(
            layer_plan, operands, x, p, w_block_rows=plan.block_rows)
        if i < n_layers - 1:
            x = jax.nn.relu(x)
    if shard_out:
        return x          # permuted order, padded height, row-sharded
    return x[inv]


def gcn_loss(
    params,
    graph: GCNGraph,
    features: jax.Array,
    labels: jax.Array,
    cfg: GCNConfig,
    mask: Optional[jax.Array] = None,
    plan=None,
) -> jax.Array:
    logits = gcn_forward(params, graph, features, cfg, plan=plan)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    if mask is not None:
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return nll.mean()


def gcn_accuracy(params, graph, features, labels, cfg, mask=None,
                 plan=None) -> jax.Array:
    logits = gcn_forward(params, graph, features, cfg, plan=plan)
    correct = (jnp.argmax(logits, -1) == labels).astype(jnp.float32)
    if mask is not None:
        return (correct * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return correct.mean()
