"""Multi-layer GCN pipeline planning: keep activations sharded end-to-end.

The per-layer executor (``exec.dispatch`` / ``exec.sharded``) already
offers two epilogues — replicated psum or row-sharded reduce-scatter —
and two dense prologues.  This module plans *across* layers: for a full
:class:`~repro.models.gcn.GCNConfig` stack it chooses, jointly, one data
mesh width, per-layer impl/block sizes, and the activation layout at
every layer boundary, so that a stack of sharded SpMMs never round-trips
activations through replicated form between layers.

The key asymmetry the planner exploits: a row-sharded activation is
gathered *after* the next layer's combination matmul (on ``xw``, which
has that layer's **output** width), not before it (on ``x``, which has
the input width).  For the canonical GCN funnel F_in >= F_hidden >>
F_out, chaining reduce-scatter -> local matmul -> all-gather moves

    (n-1)/n * Npad * (F_hidden + F_out)   bytes

across a 2-layer stack where per-layer psum moves

    2(n-1)/n * N * (F_hidden + F_out),

i.e. strictly fewer bytes whenever the widths are not all equal — and the
final layer's all-reduce is the *only* full all-reduce in the stack.  The
replicated-activation DRAM writeback (every device materializing every
intermediate) shrinks the same way.

Planning is a tiny exact DP: the state at each layer boundary is the
activation layout (``replicated`` | ``row_sharded``), edges are costed by
``plan.cost.spmm_cost`` under the edge's (dense_layout, out_layout) pair
plus the combination-matmul roofline and the layout's activation
writeback.  The input features and the final output are pinned
replicated, so a plan is a shortest path through a 2-wide lattice.  The
static per-layer default (the config's impl/blocks, replicated
everywhere, at the given mesh width) is always costed as the baseline
and the chosen pipeline is never costed worse than it.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.sparse_formats import TiledELL
from repro.exec.operands import SpmmOperands
from repro.exec.plan import SpmmPlan
from repro.plan import cost as cost_mod

LAYOUTS = ("replicated", "row_sharded")


def _round_up(x: int, q: int) -> int:
    return -(-x // q) * q


@dataclasses.dataclass(frozen=True)
class LayerPlan:
    """One layer's placed SpMM plan plus its boundary layouts.

    ``in_layout`` is the layout of the activation *entering* the layer
    (and therefore of ``xw``, so it becomes the SpMM plan's
    ``dense_layout``); ``out_layout`` the layout it emits.
    """

    spmm: SpmmPlan
    f_in: int
    f_out: int
    in_layout: str = "replicated"
    out_layout: str = "replicated"
    seconds: float = 0.0          # planner's roofline bound for this layer


@dataclasses.dataclass(frozen=True)
class GcnPipelinePlan:
    """A jointly planned multi-layer GCN forward.

    ``cost_seconds`` is the planner's bound for the whole stack;
    ``static_cost_seconds`` the same bound for the static per-layer
    default (config impl/blocks, replicated activations) it is guaranteed
    never to exceed.
    """

    layers: Tuple[LayerPlan, ...]
    n_shards: int = 1
    cost_seconds: float = 0.0
    static_cost_seconds: float = 0.0

    @property
    def mesh(self):
        return self.layers[0].spmm.mesh if self.layers else None

    @property
    def n_collective_rounds(self) -> int:
        """Full all-reduces in the stack (reduce-scatters/gathers not
        counted): the pipeline invariant is that only layers emitting a
        replicated output pay one."""
        return sum(
            1 for lp in self.layers
            if lp.out_layout == "replicated" and lp.spmm.sharded
        )

    def describe(self) -> str:
        chain = " -> ".join(
            f"L{i}:{lp.spmm.impl}/{lp.out_layout}"
            for i, lp in enumerate(self.layers)
        )
        return (
            f"data={self.n_shards} {chain} "
            f"(bound {self.cost_seconds:.3e}s vs static "
            f"{self.static_cost_seconds:.3e}s)"
        )


def _layer_dims(cfg, n_layers: Optional[int] = None) -> Tuple[Tuple[int, int], ...]:
    n = n_layers or cfg.n_layers
    dims = [cfg.in_dim] + [cfg.hidden_dim] * (n - 1) + [cfg.out_dim]
    return tuple(zip(dims[:-1], dims[1:]))


def _combination_seconds(n_rows: int, f_in: int, f_out: int, n_shards: int,
                         in_layout: str, device, act_bytes: int = 4,
                         w_bytes: int = 4) -> float:
    """Roofline bound of the layer's dense ``x @ w`` on one device: a
    row-sharded input runs the matmul on local rows only — the second,
    quieter win of keeping activations sharded."""
    rows = (
        _round_up(n_rows, n_shards) // n_shards
        if (in_layout == "row_sharded" and n_shards > 1)
        else n_rows
    )
    flops = 2.0 * rows * f_in * f_out
    byts = (float(rows) * (f_in + f_out) * act_bytes
            + float(f_in) * f_out * w_bytes)
    return max(flops / device.peak_flops, byts / device.hbm_bw)


def plan_pipeline(
    cfg,
    graph,
    *,
    mesh=None,
    n_devices: Optional[int] = None,
    n_layers: Optional[int] = None,
    interpret: Optional[bool] = None,
    out_layout: str = "replicated",
    device: cost_mod.DeviceModel = cost_mod.TPU_V5E,
    dtype_bytes: int = 4,
    precision: str = "f32",
) -> GcnPipelinePlan:
    """Jointly plan every layer of a GCN stack over one graph.

    ``graph`` is a host :class:`TiledELL` or
    :class:`~repro.plan.cost.GraphStats`.  For each candidate data-mesh
    width (one width for the whole stack — row-sharded layouts only chain
    between equal-width layers) the per-layer impl/blocks come from
    ``plan.autoplan`` pinned to that width, then an exact DP over the
    activation layout at each layer boundary picks the epilogue chain.
    Deterministic, and never costed worse than the static per-layer
    default.  ``out_layout`` pins the layout the stack must *emit*
    (``row_sharded`` when the consumer is another sharded stage; on a
    1-wide candidate the layouts coincide and replicated is used).
    ``precision`` is stamped on every per-layer plan and fed to the cost
    model, so a bf16/int8 stack is priced at its storage widths (weights
    and activations count at their quantized bytes; the accumulator
    collectives stay f32).
    """
    from repro.exec.quant import activation_bytes, validate_precision
    from repro.plan.autoplan import candidate_widths, choose_plan

    validate_precision(precision)
    act_bytes = (
        dtype_bytes if precision == "f32" else activation_bytes(precision))
    w_bytes = (
        dtype_bytes if precision == "f32"
        else device.bytes_per_element(precision))

    stats = (
        cost_mod.graph_stats_from_ell(graph)
        if isinstance(graph, TiledELL) else graph
    )
    dims = _layer_dims(cfg, n_layers)
    n_out = stats.n_out_rows

    if mesh is not None:
        mesh_width = (
            int(mesh.shape["data"]) if "data" in dict(mesh.shape) else 1)
        widths: Tuple[int, ...] = tuple(sorted({1, mesh_width}))
    else:
        mesh_width = 1
        # A placed plan needs a real mesh, so candidate widths are capped
        # by the host's device count even when the caller asks for more.
        widths = tuple(
            w for w in candidate_widths(max(n_devices or 1, 1))
            if w <= jax.device_count()
        )
    widths = tuple(
        w for w in widths if w == 1 or w <= max(stats.n_sub_rows, 1)
    ) or (1,)

    def imbalance(width: int) -> float:
        if width <= 1 or stats.row_nnz is None:
            return 1.0
        bounds = cost_mod.balanced_split_points(stats.row_nnz, width)
        return cost_mod.split_imbalance(stats.row_nnz, bounds)

    def edge_seconds(base_plan, f_in, f_out, width, in_layout, out_layout,
                     imb) -> float:
        spmm = cost_mod.spmm_cost(
            stats, f_out, impl=base_plan.impl,
            block_rows=base_plan.block_rows, block_k=base_plan.block_k,
            block_f=base_plan.block_f, n_shards=width,
            out_layout=out_layout, dense_layout=in_layout,
            shard_imbalance=imb, dtype_bytes=dtype_bytes,
            precision=precision, device=device,
        ).seconds
        comb = _combination_seconds(n_out, f_in, f_out, width, in_layout,
                                    device, act_bytes, w_bytes)
        # Per-device share of the layout's activation writeback; the
        # replication factor is what distinguishes the layouts here.
        wb = cost_mod.activation_writeback_bytes(
            n_out, f_out, width, out_layout, act_bytes
        ) / max(width, 1) / device.hbm_bw
        return spmm + comb + wb

    def mesh_for(width: int):
        if width <= 1:
            return None
        if mesh is not None and width == mesh_width:
            return mesh
        from repro.launch.mesh import make_data_mesh  # deferred: jax devices

        return make_data_mesh(width)

    # -- static per-layer baseline: config impl/blocks, replicated, at the
    # width plan_for_config(cfg, mesh) would have used.
    static_impl = cfg.spmm_impl if (
        stats.ell is not None or cfg.spmm_impl != "pallas_sparse") else "pallas"
    static_base = SpmmPlan(
        impl=static_impl, block_rows=cfg.block_rows, block_k=cfg.block_k,
        block_f=cfg.block_f, mesh=mesh,
    )
    static_w = mesh_width if mesh_width <= max(stats.n_sub_rows, 1) else 1
    static_imb = imbalance(static_w)
    static_total = sum(
        edge_seconds(static_base, f_in, f_out, static_w,
                     "replicated", "replicated", static_imb)
        for f_in, f_out in dims
    )

    best: Optional[GcnPipelinePlan] = None
    for w in widths:
        w_mesh = mesh_for(w)
        imb = imbalance(w)
        # Per-layer impl/blocks at this width (autoplan, width pinned; the
        # layout DP below only shifts additive collective/writeback terms,
        # so the impl/block argmin is shared across layouts).
        bases = []
        for f_in, f_out in dims:
            choice = choose_plan(
                stats, f_out, cfg, mesh=w_mesh, widths=(w,),
                interpret=interpret, dtype_bytes=dtype_bytes, device=device,
            )
            bases.append(choice.plan)
        states = LAYOUTS if w > 1 else ("replicated",)

        # Exact DP: dist[layout entering layer i]; input replicated; the
        # final layer pinned to the layout the caller asked the stack to
        # emit (degrading to replicated on a 1-wide candidate).
        final = out_layout if w > 1 else "replicated"
        dist = {"replicated": (0.0, [])}
        for i, (f_in, f_out) in enumerate(dims):
            last = i == len(dims) - 1
            outs = (final,) if last else states
            nxt: dict = {}
            for in_l, (acc, path) in dist.items():
                for out_l in outs:
                    s = acc + edge_seconds(
                        bases[i], f_in, f_out, w, in_l, out_l, imb)
                    if out_l not in nxt or s < nxt[out_l][0]:
                        nxt[out_l] = (s, path + [(in_l, out_l)])
            dist = nxt
        total, path = dist[final]
        layers = tuple(
            LayerPlan(
                spmm=dataclasses.replace(
                    bases[i], mesh=w_mesh, dense_layout=in_l,
                    out_layout=out_l, interpret=interpret,
                    precision=precision,
                ),
                f_in=dims[i][0], f_out=dims[i][1],
                in_layout=in_l, out_layout=out_l,
                seconds=edge_seconds(
                    bases[i], dims[i][0], dims[i][1], w, in_l, out_l, imb),
            )
            for i, (in_l, out_l) in enumerate(path)
        )
        cand = GcnPipelinePlan(
            layers=layers, n_shards=w, cost_seconds=total,
            static_cost_seconds=static_total,
        )
        if best is None or cand.cost_seconds < best.cost_seconds:
            best = cand
    return best


def chain_layouts(n_layers: int) -> Tuple[Tuple[str, str], ...]:
    """The fully chained layout assignment: replicated features in,
    row-sharded at every internal boundary, replicated out — the shape
    whose only full all-reduce is the final epilogue."""
    return tuple(
        (
            "replicated" if i == 0 else "row_sharded",
            "replicated" if i == n_layers - 1 else "row_sharded",
        )
        for i in range(n_layers)
    )


def static_pipeline(
    cfg,
    mesh=None,
    *,
    pipelined: bool = True,
    interpret: Optional[bool] = None,
    n_layers: Optional[int] = None,
    impl: Optional[str] = None,
    precision: str = "f32",
) -> GcnPipelinePlan:
    """A :class:`GcnPipelinePlan` from the config alone — no cost model.

    Every layer uses the config's impl/blocks on ``mesh``;
    ``pipelined=True`` chains :func:`chain_layouts` (reduce-scatter
    between layers, one final all-reduce), ``pipelined=False`` is the
    per-layer-psum baseline.  The two differ *only* in layouts, which is
    what the parity tests and the pipeline benchmark need: an
    apples-to-apples traffic comparison at identical impl/blocks.
    """
    dims = _layer_dims(cfg, n_layers)
    width = (
        int(mesh.shape["data"])
        if mesh is not None and "data" in dict(mesh.shape) else 1
    )
    layouts = (
        chain_layouts(len(dims))
        if (pipelined and width > 1)
        else tuple(("replicated", "replicated") for _ in dims)
    )
    base = SpmmPlan(
        impl=impl or cfg.spmm_impl, block_rows=cfg.block_rows,
        block_k=cfg.block_k, block_f=cfg.block_f, interpret=interpret,
        mesh=mesh, precision=precision,
    )
    layers = tuple(
        LayerPlan(
            spmm=dataclasses.replace(
                base, dense_layout=in_l, out_layout=out_l),
            f_in=f_in, f_out=f_out, in_layout=in_l, out_layout=out_l,
        )
        for (f_in, f_out), (in_l, out_l) in zip(dims, layouts)
    )
    return GcnPipelinePlan(layers=layers, n_shards=width)


def pipeline_forward(
    params,
    graph,
    features: jax.Array,
    pplan: GcnPipelinePlan,
) -> jax.Array:
    """Forward a GCN stack under a :class:`GcnPipelinePlan`.

    Exactly :func:`repro.models.gcn.gcn_forward`'s loop, except each
    layer dispatches through its own placed :class:`SpmmPlan` via
    :func:`repro.exec.dispatch.execute_layer` — so a ``row_sharded``
    boundary hands the next layer a padded, row-sharded activation whose
    combination matmul runs on local rows, and the only full all-reduce
    is the final replicated epilogue.  Bitwise-identical to the
    replicated path: the reduce-scatter epilogue performs the same
    per-row reduction as the psum, and the pad rows (all zeros, past
    every real row) never feed a nonzero adjacency column.
    """
    assert len(pplan.layers) == len(params), (
        f"pipeline plan has {len(pplan.layers)} layers, params have "
        f"{len(params)}"
    )
    from repro.exec import quant
    from repro.exec.dispatch import execute_layer

    operands = SpmmOperands.from_ell(graph.pre.ell)
    perm = jnp.asarray(graph.pre.perm)
    x = features[perm]
    n_layers = len(pplan.layers)
    for i, lp in enumerate(pplan.layers):
        p = params[f"layer_{i}"]
        prec = lp.spmm.precision
        if prec != "f32":
            p = quant.quantize_params({"l": p}, prec, lp.spmm.block_rows)["l"]
        x = execute_layer(
            lp.spmm, operands, x, p, w_block_rows=lp.spmm.block_rows)
        if i < n_layers - 1:
            x = jax.nn.relu(x)
    last = pplan.layers[-1]
    if last.out_layout == "row_sharded" and last.spmm.sharded:
        return x          # permuted order, padded height, row-sharded
    return x[jnp.asarray(graph.inv)]
