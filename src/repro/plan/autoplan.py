"""Cost-model SpMM plan selection.

Enumerate candidate :class:`~repro.exec.SpmmPlan`s — impl x block sizes x
viable data-mesh widths (from ``dist.topology.viable_mesh_shapes``) —
score each with :func:`repro.plan.cost.spmm_cost`, and return the
argmin-cost plan.  The static default (the plan ``exec.plan_for_config``
would have built from the config alone) is always the first candidate, so
autoplan can never choose a plan the cost model ranks worse than it, and
ties keep the static choice.  Enumeration order is fixed and the argmin is
strict, so the same graph + device budget always yields the same plan.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

from repro.core.sparse_formats import TiledELL
from repro.dist.topology import viable_mesh_shapes
from repro.exec.plan import VALID_IMPLS, SpmmPlan
from repro.plan import cost as cost_mod

BLOCK_CANDIDATES = (16, 32, 64, 128)


@dataclasses.dataclass(frozen=True)
class PlanChoice:
    """An autoplan decision with its receipts."""

    plan: SpmmPlan
    cost: cost_mod.CostBreakdown
    static_plan: SpmmPlan
    static_cost: cost_mod.CostBreakdown
    n_candidates: int
    #: How many candidates were priced by a measured-latency feedback
    #: entry instead of the DeviceModel (0 = purely modeled decision).
    measured_used: int = 0

    def describe(self) -> str:
        p = self.plan
        width = p.n_shards
        return (
            f"{p.impl} rows={p.block_rows} k={p.block_k} f={p.block_f} "
            f"data={width} prec={p.precision} "
            f"(bound {self.cost.seconds:.3e}s vs static "
            f"{self.static_cost.seconds:.3e}s)"
        )


def candidate_widths(n_devices: int) -> Tuple[int, ...]:
    """Data-axis widths viable on ``n_devices`` chips, ascending — the
    ``data`` values of every (data, model) factorization."""
    return tuple(sorted({d for d, _ in viable_mesh_shapes(n_devices,
                                                          n_devices)}))


def _as_stats(graph) -> cost_mod.GraphStats:
    if isinstance(graph, cost_mod.GraphStats):
        return graph
    if isinstance(graph, TiledELL):
        return cost_mod.graph_stats_from_ell(graph)
    raise TypeError(
        f"autoplan wants a TiledELL or GraphStats, got {type(graph).__name__}"
    )


def choose_plan(
    graph,
    feature_dim: int,
    cfg=None,
    *,
    impls: Optional[Sequence[str]] = None,
    mesh=None,
    n_devices: Optional[int] = None,
    widths: Optional[Sequence[int]] = None,
    block_candidates: Sequence[int] = BLOCK_CANDIDATES,
    interpret: Optional[bool] = None,
    dtype_bytes: int = 4,
    device: cost_mod.DeviceModel = cost_mod.TPU_V5E,
    schedulable: Optional[bool] = None,
    precisions: Sequence[str] = ("f32",),
    precision_errors: Optional[dict] = None,
    accuracy_budget: Optional[float] = None,
    f_in: Optional[int] = None,
    feedback=None,
    feedback_key: Optional[str] = None,
) -> PlanChoice:
    """Pick the argmin-cost plan for one graph + device budget.

    ``graph`` is a host :class:`TiledELL` (exact occupancy) or a
    :class:`~repro.plan.cost.GraphStats` (planned shapes, e.g. a serving
    bucket).  ``mesh`` restricts the placement candidates to {1, its data
    width}; otherwise widths are enumerated from ``n_devices`` (default 1
    — the planner never touches jax device state unasked).
    ``schedulable`` says whether the execution context can plan the
    ``pallas_sparse`` block-skipping grid host-side; when it cannot, that
    impl is excluded instead of being costed as something it will not run.

    ``precisions`` adds a storage-precision search dimension (``f32`` |
    ``bf16`` | ``int8``, ``exec.quant`` semantics).  A non-f32 precision
    is a candidate only when its *measured* end-to-end logit error
    (``precision_errors[p]``, e.g. from ``exec.quant.logit_error`` on the
    dataset at hand) fits ``accuracy_budget``; with a budget but no
    measurement the candidate is excluded — an unmeasured precision can
    never be certified, so autoplan never violates the budget.  f32 has
    error 0.0 by definition and is always admissible; the static f32
    default stays the first candidate, preserving the never-worse
    invariant.

    ``f_in`` (the layer's *input* feature width) switches the search to
    whole-layer scoring: every candidate is priced as a full GCN layer,
    ``spmm_cost + combination_seconds`` (the intermediate activation
    written and read back).

    ``feedback`` + ``feedback_key`` close ROADMAP item 5's loop: when a
    :class:`~repro.obs.feedback.PlanFeedback` store holds a measured
    execute-latency EWMA for a candidate (keyed by ``feedback_key`` —
    the serving bucket identity — and the candidate's
    :func:`~repro.obs.feedback.plan_key`), the *measurement* replaces
    the modeled seconds in the comparison; candidates without a
    measurement keep their DeviceModel price (cold-start fallback).
    The static default is re-priced by its own measurement first, so
    the never-worse invariant is kept against measured cost whenever
    measurements exist.  Mixing measured seconds with modeled
    comparison-units is the standard cold-start compromise (same shape
    as ``BucketEstimator``); it converges as measurement coverage
    grows.
    """
    stats = _as_stats(graph)
    errs = dict(precision_errors or {})
    errs.setdefault("f32", 0.0)

    def admissible(p: str) -> bool:
        if p == "f32":
            return True
        if accuracy_budget is None:
            return True
        return p in errs and errs[p] <= accuracy_budget

    precs = tuple(p for p in precisions if admissible(p)) or ("f32",)
    if schedulable is None:
        schedulable = stats.ell is not None

    base_impl = getattr(cfg, "spmm_impl", "reference") if cfg else "reference"
    base_blocks = tuple(
        getattr(cfg, name, 128) if cfg else 128
        for name in ("block_rows", "block_k", "block_f")
    )
    if impls is None:
        impls = (base_impl,) + tuple(
            i for i in VALID_IMPLS if i != base_impl)
    impls = tuple(
        i for i in impls if schedulable or i != "pallas_sparse"
    ) or ("reference",)

    if mesh is not None:
        mesh_width = (
            int(mesh.shape["data"]) if "data" in dict(mesh.shape) else 1)
        if widths is None:
            widths = tuple(sorted({1, mesh_width}))
    else:
        mesh_width = 1
        if widths is None:
            widths = candidate_widths(max(n_devices or 1, 1))
    # An explicit ``widths`` pins the placement candidates (the pipeline
    # planner fixes one common width across layers so row-sharded layouts
    # chain); the static baseline is still scored at the mesh width.
    widths = tuple(
        w for w in widths if w == 1 or w <= max(stats.n_sub_rows, 1)
    ) or (1,)

    def blocks_for(base: int) -> Tuple[int, ...]:
        return tuple(sorted(set(block_candidates) | {base}))

    # Width candidates are priced against the *achievable* balance of the
    # nnz-weighted contiguous sub-row split each width would actually use
    # (exec.sharded's default): a hub-heavy graph whose best w-way split
    # still leaves one shard carrying imb x the mean work gets its
    # per-device terms scaled by imb, so autoplan stops at the split count
    # where the residual imbalance eats the division of labor.
    _imb_cache: dict = {1: 1.0}

    def width_imbalance(width: int) -> float:
        hit = _imb_cache.get(width)
        if hit is None:
            if stats.row_nnz is None:
                hit = 1.0
            else:
                bounds = cost_mod.balanced_split_points(stats.row_nnz, width)
                hit = cost_mod.split_imbalance(stats.row_nnz, bounds)
            _imb_cache[width] = hit
        return hit

    def score(impl, br, bk, bf, width, precision="f32"):
        return cost_mod.spmm_cost(
            stats, feature_dim, impl=impl, block_rows=br, block_k=bk,
            block_f=bf, n_shards=width, dtype_bytes=dtype_bytes,
            precision=precision,
            shard_imbalance=width_imbalance(width), device=device,
        )

    def layer_score(impl, br, bk, bf, width, precision):
        """(comparison seconds, CostBreakdown receipt) for one candidate.

        Without ``f_in`` the comparison scalar is the SpMM bound alone
        (historical behavior).  With ``f_in`` it is the whole layer: the
        SpMM plus the standalone combination launch (which writes the
        intermediate activation the SpMM then re-reads).
        """
        c = score(impl, br, bk, bf, width, precision)
        if f_in is None:
            return c.seconds, c
        comb = cost_mod.combination_seconds(
            stats.n_dense_rows, f_in, feature_dim,
            precision=precision, device=device,
        )
        return c.seconds + comb, c

    measured_used = 0

    def with_measured(modeled, impl, br, bk, bf, w, prec):
        """A candidate's comparison scalar: measured EWMA if one exists,
        else the modeled seconds (cold-start fallback)."""
        nonlocal measured_used
        if feedback is None or feedback_key is None:
            return modeled
        from repro.obs.feedback import plan_key  # deferred: no cycle

        m = feedback.measured(
            feedback_key, plan_key(impl, br, bk, bf, w, prec))
        if m is None:
            return modeled
        measured_used += 1
        return m

    # The static default leads: what plan_for_config(cfg[, mesh]) builds.
    static_impl = base_impl if (
        schedulable or base_impl != "pallas_sparse") else "pallas"
    static_secs, static_cost = layer_score(
        static_impl, *base_blocks, mesh_width, "f32")
    static_secs = with_measured(
        static_secs, static_impl, *base_blocks, mesh_width, "f32")
    best = (static_impl, *base_blocks, mesh_width, "f32")
    best_secs, best_cost = static_secs, static_cost

    n_cand = 1
    for impl in impls:
        for br in blocks_for(base_blocks[0]):
            for bk in blocks_for(base_blocks[1]):
                for bf in blocks_for(base_blocks[2]):
                    for w in widths:
                        for prec in precs:
                            n_cand += 1
                            s, c = layer_score(impl, br, bk, bf, w, prec)
                            s = with_measured(s, impl, br, bk, bf, w, prec)
                            if s < best_secs:
                                best = (impl, br, bk, bf, w, prec)
                                best_secs, best_cost = s, c

    impl, br, bk, bf, width, precision = best
    hot_k_first = True
    if impl == "pallas_sparse" and stats.ell is not None:
        hot_k_first = choose_hot_k_first(
            stats.ell, feature_dim, block_rows=br, block_k=bk, block_f=bf)
    if width <= 1:
        chosen_mesh = None
    elif mesh is not None and width == mesh_width:
        chosen_mesh = mesh
    else:
        from repro.launch.mesh import make_data_mesh  # deferred: jax devices

        chosen_mesh = make_data_mesh(width)
    plan = SpmmPlan(
        impl=impl, block_rows=br, block_k=bk, block_f=bf,
        interpret=interpret, mesh=chosen_mesh, hot_k_first=hot_k_first,
        precision=precision,
    )
    static_plan = SpmmPlan(
        impl=base_impl, block_rows=base_blocks[0], block_k=base_blocks[1],
        block_f=base_blocks[2], interpret=interpret, mesh=mesh,
    )
    return PlanChoice(
        plan=plan, cost=best_cost, static_plan=static_plan,
        static_cost=static_cost, n_candidates=n_cand,
        measured_used=measured_used,
    )


def choose_hot_k_first(
    ell: TiledELL,
    feature_dim: int,
    *,
    block_rows: int = 128,
    block_k: int = 128,
    block_f: int = 128,
) -> bool:
    """Pick the ``pallas_sparse`` k-tile visit order that minimizes dense
    k-tile switches.

    The block-skipping grid streams a fresh dense k-tile into VMEM every
    time consecutive schedule steps change ``k`` — the schedule's dominant
    re-fill traffic.  Score both orderings (hot-tiles-first vs natural
    row-major) by counting switches in the planned pair list and keep the
    cheaper one; ties keep ``hot_k_first=True`` (the historical default).
    Deterministic: the grids are, so the counts are.
    """
    import numpy as np

    from repro.core.dataflow import plan_kernel_grid

    def switches(hot: bool) -> int:
        pairs = plan_kernel_grid(
            ell, feature_dim, block_rows=block_rows, block_k=block_k,
            block_f=block_f, skip_empty=True, hot_k_first=hot,
        ).pairs
        if len(pairs) <= 1:
            return 0
        return int(np.count_nonzero(np.diff(pairs[:, 1]) != 0))

    return switches(True) <= switches(False)


def autoplan(graph, feature_dim: int, cfg=None, **kw) -> SpmmPlan:
    """:func:`choose_plan` without the receipts."""
    return choose_plan(graph, feature_dim, cfg, **kw).plan


# ---------------------------------------------------------------------------
# Serving bucket-ladder growth factor
# ---------------------------------------------------------------------------

GROWTH_CANDIDATES = (1.3, 1.5, 2.0, 4.0)


def choose_ladder_growth(
    stats,
    cfg,
    *,
    base_nodes: int,
    top_nodes: int,
    candidates: Sequence[float] = GROWTH_CANDIDATES,
    feature_dim: Optional[int] = None,
    horizon: int = 256,
    n_probes: int = 33,
    device: cost_mod.DeviceModel = cost_mod.TPU_V5E,
) -> float:
    """Pick the serving bucket ladder's growth factor with the cost model.

    The tradeoff: a finer ladder (small growth) pads each request to a
    tighter rung — less wasted SpMM work per query — but multiplies the
    rung count, and every rung costs a warmup compile *and* an execution
    of that rung's shape to prime it.  Score each candidate as

        E_s[cost(rung(s))]  +  sum_r cost(r) / horizon

    where ``s`` ranges over ``n_probes`` geometric probe sizes between
    the base and top rung (serving receptive fields span orders of
    magnitude, so the size distribution is modelled log-uniform),
    ``rung(s)`` is the smallest rung covering ``s``, ``cost`` is the
    per-rung :func:`repro.plan.cost.spmm_cost` roofline bound over the
    graph's own statistics (``rows_per_node``, ``mean_row_nnz``), and the
    second term amortizes one priming execution per rung over a
    ``horizon`` of expected requests.  Deterministic: fixed probe set,
    fixed candidate order, strict argmin with earlier candidates winning
    ties.
    """
    import math

    stats = _as_stats(stats) if not isinstance(
        stats, cost_mod.GraphStats) else stats
    if feature_dim is None:
        feature_dim = max(
            getattr(cfg, "hidden_dim", 128), getattr(cfg, "out_dim", 1))
    rows_factor = stats.rows_per_node
    mean_nnz = stats.mean_row_nnz or cfg.tau / 2

    def rung_cost(nodes: int) -> float:
        # One representative SpMM per rung (relative comparison across
        # candidates only), priced by the same bucket-cost arithmetic the
        # runtime's admission estimator uses.
        rows = -(-int(nodes * rows_factor) // cfg.block_rows) * cfg.block_rows
        return cost_mod.bucket_forward_seconds(
            rows=rows,
            n_out_rows=nodes,
            mean_row_nnz=mean_nnz,
            tau=cfg.tau,
            f_dims=(feature_dim,),
            impl=cfg.spmm_impl,
            block_rows=cfg.block_rows, block_k=cfg.block_k,
            block_f=cfg.block_f, device=device,
        )

    base = min(base_nodes, top_nodes)
    if base >= top_nodes:
        return float(candidates[0])
    ratio = top_nodes / base
    probes = [
        min(int(math.ceil(base * ratio ** (i / (n_probes - 1)))), top_nodes)
        for i in range(n_probes)
    ]

    from repro.serve.batcher import ladder_rungs

    best_growth, best_score = None, None
    for growth in candidates:
        rungs = ladder_rungs(base, top_nodes, growth, cfg.block_k)
        costs = [rung_cost(n) for n in rungs]
        expected = 0.0
        for s in probes:
            idx = next(i for i, n in enumerate(rungs) if n >= s)
            expected += costs[idx]
        score = expected / len(probes) + sum(costs) / max(horizon, 1)
        if best_score is None or score < best_score:
            best_growth, best_score = growth, score
    return float(best_growth)
