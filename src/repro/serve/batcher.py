"""Shape-bucketed micro-batching for GCN queries.

jit recompiles on every new operand shape, and sampled subgraphs have a
different shape per request — fatal for tail latency.  The batcher fixes
this with a small geometric ladder of ``(nodes, ell_rows)`` buckets:

* every extracted subgraph is padded up to the smallest bucket that fits
  (PAD_COL ELL slots, zero feature rows), so the set of operand shapes the
  compiler ever sees is the ladder × a power-of-two batch ladder —
  enumerable, and therefore fully compilable at warmup;
* concurrent requests in the same bucket are coalesced into one
  block-diagonal operand (each request's columns and output rows offset by
  its slot × bucket nodes), so a batch of B subgraphs runs as **one**
  ``spmm_ell`` call per layer, not B;
* executables are AOT-compiled (``jit(...).lower(avals).compile()``) and
  cached per ``(bucket, batch)``; ``compiles`` counts every executable
  actually built, which is how tests assert the zero-recompile-after-warmup
  guarantee.

The top ladder entry is sized from the full graph's preprocessed operand,
so any subgraph — even an adversarially hub-heavy one — fits some bucket.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sparse_formats import PAD_COL
from repro.exec import SpmmOperands, plan_for_config, quant
from repro.exec.dispatch import execute_layer
from repro.models.gcn import GCNConfig, GCNGraph
from repro.obs.trace import span
from repro.serve.sampler import SampledSubgraph


@dataclasses.dataclass(frozen=True, order=True)
class Bucket:
    """One ladder rung: per-request padded (dense nodes, ELL rows)."""

    nodes: int
    rows: int


def _round_up(x: int, q: int) -> int:
    return -(-x // q) * q


def ladder_rungs(base: int, top: int, growth: float, quantum: int) -> List[int]:
    """Node counts of a geometric ladder: ``base`` up to ``top`` by factor
    ``growth``, every rung rounded up to ``quantum`` and strictly
    increasing (a fractional factor whose step rounds away still advances
    by one quantum, so the ladder always terminates at ``top``)."""
    if growth <= 1:
        raise ValueError(f"ladder growth must be > 1, got {growth}")
    rungs = [min(base, top)]
    while rungs[-1] < top:
        nxt = max(_round_up(int(rungs[-1] * growth), quantum),
                  rungs[-1] + quantum)
        rungs.append(min(nxt, top))
    return rungs


@dataclasses.dataclass(frozen=True)
class BucketLadder:
    entries: Tuple[Bucket, ...]   # ascending
    mean_row_nnz: float = 0.0     # graph's mean nnz per sub-row (cost stats)

    @staticmethod
    def for_graph(
        full_graph: GCNGraph,
        cfg: GCNConfig,
        base_nodes: int = 256,
        growth=4,
    ) -> "BucketLadder":
        """Geometric ladder capped by the full graph's operand.

        The per-rung ELL-row budget comes from the cost model's graph
        statistics: ``rows = nodes * stats.rows_per_node`` ties it to the
        graph's own vertex-cut expansion factor, and ``mean_row_nnz`` is
        carried on the ladder so per-bucket autoplanning can estimate a
        rung's nonzero count before any request has landed in it.  The top
        entry covers the whole graph, so escalation always terminates.

        ``growth`` may be any factor > 1 (each rung rounds up to
        ``block_k`` and always advances by at least one block, so a
        fractional factor still terminates), or ``"auto"`` to let the
        cost model pick a factor
        (:func:`repro.plan.autoplan.choose_ladder_growth`: padded-work
        vs warmup-compile tradeoff scored on this graph's statistics).
        """
        from repro.plan import cost

        stats = cost.graph_stats_from_ell(full_graph.pre.ell)
        n_nodes = full_graph.n_nodes
        top_nodes = _round_up(n_nodes, cfg.block_k)
        base = min(_round_up(base_nodes, cfg.block_k), top_nodes)
        if growth == "auto":
            from repro.plan.autoplan import choose_ladder_growth

            growth = choose_ladder_growth(
                stats, cfg, base_nodes=base, top_nodes=top_nodes
            )
        entries = tuple(
            Bucket(nodes=n, rows=_round_up(n * stats.rows_per_node,
                                           cfg.block_rows))
            for n in ladder_rungs(base, top_nodes, growth, cfg.block_k)
        )
        return BucketLadder(
            entries=entries, mean_row_nnz=stats.mean_row_nnz
        )

    def bucket_for(self, n_sub_nodes: int, n_ell_rows: int) -> Bucket:
        for b in self.entries:
            if b.nodes >= n_sub_nodes and b.rows >= n_ell_rows:
                return b
        raise ValueError(
            f"no bucket fits (nodes={n_sub_nodes}, rows={n_ell_rows}); "
            f"ladder top is {self.entries[-1]}"
        )


@dataclasses.dataclass
class PaddedRequest:
    """A subgraph padded to its bucket, ready to coalesce."""

    bucket: Bucket
    cols: np.ndarray      # (rows, tau) int32, PAD_COL padding
    vals: np.ndarray      # (rows, tau); f32, bf16 or int8 per precision
    row_map: np.ndarray   # (rows,) int32, -1 padding
    feats: np.ndarray     # (nodes, F) float32, permuted node order
    seed_pos: np.ndarray  # (max_seeds,) int32 output rows to read, -1 padding
    n_seeds: int
    # (rows / block_rows,) f32 per-row-block scales when vals are int8
    scales: Optional[np.ndarray] = None


class MicroBatcher:
    """Pads requests into buckets and runs coalesced forwards."""

    def __init__(
        self,
        cfg: GCNConfig,
        ladder: BucketLadder,
        *,
        max_batch: int = 8,
        max_seeds: int = 16,
        interpret: Optional[bool] = None,
        mesh=None,
        autoplan: bool = False,
        precision: str = "f32",
        feedback=None,
    ):
        self.cfg = cfg
        self.ladder = ladder
        self.max_batch = max_batch
        self.max_seeds = max_seeds
        self.interpret = interpret
        # Optional repro.obs.feedback.PlanFeedback store: when set and
        # ``autoplan`` is on, per-rung planning consults measured
        # execute-latency EWMAs before the modeled DeviceModel costs
        # (ROADMAP item 5's measured half).  Plan decisions stay pinned
        # by the per-rung caches below, so feedback arriving *after* a
        # rung warmed never triggers a recompile — it informs the next
        # engine build instead.
        self.feedback = feedback
        # Default storage precision for every rung; per-rung overrides
        # (the engine's accuracy-budgeted warmup choice) land in
        # _bucket_precisions via set_bucket_precision *before* warmup
        # compiles, so precision never causes a post-warmup recompile.
        self.precision = quant.validate_precision(precision)
        self._bucket_precisions: Dict[Bucket, str] = {}
        # The coalesced forward traces the SpMM on bare arrays, so the plan
        # resolves here, once: a pallas_sparse config records its degradation
        # to the masked dense grid (visible to callers/benchmarks as
        # ``batcher.plan.effective_impl`` / ``.degraded_reason``).  The mesh
        # is deliberately NOT put on the plan — bucket chunks shard at
        # request granularity through ``batch_spec`` constraints below, not
        # through the host-side row-split of ``exec.sharded``.
        self.plan = plan_for_config(cfg, interpret=interpret).resolve(
            schedulable=False
        )
        self.autoplan = autoplan
        self.mesh = mesh
        self.compiles = 0          # executables built (warmup or on-demand)
        self.calls = 0             # coalesced forward invocations
        self._executables: Dict[Tuple[Bucket, int], object] = {}
        self._bucket_plans: Dict[Tuple[Bucket, int], object] = {}
        self._layer_plans: Dict[Tuple[Bucket, int], list] = {}

    def set_bucket_precision(self, bucket: Bucket, precision: str) -> None:
        """Pin one rung's storage precision (call before warmup: the
        precision is baked into the rung's trace and executable key)."""
        self._bucket_precisions[bucket] = quant.validate_precision(precision)

    def precision_for_bucket(self, bucket: Bucket) -> str:
        return self._bucket_precisions.get(bucket, self.precision)

    def plan_for_bucket(self, bucket: Bucket, feature_dim: int):
        """The plan one ladder rung traces with.

        With ``autoplan`` off this is the single config-derived plan
        (historical behaviour).  With it on, each rung gets its own
        argmin-cost plan: the rung's padded shape plus the graph's mean
        sub-row nnz (carried on the ladder) form synthetic graph stats,
        and ``repro.plan.autoplan`` picks impl and block sizes for that
        shape.  ``pallas_sparse`` is excluded — the coalesced forward
        traces bare arrays, so it could never run here anyway — and no
        mesh candidates are offered (bucket chunks shard at request
        granularity, not through the host-side row split).
        """
        if not self.autoplan:
            return self.plan
        key = (bucket, feature_dim)
        plan = self._bucket_plans.get(key)
        if plan is None:
            from repro.plan import cost
            from repro.plan.autoplan import choose_plan

            stats = cost.synthetic_stats(
                rows=bucket.rows,
                n_out_rows=bucket.nodes,
                n_dense_rows=bucket.nodes,
                nnz=max(
                    int(bucket.rows
                        * (self.ladder.mean_row_nnz or self.cfg.tau / 2)), 1
                ),
                tau=self.cfg.tau,
            )
            feedback_key = None
            if self.feedback is not None:
                from repro.obs.feedback import bucket_key

                feedback_key = bucket_key(bucket, feature_dim)
            choice = choose_plan(
                stats,
                feature_dim,
                self.cfg,
                impls=("reference", "pallas"),
                interpret=self.interpret,
                schedulable=False,
                feedback=self.feedback,
                feedback_key=feedback_key,
            )
            plan = choice.plan.resolve(schedulable=False)
            self._bucket_plans[key] = plan
        return plan

    def layer_plans_for_bucket(self, bucket: Bucket, feature_dim: int):
        """One plan per layer for one rung's coalesced forward.

        With ``autoplan`` off every layer shares the single config-derived
        plan (historical behaviour).  With it on, the rung's synthetic
        stats go through the multi-layer pipeline planner
        (``repro.exec.pipeline``), which picks impl/blocks per layer —
        the hidden-width layers and the narrow output layer genuinely
        want different tiles.  Layouts stay replicated here: the
        coalesced forward traces bare arrays with no host-side row split;
        bucket chunks shard at request granularity instead.  Cached per
        (bucket, feature_dim), so the choice is made once and the
        zero-recompile-after-warmup invariant is untouched.
        """
        if not self.autoplan:
            return [self.plan] * self.cfg.n_layers
        key = (bucket, feature_dim)
        plans = self._layer_plans.get(key)
        if plans is None and self.feedback is not None:
            from repro.obs.feedback import bucket_key

            if self.feedback.has_bucket(bucket_key(bucket, feature_dim)):
                # Measured entries exist for this rung: serve every layer
                # with the feedback-informed single-plan choice.  A
                # measured EWMA prices the *whole* coalesced forward, so
                # within one bucket key the measured comparison is only
                # meaningful plan-vs-plan, not layer-vs-layer — the
                # pipeline DP's per-layer modeled costs would silently
                # override what was actually measured.
                plan = self.plan_for_bucket(bucket, feature_dim)
                plans = [plan] * self.cfg.n_layers
                self._layer_plans[key] = plans
                return plans
        if plans is None:
            from repro.exec.pipeline import plan_pipeline
            from repro.plan import cost

            stats = cost.synthetic_stats(
                rows=bucket.rows,
                n_out_rows=bucket.nodes,
                n_dense_rows=bucket.nodes,
                nnz=max(
                    int(bucket.rows
                        * (self.ladder.mean_row_nnz or self.cfg.tau / 2)), 1
                ),
                tau=self.cfg.tau,
            )
            pplan = plan_pipeline(
                self.cfg, stats, interpret=self.interpret
            )
            plans = [
                lp.spmm.resolve(schedulable=False) for lp in pplan.layers
            ]
            self._layer_plans[key] = plans
        return plans

    # ------------------------------------------------------------------
    # Request preparation
    # ------------------------------------------------------------------

    def batch_ladder(self) -> List[int]:
        sizes = [1]
        while sizes[-1] < self.max_batch:
            sizes.append(min(sizes[-1] * 2, self.max_batch))
        return sizes

    def pad_batch(self, n: int) -> int:
        for b in self.batch_ladder():
            if b >= n:
                return b
        raise ValueError(f"batch {n} exceeds max_batch {self.max_batch}")

    def prepare(self, sub: SampledSubgraph, features: np.ndarray) -> PaddedRequest:
        """Pad one extracted subgraph to its bucket.

        ``features`` are the subgraph's feature rows in *local* node order
        (i.e. ``global_features[sub.nodes]``).
        """
        if sub.seed_local.size > self.max_seeds:
            raise ValueError(
                f"{sub.seed_local.size} seeds > max_seeds {self.max_seeds}"
            )
        ell = sub.graph.pre.ell
        bucket = self.ladder.bucket_for(sub.n_sub_nodes, ell.padded_rows)
        tau = ell.tau
        cols = np.full((bucket.rows, tau), PAD_COL, dtype=np.int32)
        vals = np.zeros((bucket.rows, tau), dtype=np.float32)
        rmap = np.full((bucket.rows,), -1, dtype=np.int32)
        cols[: ell.padded_rows] = ell.cols
        vals[: ell.padded_rows] = ell.vals
        rmap[: ell.padded_rows] = ell.row_map
        feats = np.zeros((bucket.nodes, features.shape[1]), dtype=np.float32)
        feats[: sub.n_sub_nodes] = features[sub.graph.pre.perm]
        seed_pos = np.full((self.max_seeds,), -1, dtype=np.int32)
        seed_pos[: sub.seed_local.size] = sub.graph.inv[sub.seed_local]
        # Quantize host-side to the rung's storage precision: the padded
        # tail rows are zero, so extra all-zero scale blocks get scale 1.0
        # and dequantize to the same zeros.
        prec = self.precision_for_bucket(bucket)
        scales = None
        if prec == "int8":
            vals, scales = quant.quantize_values(vals, self.cfg.block_rows)
            scales = np.asarray(scales, dtype=np.float32)
        elif prec == "bf16":
            vals = vals.astype(jnp.bfloat16)
        return PaddedRequest(
            bucket=bucket,
            cols=cols,
            vals=vals,
            row_map=rmap,
            feats=feats,
            seed_pos=seed_pos,
            n_seeds=int(sub.seed_local.size),
            scales=scales,
        )

    # ------------------------------------------------------------------
    # Coalesced execution
    # ------------------------------------------------------------------

    def _make_forward(self, bucket: Bucket, feature_dim: int):
        cfg = self.cfg
        prec = self.precision_for_bucket(bucket)
        layer_plans = self.layer_plans_for_bucket(bucket, feature_dim)
        if prec != "f32":
            layer_plans = [
                dataclasses.replace(p, precision=prec) for p in layer_plans
            ]
        nodes_b = bucket.nodes
        mesh = self.mesh

        def fwd_impl(params, cols, vals, scales, row_map, feats, seed_pos):
            with jax.named_scope("gcn_bucket_step"):
                return step(params, cols, vals, scales, row_map, feats,
                            seed_pos)

        def step(params, cols, vals, scales, row_map, feats, seed_pos):
            b, rows_b, tau = cols.shape
            f_in = feats.shape[-1]
            if mesh is not None:
                # Shard the bucket chunk over the data axis at request
                # granularity: the block-diagonal coalesced operand
                # partitions cleanly on its leading (batch) dim, and
                # batch_spec degrades to replication when b is indivisible.
                from jax.sharding import NamedSharding

                from repro.dist.sharding import batch_spec

                sh = NamedSharding(mesh, batch_spec(mesh, b))
                cols, vals, row_map, feats, seed_pos = (
                    jax.lax.with_sharding_constraint(a, sh)
                    for a in (cols, vals, row_map, feats, seed_pos)
                )
                if scales is not None:
                    scales = jax.lax.with_sharding_constraint(scales, sh)
            # Block-diagonal coalescing: slot i's columns/output rows live in
            # [i * nodes_b, (i+1) * nodes_b), so one kernel call serves all.
            offs = jnp.arange(b, dtype=jnp.int32) * nodes_b
            cols_f = jnp.where(
                cols == PAD_COL, PAD_COL, cols + offs[:, None, None]
            ).reshape(b * rows_b, tau)
            vals_f = vals.reshape(b * rows_b, tau)
            rmap_f = jnp.where(row_map < 0, -1, row_map + offs[:, None]).reshape(
                b * rows_b
            )
            # Per-request scale blocks concatenate in row order: each slot's
            # rows are a multiple of block_rows, so the flattened scales
            # stay aligned to the coalesced operand's row blocks.
            scales_f = None if scales is None else scales.reshape(-1)
            qparams = (
                params if prec == "f32"
                else quant.quantize_params(params, prec, cfg.block_rows)
            )
            # Operands mirror what spmm_ell_arrays builds: the coalesced
            # block-diagonal ELL triple with the rung's stored precision.
            operands = SpmmOperands(
                cols=cols_f,
                vals=vals_f,
                row_map=rmap_f,
                n_out_rows=b * nodes_b,
                scales=scales_f,
                scale_block_rows=(
                    None if scales_f is None else cfg.block_rows),
                precision="int8" if scales_f is not None else "f32",
            )
            x = feats.reshape(b * nodes_b, f_in)
            for i in range(cfg.n_layers):
                x = execute_layer(
                    layer_plans[i], operands, x, qparams[f"layer_{i}"],
                    w_block_rows=cfg.block_rows,
                )
                if i < cfg.n_layers - 1:
                    x = jax.nn.relu(x)
            out = x.reshape(b, nodes_b, cfg.out_dim)
            safe = jnp.maximum(seed_pos, 0)
            return jnp.take_along_axis(out, safe[:, :, None], axis=1)

        if prec == "int8":
            return fwd_impl

        def fwd(params, cols, vals, row_map, feats, seed_pos):
            return fwd_impl(params, cols, vals, None, row_map, feats,
                            seed_pos)

        return fwd

    def _avals(self, params, bucket: Bucket, batch: int, feature_dim: int):
        tau = self.cfg.tau
        prec = self.precision_for_bucket(bucket)
        p_avals = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.asarray(x).dtype),
            params,
        )
        val_aval = jax.ShapeDtypeStruct(
            (batch, bucket.rows, tau), quant.storage_dtype(prec))
        scale_avals = ()
        if prec == "int8":
            n_qb = -(-bucket.rows // self.cfg.block_rows)
            scale_avals = (
                jax.ShapeDtypeStruct((batch, n_qb), jnp.float32),)
        return (
            p_avals,
            jax.ShapeDtypeStruct((batch, bucket.rows, tau), jnp.int32),
            val_aval,
            *scale_avals,
            jax.ShapeDtypeStruct((batch, bucket.rows), jnp.int32),
            jax.ShapeDtypeStruct((batch, bucket.nodes, feature_dim), jnp.float32),
            jax.ShapeDtypeStruct((batch, self.max_seeds), jnp.int32),
        )

    def executable(self, params, bucket: Bucket, batch: int, feature_dim: int):
        """AOT-compiled forward for one (bucket, batch, operand-signature)
        combo; builds and counts a compilation only on first sight."""
        p_sig = tuple(
            (tuple(jnp.shape(leaf)), str(jnp.result_type(leaf)))
            for leaf in jax.tree.leaves(params)
        )
        key = (bucket, batch, feature_dim,
               self.precision_for_bucket(bucket), p_sig)
        exe = self._executables.get(key)
        if exe is None:
            fwd = jax.jit(self._make_forward(bucket, feature_dim))
            exe = fwd.lower(*self._avals(params, bucket, batch, feature_dim)).compile()
            self.compiles += 1
            self._executables[key] = exe
        return exe

    def clear_executables(self) -> int:
        """Drop every AOT executable (fleet hot-unload reclaiming compile
        memory); returns how many were dropped.  ``compiles`` keeps
        counting monotonically, so re-warming after a reload is visible
        to the zero-recompile assertions rather than hidden by a reset."""
        dropped = len(self._executables)
        self._executables.clear()
        return dropped

    def warmup(
        self,
        params,
        feature_dim: int,
        *,
        max_nodes: Optional[int] = None,
        batch_sizes: Optional[List[int]] = None,
    ) -> int:
        """Pre-compile the (bucket × batch) grid; returns executables built.

        ``max_nodes`` skips buckets above a node budget (the full-graph rung
        of a huge graph at batch 8 is rarely a real serving shape).
        """
        built = 0
        for bucket in self.ladder.entries:
            if max_nodes is not None and bucket.nodes > max_nodes:
                continue
            for b in batch_sizes or self.batch_ladder():
                before = self.compiles
                self.executable(params, bucket, b, feature_dim)
                built += self.compiles - before
        return built

    def run(self, params, reqs: List[PaddedRequest]) -> List[np.ndarray]:
        """Run one coalesced forward; returns per-request seed logits."""
        if not reqs:
            return []
        bucket = reqs[0].bucket
        if any(r.bucket != bucket for r in reqs):
            raise ValueError("run() requires a single-bucket batch")
        batch = self.pad_batch(len(reqs))
        pad = batch - len(reqs)

        def stack(field: str, fill) -> np.ndarray:
            arrs = [getattr(r, field) for r in reqs]
            if pad:
                arrs.extend([np.full_like(arrs[0], fill)] * pad)
            return np.stack(arrs)

        with span("batcher.stack"):
            # int8 rungs carry a scales operand (padding slots get scale
            # 1.0: their vals are all-zero int8, so any scale dequantizes
            # to zero).
            scale_args = ()
            if self.precision_for_bucket(bucket) == "int8":
                scale_args = (stack("scales", 1.0),)
            args = (
                stack("cols", PAD_COL),
                stack("vals", 0),
                *scale_args,
                stack("row_map", -1),
                stack("feats", 0),
                stack("seed_pos", -1),
            )
        with span("batcher.dispatch"):
            # The executable call, with its host-to-device copies.
            exe = self.executable(params, bucket, batch,
                                  reqs[0].feats.shape[1])
            out = exe(params, *args)
        with span("batcher.fetch"):
            out = np.asarray(out)  # blocks until ready
        self.calls += 1
        return [out[i, : r.n_seeds] for i, r in enumerate(reqs)]
