"""The GCN serving engine: registry + sampler + micro-batcher, end to end.

Three request scenarios, all on the FlexVector SpMM core:

* ``full_forward``  — one full-graph forward (embeddings for every node),
  through the registry's jitted full-graph step;
* ``query``         — logits for a handful of seed nodes via k-hop
  fanout-capped extraction (bounded latency, independent of graph size);
* ``query_batch``   — many concurrent seed queries, grouped by shape
  bucket and coalesced into one kernel call per bucket chunk.

Every path records wall-clock latency per request; ``latency_report``
summarizes p50/p99 and throughput (requests/s plus "tok-equivalent"
seed-logits/s — one answered seed node is the serving unit of work, the
analogue of one decoded token in `repro.launch.serve`).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np

from repro.core.sparse_formats import CSRMatrix
from repro.exec import plan_for_config
from repro.models.gcn import GCNConfig, init_params
from repro.obs.trace import span
from repro.serve.batcher import BucketLadder, MicroBatcher, PaddedRequest
from repro.serve.registry import ArtifactRegistry
from repro.serve.sampler import SubgraphSampler


@dataclasses.dataclass
class LatencyReport:
    scenario: str
    n_requests: int
    p50_ms: float
    p99_ms: float
    req_per_s: float
    tok_per_s: float          # answered seed logits per second

    def line(self) -> str:
        return (
            f"{self.scenario}: {self.n_requests} requests, "
            f"p50 {self.p50_ms:.2f} ms, p99 {self.p99_ms:.2f} ms, "
            f"{self.req_per_s:.1f} req/s, {self.tok_per_s:.1f} tok-equiv/s"
        )


def latency_report(
    scenario: str, latencies_s: Sequence[float], total_seeds: int,
    wall_s: Optional[float] = None,
) -> LatencyReport:
    if len(latencies_s) == 0:
        return LatencyReport(scenario, 0, 0.0, 0.0, 0.0, 0.0)
    lat_ms = np.asarray(latencies_s, dtype=np.float64) * 1e3
    wall = wall_s if wall_s is not None else float(np.sum(lat_ms) / 1e3)
    wall = max(wall, 1e-9)
    return LatencyReport(
        scenario=scenario,
        n_requests=len(lat_ms),
        p50_ms=float(np.percentile(lat_ms, 50)),
        p99_ms=float(np.percentile(lat_ms, 99)),
        req_per_s=len(lat_ms) / wall,
        tok_per_s=total_seeds / wall,
    )


class ServeEngine:
    """Batched GCN inference over one graph."""

    def __init__(
        self,
        adj_norm: CSRMatrix,
        features: np.ndarray,
        cfg: GCNConfig,
        *,
        params=None,
        registry: Optional[ArtifactRegistry] = None,
        ladder: Optional[BucketLadder] = None,
        hops: Optional[int] = None,
        fanout: Optional[int] = 32,
        max_batch: int = 8,
        max_seeds: int = 16,
        base_bucket_nodes: int = 256,
        sampler_seed: int = 0,
        interpret: Optional[bool] = None,
        mesh=None,
        autoplan: bool = False,
        ladder_growth=None,
        precision: str = "f32",
        accuracy_budget: float = 0.05,
        feedback=None,
    ):
        from repro.exec import quant

        self.cfg = cfg
        self.adj_norm = adj_norm
        self.features = np.asarray(features, dtype=np.float32)
        self.registry = registry or ArtifactRegistry()
        self.params = (
            params if params is not None else init_params(cfg, jax.random.PRNGKey(0))
        )
        # ``precision`` is a fixed storage precision (exec.quant semantics)
        # or "auto": measure each precision's full-graph logit error at
        # warmup and let the cost model pick per rung under
        # ``accuracy_budget``.  Until warmup resolves it, auto serves f32.
        if precision != "auto":
            quant.validate_precision(precision)
        self.precision = precision
        self.accuracy_budget = float(accuracy_budget)
        self.precision_errors: Dict[str, float] = {"f32": 0.0}
        self._static_precision = "f32" if precision == "auto" else precision
        # Full-graph artifact: preprocessed once per content key, persisted.
        # With autoplanning on, the full-graph step routes through the
        # multi-layer pipeline planner (per-layer impl/blocks + activation
        # layouts chosen jointly); the static config plan otherwise.
        self.graph = self.registry.get_or_build(adj_norm, cfg, persist=True)
        # The full graph's operand is a host TiledELL, so its static plan
        # resolves schedulable: pallas_sparse keeps its block-skipping
        # grid here (served buckets degrade, see ``batcher.plan``).
        self.interpret = interpret
        self.full_plan = None if autoplan else plan_for_config(
            cfg, interpret=interpret).resolve(schedulable=True)
        self._plan_arg = "auto" if autoplan else self.full_plan
        self._full_step = self._forward_step(self._static_precision)
        self.sampler = SubgraphSampler(
            adj_norm,
            cfg,
            hops=hops,
            fanout=fanout,
            seed=sampler_seed,
            registry=self.registry,
        )
        # With autoplanning on, the ladder's growth factor is a plan
        # decision too (cost-model search over candidate factors) unless
        # the caller pinned one; the historical geometric default holds
        # otherwise.
        if ladder_growth is None:
            ladder_growth = "auto" if autoplan else 4
        self.batcher = MicroBatcher(
            cfg,
            ladder
            or BucketLadder.for_graph(self.graph, cfg,
                                      base_nodes=base_bucket_nodes,
                                      growth=ladder_growth),
            max_batch=max_batch,
            max_seeds=max_seeds,
            interpret=interpret,
            mesh=mesh,
            autoplan=autoplan,
            precision=self._static_precision,
            feedback=feedback,
        )
        # repro.obs.feedback.PlanFeedback (or None): measured per-rung
        # execute latency consulted by autoplan warmup (through the
        # batcher above) and recorded into by runtimes built from
        # :meth:`runtime`.
        self.feedback = feedback
        self.timings: Dict[str, List[float]] = {}
        self.seeds_served: Dict[str, int] = {}
        self.wall: Dict[str, float] = {}
        self._graph_key = None

    # ------------------------------------------------------------------

    @staticmethod
    def from_dataset(
        name: str,
        cfg: Optional[GCNConfig] = None,
        hidden_dim: int = 64,
        spmm_impl: str = "reference",
        **kw,
    ) -> "ServeEngine":
        """Build an engine for a named dataset; in/out dims come from the
        dataset spec, ``hidden_dim``/``spmm_impl`` from the caller (or pass
        a full ``cfg`` to control everything)."""
        from repro.graphs import load_dataset

        ds = load_dataset(name)
        if cfg is None:
            cfg = GCNConfig(
                in_dim=ds.spec.feature_dim,
                hidden_dim=hidden_dim,
                out_dim=ds.spec.classes,
                spmm_impl=spmm_impl,
            )
        return ServeEngine(ds.adj_norm, ds.features, cfg, **kw)

    # ------------------------------------------------------------------

    def warmup(
        self,
        *,
        max_nodes: Optional[int] = None,
        batch_sizes: Optional[List[int]] = None,
    ) -> int:
        """Compile the full-graph step plus the (bucket × batch) ladder.

        After this returns, any query whose subgraph fits a compiled bucket
        runs with zero new compilations (``compile_count`` is the proof).

        With ``max_nodes`` unset and a fanout cap active, warmup derives
        the reachable rungs from the sampler's bounds instead of compiling
        the whole ladder: at most max_seeds · Σ fanout^i (i ≤ hops) nodes
        can enter a receptive field, and — because the induced subgraph
        keeps every edge among selected nodes — the ELL-row bound is taken
        from the sum over the N globally highest-degree nodes of the
        per-row vertex-cut worst case (≤ 2·ceil(deg/tau) sub-rows).  Every
        rung up to the first satisfying *both* bounds is warmed, so bucket
        escalation on hub-dense subgraphs cannot leave the compiled set —
        the full-graph rung of a big graph is skipped as unreachable.
        Uncapped fanout warms every rung.

        With ``precision="auto"`` this is also where precision resolves:
        each candidate's full-graph logit error is measured against the
        f32 reference (``precision_errors``), then every ladder rung gets
        the cheapest precision whose measured error fits
        ``accuracy_budget`` — pinned on the batcher *before* its
        executables compile, so serving at the chosen precisions never
        recompiles.
        """
        if self.precision == "auto":
            self._resolve_auto_precision()
        if max_nodes is None and self.sampler.fanout is not None:
            f, h = self.sampler.fanout, self.sampler.hops
            bound_nodes = min(
                self.batcher.max_seeds * sum(f**i for i in range(h + 1)),
                self.graph.n_nodes,
            )
            per_node = np.sort(-(-self.adj_norm.row_nnz() // self.cfg.tau))[::-1]
            br = self.cfg.block_rows
            bound_rows = -(-int(2 * per_node[:bound_nodes].sum()) // br) * br
            for b in self.batcher.ladder.entries:
                max_nodes = b.nodes
                if b.nodes >= bound_nodes and b.rows >= bound_rows:
                    break
        built = self.batcher.warmup(
            self.params,
            self.features.shape[1],
            max_nodes=max_nodes,
            batch_sizes=batch_sizes,
        )
        np.asarray(self._full_step(self.params, self.features))  # compile + run
        return built

    @property
    def compile_count(self) -> int:
        """Bucketed-path executables built so far (the recompile monitor)."""
        return self.batcher.compiles

    @property
    def resolved_precision(self) -> str:
        """Precision the full-graph step actually runs at — the
        configured one, or the auto-resolved pick after ``warmup()``."""
        return self._static_precision

    def _resolve_auto_precision(self) -> None:
        """Measure per-precision logit error and pin a precision per rung.

        The measurement is the real thing, not a proxy: one full-graph
        forward per candidate precision through the registry's jitted
        steps, scored with :func:`repro.exec.quant.logit_error` against
        the f32 reference.  Rung selection then reuses the bucket-cost
        arithmetic (``plan.cost.bucket_forward_seconds``) with the
        precision whose error exceeds the budget excluded — f32 is always
        admissible, so resolution cannot fail.  Idempotent: errors are
        measured once and re-running only re-pins the same choices.
        """
        from repro.exec import quant
        from repro.plan import cost

        if len(self.precision_errors) <= 1:
            ref = np.asarray(self._full_step(self.params, self.features))
            for p in ("bf16", "int8"):
                step = self._forward_step(p)
                out = np.asarray(step(self.params, self.features))
                self.precision_errors[p] = quant.logit_error(ref, out)
        admissible = tuple(
            p for p in quant.PRECISIONS
            if self.precision_errors.get(p, float("inf"))
            <= self.accuracy_budget or p == "f32"
        )
        cfg = self.cfg
        f_dims = [cfg.hidden_dim] * (cfg.n_layers - 1) + [cfg.out_dim]
        mean_nnz = self.batcher.ladder.mean_row_nnz or cfg.tau / 2
        for b in self.batcher.ladder.entries:
            best_p, best_s = "f32", None
            for p in admissible:
                s = cost.bucket_forward_seconds(
                    rows=b.rows, n_out_rows=b.nodes, mean_row_nnz=mean_nnz,
                    tau=cfg.tau, f_dims=f_dims, impl=cfg.spmm_impl,
                    block_rows=cfg.block_rows, block_k=cfg.block_k,
                    block_f=cfg.block_f, precision=p,
                )
                if best_s is None or s < best_s:
                    best_p, best_s = p, s
            self.batcher.set_bucket_precision(b, best_p)
        # Full-graph serving swaps to the cheapest admissible precision
        # too; its step was already compiled during measurement, so the
        # swap costs nothing.
        full = admissible[-1] if len(admissible) > 1 else "f32"
        if full != self._static_precision:
            self._full_step = self._forward_step(full)
            self._static_precision = full

    def _forward_step(self, precision: str):
        """The registry's jitted full-graph step at ``precision``."""
        return self.registry.forward_step(
            self.adj_norm, self.cfg, plan=self._plan_arg,
            precision=precision, interpret=self.interpret)

    # ------------------------------------------------------------------
    # Scenarios
    # ------------------------------------------------------------------

    def full_forward(self) -> np.ndarray:
        """Full-graph logits for every node (original node order)."""
        with span("engine.full_forward") as s:
            s.set(residency=self._full_step.residency,
                  visits=self._full_step.visits)
            t0 = time.perf_counter()
            with span("engine.dispatch"):
                # The call into the jitted step; the numpy features are
                # copied to the device inside it.
                out = self._full_step(self.params, self.features)
            with span("engine.fetch"):
                out = np.asarray(out)  # blocks until the logits are home
            self._record("full", [time.perf_counter() - t0],
                         self.graph.n_nodes)
        return out

    def query(self, seeds: Sequence[int]) -> np.ndarray:
        """Logits for ``seeds`` via sampled-subgraph inference."""
        t0 = time.perf_counter()
        req = self._prepare(seeds)
        out = self.batcher.run(self.params, [req])[0]
        self._record("query", [time.perf_counter() - t0], len(out))
        return out

    def query_batch(self, requests: Sequence[Sequence[int]]) -> List[np.ndarray]:
        """Answer many seed queries, coalescing per shape bucket.

        A thin synchronous facade over the ``repro.runtime`` machinery:
        every query is submitted (best effort, no deadline) into the
        runtime's queue and the scheduler is drained on the calling
        thread.  With equal priorities and no deadlines the scheduler's
        EDF order degrades to arrival order and its full/flush chunking
        reproduces the historical eager grouping exactly, so results are
        bit-identical to the pre-runtime implementation.

        Per-request latency spans its own extraction plus the coalesced
        forward it rode in (requests in one chunk share that cost), so the
        latency sum over-counts shared time; throughput uses the actual
        wall clock of the whole call.
        """
        t_call = time.perf_counter()
        rt = self._sync_runtime()
        reqs = [rt.submit(seeds) for seeds in requests]
        rt.drain()
        outputs = [r.future.result() for r in reqs]
        lats = [r.prep_s + (r.exec_s or 0.0) for r in reqs]
        n_seeds = sum(len(o) for o in outputs)
        self._record("batch", lats, n_seeds, wall=time.perf_counter() - t_call)
        return outputs

    def runtime(self, **kw) -> "ServeRuntime":
        """A fresh async runtime over this (ideally warmed) engine; see
        :class:`repro.runtime.ServeRuntime` for the knobs.  An engine
        built with a ``feedback`` store hands it to every runtime (so
        serving keeps feeding the EWMAs warmup consulted) unless the
        caller overrides it here."""
        from repro.runtime import ServeRuntime

        kw.setdefault("feedback", self.feedback)
        return ServeRuntime(self, **kw)

    def servable(self, key: Optional[str] = None, **kw) -> "GcnServable":
        """Wrap this engine as a fleet servable (``repro.fleet``); ``key``
        defaults to the graph's content hash, so two engines over the same
        preprocessed graph collide deliberately."""
        from repro.fleet.servable import GcnServable

        return GcnServable(self, key=key, **kw)

    @property
    def graph_key(self) -> str:
        """Content hash identifying this engine's graph (cached)."""
        if self._graph_key is None:
            from repro.serve.registry import graph_key

            self._graph_key = graph_key(self.adj_norm, self.cfg)
        return self._graph_key

    def _sync_runtime(self) -> "ServeRuntime":
        """The facade's runtime: unbounded (a synchronous batch must never
        shed), never threaded (drained inline per call), and built fresh
        per call so its raw-sample metrics registry stays bounded by one
        batch instead of growing for the engine's lifetime.  The graph
        content hash is computed once per engine and reused."""
        return self.runtime(capacity=None, graph_key=self.graph_key)

    # ------------------------------------------------------------------

    def _prepare(self, seeds: Sequence[int]) -> PaddedRequest:
        sub = self.sampler.extract(seeds)
        with span("batcher.pad"):
            return self.batcher.prepare(sub, self.features[sub.nodes])

    def _record(
        self, scenario: str, lats: List[float], seeds: int,
        wall: Optional[float] = None,
    ) -> None:
        self.timings.setdefault(scenario, []).extend(lats)
        self.seeds_served[scenario] = self.seeds_served.get(scenario, 0) + seeds
        # Coalesced calls pass true elapsed time; per-request scenarios'
        # wall is the latency sum (requests ran back to back).
        self.wall[scenario] = self.wall.get(scenario, 0.0) + (
            wall if wall is not None else float(np.sum(lats))
        )

    def report(self, scenario: str, wall_s: Optional[float] = None) -> LatencyReport:
        """Latency/throughput summary; ``wall_s`` overrides the recorded
        per-call wall time (e.g. to include inter-request think time)."""
        return latency_report(
            scenario,
            self.timings.get(scenario, []),
            self.seeds_served.get(scenario, 0),
            wall_s=wall_s if wall_s is not None else self.wall.get(scenario),
        )

    def reset_timings(self) -> None:
        self.timings.clear()
        self.seeds_served.clear()
        self.wall.clear()
