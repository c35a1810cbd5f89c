"""GCN serving launcher: full-graph, single-node, batched-query and
async-runtime scenarios on the FlexVector SpMM core.

Usage:
  PYTHONPATH=src python -m repro.launch.serve_gcn --dataset cora \
      --requests 64 --batch 8 --fanout 16
  PYTHONPATH=src python -m repro.launch.serve_gcn --dataset cora \
      --requests 32 --reduced          # CI smoke configuration
  PYTHONPATH=src python -m repro.launch.serve_gcn --dataset cora \
      --requests 64 --reduced --runtime-async --deadline-ms 200 --qps 100

The async and fleet scenarios exit non-zero when any admitted request
failed (load shed by admission or deadline is reported, not failed).
"""

import argparse
import time

import numpy as np

from repro.serve import ServeEngine


def build_engine(args, feedback=None, **overrides) -> ServeEngine:
    """The engine the CLI serves with; ``overrides`` replace any
    ``ServeEngine`` keyword the arguments set (or add one, such as
    ``interpret``)."""
    mesh = None
    if args.mesh > 1:
        from repro.launch.mesh import make_data_mesh

        mesh = make_data_mesh(args.mesh)
    growth = None
    if args.ladder_growth:
        growth = "auto" if args.ladder_growth == "auto" \
            else float(args.ladder_growth)
    kw = dict(
        hidden_dim=16 if args.reduced else args.hidden,
        spmm_impl=args.impl,
        fanout=args.fanout,
        max_batch=args.batch,
        max_seeds=max(args.seeds_per_request, 1),
        base_bucket_nodes=args.bucket_base,
        mesh=mesh,
        autoplan=args.autoplan,
        ladder_growth=growth,
        precision=args.precision,
        accuracy_budget=args.accuracy_budget,
        feedback=feedback,
    )
    kw.update(overrides)
    return ServeEngine.from_dataset(args.dataset, **kw)


def make_tracer(args):
    """One Tracer when any trace/metrics export is requested, else None —
    tracing off keeps the serving hot path exactly as before."""
    if not (args.trace_json or args.metrics_prom):
        return None
    from repro.obs import Tracer

    return Tracer()


def export_observability(args, tracer, metrics) -> None:
    """Write the requested trace/metrics artifacts after a run."""
    from repro.obs import write_metrics_json, write_prometheus, \
        write_traces_json

    if tracer is not None and args.trace_json:
        n = write_traces_json(args.trace_json, tracer.drain())
        print(f"[obs] {n} traces written to {args.trace_json}")
    if args.metrics_prom:
        write_prometheus(args.metrics_prom, metrics)
        print(f"[obs] prometheus metrics written to {args.metrics_prom}")
    if args.metrics_json:
        write_metrics_json(args.metrics_json, metrics)
        print(f"[metrics] snapshot written to {args.metrics_json}")


def device_note() -> str:
    """``platform/kind xcount`` of the devices JAX runs on."""
    import jax

    devs = jax.devices()
    return f"{devs[0].platform}/{devs[0].device_kind} x{len(devs)}"


def exit_on_failures(scenario: str, counters) -> None:
    """Exit non-zero when the runtime counted any failed request."""
    failed = counters.get("failed", 0)
    if failed:
        raise SystemExit(f"{scenario}: {failed} requests failed")


def run_async_scenario(engine: ServeEngine, requests, args) -> None:
    """Open-loop Poisson load through the deadline-aware runtime
    (``repro.runtime.loadgen`` — the same driver ``bench_queue.py``
    measures with), reporting the SLO picture from the metrics registry.
    """
    from repro.runtime import run_open_loop

    tracer = make_tracer(args)
    with engine.runtime(capacity=args.queue_capacity, tracer=tracer) as rt:
        wall = run_open_loop(
            rt,
            requests,
            qps=args.qps,
            deadline_s=args.deadline_ms / 1e3,
            rng=np.random.default_rng(1),
        )

    snap = rt.metrics.snapshot()
    c = snap["counters"]
    e2e = snap["latency_ms"]["e2e_s"]
    goodput = c["slo_met"] / max(wall, 1e-9)
    print(
        f"async: offered {c['submitted']} @ {args.qps:.0f} qps, "
        f"completed {c['completed']}, "
        f"shed {c['rejected_queue_full'] + c['rejected_infeasible'] + c['shed_expired']} "
        f"(rate {snap['derived']['shed_rate']:.3f}); "
        f"e2e p50 {e2e['p50']:.2f} ms p99 {e2e['p99']:.2f} ms; "
        f"SLO({args.deadline_ms:.0f}ms) attainment "
        f"{snap['derived']['slo_attainment']:.3f}, "
        f"goodput {goodput:.1f} req/s; batches "
        f"full={c['batches_full']} deadline={c['batches_deadline']}"
    )
    if engine.feedback is not None and args.plan_feedback:
        engine.feedback.save(args.plan_feedback)
        print(f"[obs] {len(engine.feedback)} measured plan latencies "
              f"saved to {args.plan_feedback}")
    export_observability(args, tracer, rt.metrics)
    exit_on_failures("async", c)


def run_fleet_scenario(args) -> None:
    """Multi-tenant fleet serving from a ``--fleet-config`` JSON file.

    The file follows :func:`repro.fleet.fleet_from_config`'s schema plus
    an optional ``loads`` section driving open-loop traffic::

        {"servables": [{"kind": "gcn", "key": "cora", "dataset": "cora",
                        "hidden_dim": 16, "fanout": 8},
                       {"kind": "lm", "key": "lm", "arch": "internlm2-1.8b"}],
         "capacity_units": 8.0,
         "tenants": [{"name": "hot", "qps": 50, "burst": 8,
                      "deadline_s": 0.2},
                     {"name": "cold", "priority": 1, "deadline_s": 0.2}],
         "weights": {"cora": 1.0, "lm": 1.0},
         "loads": [{"tenant": "hot", "servable": "cora", "qps": 80,
                    "requests": 64, "deadline_ms": 200},
                   {"tenant": "cold", "servable": "lm", "qps": 5,
                    "requests": 16, "deadline_ms": 200, "seq_len": 12}]}
    """
    import json

    from repro.fleet import (
        GcnServable,
        LmServable,
        TenantLoad,
        fleet_from_config,
        run_open_loop_mix,
    )
    from repro.runtime.metrics import labeled

    with open(args.fleet_config) as f:
        config = json.load(f)
    tracer = make_tracer(args)
    rt = fleet_from_config(config, tracer=tracer)
    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    for key in rt.manager.keys():
        rt.manager.resolve(key)   # load + warm before the clock starts
    print(f"[fleet] {rt.manager.loads} servables loaded in "
          f"{time.perf_counter() - t0:.1f}s: {rt.manager.keys()}")

    loads = []
    for spec in config.get("loads", []):
        sv = rt.manager.servable(spec["servable"])
        n = int(spec.get("requests", args.requests))
        if isinstance(sv, GcnServable):
            n_nodes = sv.engine.graph.n_nodes
            payloads = [
                rng.choice(n_nodes,
                           size=rng.integers(1, args.seeds_per_request + 1),
                           replace=False)
                for _ in range(n)
            ]
        elif isinstance(sv, LmServable):
            seq = int(spec.get("seq_len", 12))
            payloads = [rng.integers(0, sv.cfg.vocab, size=seq)
                        for _ in range(n)]
        else:
            raise ValueError(
                f"no payload generator for servable {spec['servable']!r}")
        loads.append(TenantLoad(
            tenant=spec["tenant"],
            servable=spec["servable"],
            payloads=payloads,
            qps=float(spec["qps"]),
            deadline_s=float(spec.get("deadline_ms", args.deadline_ms)) / 1e3,
        ))

    with rt:
        wall = run_open_loop_mix(rt, loads, rng=np.random.default_rng(1))

    snap = rt.metrics.snapshot()
    c = snap["counters"]
    print(
        f"fleet: offered {c['submitted']} over {wall:.2f}s, "
        f"completed {c['completed']}, shed rate "
        f"{snap['derived']['shed_rate']:.3f} "
        f"(quota={c['rejected_quota']} inflight={c['rejected_inflight']} "
        f"queue={c['rejected_queue_full']} expired={c['shed_expired']}); "
        f"SLO attainment {snap['derived']['slo_attainment']:.3f}"
    )
    for load in loads:
        t = load.tenant
        met = c.get(labeled("slo_met", tenant=t), 0)
        missed = c.get(labeled("slo_missed", tenant=t), 0)
        quota = c.get(labeled("rejected_quota", tenant=t), 0)
        e2e = snap["latency_ms"].get(labeled("e2e_s", tenant=t),
                                     {"p50": 0.0, "p99": 0.0})
        print(f"  tenant {t} -> {load.servable}: slo {met}/{met + missed} "
              f"met, quota-shed {quota}, e2e p50 {e2e['p50']:.2f} ms "
              f"p99 {e2e['p99']:.2f} ms")
    export_observability(args, tracer, rt.metrics)
    exit_on_failures("fleet", c)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="cora")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seeds-per-request", type=int, default=4)
    ap.add_argument("--fanout", type=int, default=16)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--bucket-base", type=int, default=256)
    ap.add_argument("--warmup-max-nodes", type=int, default=0,
                    help="skip warmup of bucket rungs above this node count; "
                         "0 = let the engine derive the reachable bound from "
                         "fanout/hops (uncapped fanout warms every rung)")
    ap.add_argument("--impl", default="pallas_sparse",
                    choices=["reference", "pallas", "pallas_sparse"],
                    help="SpMM impl; the Pallas kernels run compiled on a "
                         "TPU and in interpret mode elsewhere")
    ap.add_argument("--precision", default="f32",
                    choices=["f32", "bf16", "int8", "auto"],
                    help="serving numerics: f32 keeps the baseline "
                         "bit-identical; bf16/int8 quantize the ELL values "
                         "and weights (f32 accumulate); auto measures the "
                         "full-graph logit error per precision at warmup "
                         "and picks the cheapest one within "
                         "--accuracy-budget per bucket rung")
    ap.add_argument("--accuracy-budget", type=float, default=0.05,
                    help="max relative logit error a non-f32 precision may "
                         "introduce before --precision auto rejects it")
    ap.add_argument("--autoplan", action="store_true",
                    help="pick a per-bucket SpMM plan (impl + block sizes) "
                         "with the repro.plan cost model at warmup instead "
                         "of one config-derived default for every bucket")
    ap.add_argument("--mesh", type=int, default=1,
                    help="width of the data mesh axis to shard batched "
                         "query chunks over (1 = no mesh; needs that many "
                         "local/virtual devices, e.g. under "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    ap.add_argument("--scenario", default="all",
                    choices=["all", "full", "node", "batch"])
    ap.add_argument("--reduced", action="store_true",
                    help="small hidden dim (CI smoke configuration)")
    ap.add_argument("--ladder-growth", default=None,
                    help="bucket ladder growth factor (float), or 'auto' "
                         "for the cost-model search; default: 4, or auto "
                         "when --autoplan is set")
    ap.add_argument("--runtime-async", action="store_true",
                    help="drive the batched scenario through the async "
                         "deadline-aware repro.runtime worker loop "
                         "(open-loop Poisson arrivals) instead of the "
                         "synchronous query_batch facade")
    ap.add_argument("--deadline-ms", type=float, default=200.0,
                    help="per-request SLO for --runtime-async (absolute "
                         "deadline = arrival + this)")
    ap.add_argument("--qps", type=float, default=100.0,
                    help="offered load for --runtime-async (Poisson "
                         "arrival rate, requests/s)")
    ap.add_argument("--queue-capacity", type=int, default=256,
                    help="bounded queue size for --runtime-async "
                         "(admission sheds beyond it)")
    ap.add_argument("--metrics-json", default=None,
                    help="write the runtime metrics snapshot to this path "
                         "after --runtime-async")
    ap.add_argument("--trace-json", default=None,
                    help="turn on repro.obs request tracing and write the "
                         "drained traces (JSON) to this path after the "
                         "async/fleet run")
    ap.add_argument("--metrics-prom", default=None,
                    help="write the metrics snapshot in Prometheus text "
                         "exposition format to this path after the "
                         "async/fleet run")
    ap.add_argument("--plan-feedback", default=None,
                    help="path of a repro.obs PlanFeedback store: loaded "
                         "before warmup (measured latencies steer autoplan) "
                         "and re-saved with this run's measurements after "
                         "--runtime-async")
    ap.add_argument("--fleet-config", default=None,
                    help="JSON file describing a multi-tenant servable "
                         "fleet (servables + tenant policies + loads); "
                         "runs the fleet scenario instead of the "
                         "single-engine ones")
    return ap


def main() -> None:
    from repro.serve.cache import enable_compile_cache

    args = build_parser().parse_args()
    enable_compile_cache()

    if args.fleet_config:
        run_fleet_scenario(args)
        return

    feedback = None
    if args.plan_feedback:
        from repro.obs import PlanFeedback

        feedback = PlanFeedback.load(args.plan_feedback)
        print(f"[obs] plan feedback loaded from {args.plan_feedback}: "
              f"{len(feedback)} measured (bucket, plan) entries")
    engine = build_engine(args, feedback=feedback)
    t0 = time.perf_counter()
    built = engine.warmup(max_nodes=args.warmup_max_nodes or None)
    reg = engine.registry.stats
    plan = engine.batcher.plan
    impl_note = plan.effective_impl + (
        f" (degraded from {plan.impl})" if plan.degraded else "")
    full_impl = (engine.full_plan.effective_impl if engine.full_plan
                 else "autoplanned")
    print(f"[warmup] {built} bucket executables compiled in "
          f"{time.perf_counter() - t0:.1f}s; ladder "
          f"{[ (b.nodes, b.rows) for b in engine.batcher.ladder.entries ]}; "
          f"impl full-graph {full_impl}, buckets {impl_note}; "
          f"mesh data={args.mesh}; "
          f"device {device_note()}; "
          f"registry builds={reg.builds} disk_hits={reg.disk_hits}")
    if args.precision != "f32":
        errs = {p: round(e, 5)
                for p, e in sorted(engine.precision_errors.items())}
        picks = {b.rows: engine.batcher.precision_for_bucket(b)
                 for b in engine.batcher.ladder.entries}
        print(f"[precision] requested {args.precision} "
              f"(budget {args.accuracy_budget}); measured errors {errs}; "
              f"per-rung picks {picks}; "
              f"full-graph {engine.resolved_precision}")
    if args.autoplan:
        for (bucket, _), bplan in sorted(
                engine.batcher._bucket_plans.items()):
            print(f"[autoplan] bucket ({bucket.nodes}, {bucket.rows}): "
                  f"{bplan.effective_impl} rows={bplan.block_rows} "
                  f"k={bplan.block_k} f={bplan.block_f}")
        # per-layer plans from the pipeline planner (the ones the
        # coalesced forwards actually trace with)
        for (bucket, _), layer_plans in sorted(
                engine.batcher._layer_plans.items()):
            chain = " -> ".join(
                f"L{i}:{p.effective_impl}/{p.block_rows}x{p.block_k}"
                f"x{p.block_f}" for i, p in enumerate(layer_plans))
            print(f"[autoplan] bucket ({bucket.nodes}, {bucket.rows}) "
                  f"layers: {chain}")

    rng = np.random.default_rng(0)
    n_nodes = engine.graph.n_nodes
    requests = [
        rng.choice(n_nodes, size=rng.integers(1, args.seeds_per_request + 1),
                   replace=False)
        for _ in range(args.requests)
    ]

    if args.scenario in ("all", "full"):
        for _ in range(3):
            engine.full_forward()
        print(engine.report("full").line())

    if args.scenario in ("all", "node"):
        t0 = time.perf_counter()
        for seeds in requests:
            engine.query(seeds)
        print(engine.report("query", wall_s=time.perf_counter() - t0).line())

    if args.scenario in ("all", "batch"):
        if args.runtime_async:
            run_async_scenario(engine, requests, args)
        else:
            t0 = time.perf_counter()
            engine.query_batch(requests)
            print(engine.report(
                "batch", wall_s=time.perf_counter() - t0).line())

    print(f"[post-warmup compiles] {engine.compile_count - built} "
          f"(warmup built {built}); batcher calls {engine.batcher.calls}; "
          f"registry mem_hits={reg.mem_hits} builds={reg.builds}")


if __name__ == "__main__":
    main()
