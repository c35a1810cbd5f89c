"""Multi-layer pipeline benchmark: chained sharded activations vs
per-layer psum.

For each (graph, device-count) cell this harness runs the same 2-layer
GCN forward twice at identical impl/block sizes — once with the
pipelined layout chain (reduce-scatter between layers, all-gather after
the next combination matmul, one final all-reduce) and once with the
per-layer-psum baseline — and reads the measured collective and
activation-DRAM bytes off ``repro.dist.collectives.LEDGER``.  The cell
passes only if, on >= 2 devices, the chain performs exactly one full
all-reduce and moves strictly fewer collective *and* DRAM bytes than the
baseline, the outputs are bitwise identical, and the autoplanned
pipeline (``plan_pipeline``) is never costed worse than the static
per-layer default.

Like ``bench_spmm_sharded``, multi-device CPU execution needs
``xla_force_host_platform_device_count`` set before jax initializes, so
``run()`` re-executes this file in a child process.  The forwards run
eagerly (no jit around the stack): the ledger records at dispatch time,
and a traced run would log bytes once at trace time instead of per
execution.  Results land in the standard BENCH json format at
``results/bench/pipeline.json`` (``REPRO_BENCH_DIR`` to relocate).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.cpu_child import (  # noqa: E402
    print_device_line,
    run_child,
)

BENCH_DIR = os.environ.get("REPRO_BENCH_DIR", "results/bench")
DEVICE_COUNTS = (1, 2, 4)

# (n, nnz, tau, hidden, out) — hidden >> out: the canonical GCN funnel
# where chaining wins (the gather moves F_out-wide rows, not F_hidden).
SMOKE_CASES = [(256, 2_000, 4, 64, 8)]
FULL_CASES = SMOKE_CASES + [(512, 6_000, 6, 128, 16)]


def _bench_records(smoke: bool):
    """Child-process body: runs with N virtual devices available."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import random_power_law_csr
    from repro.dist.collectives import LEDGER
    from repro.exec import pipeline_forward, plan_pipeline, static_pipeline
    from repro.launch.mesh import make_data_mesh
    from repro.models.gcn import GCNConfig, GCNGraph, init_params

    def coll_bytes(snap):
        return sum(snap["bytes"].get(k, 0.0) for k in
                   ("psum", "reduce_scatter", "all_gather"))

    records = []
    for n, nnz, tau, hidden, out_dim in (SMOKE_CASES if smoke else FULL_CASES):
        adj = random_power_law_csr(n, n, nnz, seed=0)
        cfg = GCNConfig(in_dim=32, hidden_dim=hidden, out_dim=out_dim,
                        n_layers=2, tau=tau, spmm_impl="reference",
                        block_rows=16, block_k=16, block_f=16)
        graph = GCNGraph.build(adj, cfg)
        params = init_params(cfg, jax.random.PRNGKey(0))
        feats = jnp.asarray(
            np.random.default_rng(1).standard_normal((n, cfg.in_dim)),
            jnp.float32)
        for n_dev in DEVICE_COUNTS:
            if n_dev > jax.device_count():
                continue
            mesh = make_data_mesh(n_dev) if n_dev > 1 else None

            def timed(pplan):
                LEDGER.reset()
                t0 = time.perf_counter()
                res = np.asarray(pipeline_forward(params, graph, feats,
                                                  pplan))
                return res, time.perf_counter() - t0, LEDGER.snapshot()

            pipe_out, pipe_s, pipe = timed(
                static_pipeline(cfg, mesh, pipelined=True))
            base_out, base_s, base = timed(
                static_pipeline(cfg, mesh, pipelined=False))
            auto = plan_pipeline(cfg, graph.pre.ell, mesh=mesh)

            sharded = n_dev > 1
            full_all_reduces = pipe["counts"].get("psum", 0)
            ok = (
                np.array_equal(pipe_out, base_out)
                and auto.cost_seconds <= auto.static_cost_seconds + 1e-12
                and (not sharded or (
                    full_all_reduces == 1
                    and coll_bytes(pipe) < coll_bytes(base)
                    and pipe["bytes"]["activation_dram"]
                    < base["bytes"]["activation_dram"]
                ))
            )
            records.append({
                "case": f"n{n}_nnz{nnz}_h{hidden}_o{out_dim}",
                "n_devices": n_dev,
                "pipelined_us": round(pipe_s * 1e6, 1),
                "baseline_us": round(base_s * 1e6, 1),
                "full_all_reduces": int(full_all_reduces),
                "pipelined_coll_bytes": coll_bytes(pipe),
                "baseline_coll_bytes": coll_bytes(base),
                "pipelined_dram_bytes": pipe["bytes"].get(
                    "activation_dram", 0.0),
                "baseline_dram_bytes": base["bytes"].get(
                    "activation_dram", 0.0),
                "autoplan_cost_s": auto.cost_seconds,
                "static_cost_s": auto.static_cost_seconds,
                "bitwise_equal": bool(np.array_equal(pipe_out, base_out)),
                "ok": bool(ok),
            })
    return records


def _child_main(args) -> None:
    print_device_line()
    records = _bench_records(args.smoke)
    os.makedirs(os.path.dirname(args.json), exist_ok=True)
    with open(args.json, "w") as f:
        json.dump({"benchmark": "pipeline",
                   "smoke": args.smoke,
                   "records": records}, f, indent=2)
    for r in records:
        print(f"{r['case']},{r['n_devices']},{r['full_all_reduces']},"
              f"{r['pipelined_coll_bytes']:.0f},{r['baseline_coll_bytes']:.0f},"
              f"{r['pipelined_dram_bytes']:.0f},{r['baseline_dram_bytes']:.0f},"
              f"{int(r['bitwise_equal'])},{int(r['ok'])}")
    if not all(r["ok"] for r in records):
        raise SystemExit("pipeline chain lost to the per-layer-psum baseline")


def run(csv=print, smoke: bool = True) -> dict:
    """Spawn the multi-device child and emit its CSV block."""
    csv("case,n_devices,full_all_reduces,pipe_coll_bytes,base_coll_bytes,"
        "pipe_dram_bytes,base_dram_bytes,bitwise,ok")
    json_path = os.path.join(BENCH_DIR, "pipeline.json")
    run_child(__file__, json_path, smoke, csv, "pipeline")
    with open(json_path) as f:
        return json.load(f)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true",
                    help="internal: run the bench body in this process")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--json",
                    default=os.path.join(BENCH_DIR, "pipeline.json"))
    args = ap.parse_args()
    args.smoke = args.smoke or not args.full
    if args.child:
        _child_main(args)
    else:
        run(smoke=args.smoke)


if __name__ == "__main__":
    main()
