"""Autoplan vs static-default SpmmPlan across a synthetic sparsity sweep.

Each cell builds a power-law graph at a given skew (``alpha``), takes the
config's static default plan (the historical behaviour: config impl +
128-wide blocks, no mesh) and the cost model's pick
(``repro.plan.autoplan`` over block sizes x viable data-mesh widths for
the same impl), then measures both end to end through the one
``repro.exec.execute`` path.  The point of the sweep: on the skewed
scenario the static 128-wide ``block_f`` pads a narrow feature dim 4x,
and the cost model must both predict that (``cost_ok``: the chosen plan
is never costed worse than the static default — enforced) and cash it in
(``tput_ratio``: measured autoplan/static throughput — recorded).

Runs in a child process with 8 virtual CPU devices (same pattern as
``bench_spmm_sharded``) so mesh candidates are real; writes the standard
BENCH json to ``results/bench/plan_autoplan.json`` (``REPRO_BENCH_DIR``
to relocate).
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

#                 name       n    nnz   alpha  tau  fdim
SMOKE_CASES = [("uniform", 256, 2_000, 0.8, 4, 32),
               ("skewed", 256, 2_000, 2.5, 4, 32)]
FULL_CASES = SMOKE_CASES + [("skewed-large", 512, 8_000, 2.5, 6, 64)]


def _bench_records(smoke: bool):
    """Child-process body: runs with N virtual devices available."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import preprocess, random_power_law_csr
    from repro.exec import SpmmOperands, execute, plan_for_config
    from repro.models.gcn import GCNConfig
    from repro.plan.autoplan import choose_plan

    records = []
    for name, n, nnz, alpha, tau, fdim in (SMOKE_CASES if smoke
                                           else FULL_CASES):
        adj = random_power_law_csr(n, n, nnz, alpha=alpha, seed=0)
        res = preprocess(adj, tau=tau, tile_rows=16, pad_rows_to=128)
        dense = jnp.asarray(
            np.random.default_rng(1).standard_normal((n, fdim)), jnp.float32
        )
        operands = SpmmOperands.from_ell(res.ell)
        cfg = GCNConfig(in_dim=fdim, hidden_dim=fdim, out_dim=fdim,
                        tau=tau, spmm_impl="pallas")
        static = plan_for_config(cfg)
        choice = choose_plan(res.ell, fdim, cfg, impls=(cfg.spmm_impl,),
                             n_devices=jax.device_count())

        def timed(plan):
            out = np.asarray(execute(plan, operands, dense))  # warm/compile
            t0 = time.perf_counter()
            reps = 3
            for _ in range(reps):
                jax.block_until_ready(execute(plan, operands, dense))
            return out, (time.perf_counter() - t0) / reps * 1e6

        ref, static_us = timed(static)
        auto_out, auto_us = timed(choice.plan)
        err = float(np.abs(auto_out - ref).max())
        p = choice.plan
        records.append({
            "case": name,
            "alpha": alpha,
            "impl": cfg.spmm_impl,
            "auto_plan": {"block_rows": p.block_rows, "block_k": p.block_k,
                          "block_f": p.block_f, "n_shards": p.n_shards},
            "static_us": round(static_us, 1),
            "auto_us": round(auto_us, 1),
            "tput_ratio": round(static_us / max(auto_us, 1e-9), 3),
            "static_cost_s": choice.static_cost.seconds,
            "auto_cost_s": choice.cost.seconds,
            "cost_ok": bool(choice.cost.seconds
                            <= choice.static_cost.seconds),
            "max_abs_err_vs_static": err,
            "ok": bool(err < 1e-4),
        })
    return records


def _child_main(args) -> None:
    print_device_line()
    records = _bench_records(args.smoke)
    os.makedirs(os.path.dirname(args.json), exist_ok=True)
    with open(args.json, "w") as f:
        json.dump({"benchmark": "plan_autoplan",
                   "smoke": args.smoke,
                   "records": records}, f, indent=2)
    for r in records:
        a = r["auto_plan"]
        print(f"{r['case']},{r['impl']},"
              f"r{a['block_rows']}/k{a['block_k']}/f{a['block_f']}"
              f"x{a['n_shards']},{r['static_us']:.0f},{r['auto_us']:.0f},"
              f"{r['tput_ratio']:.2f},{int(r['cost_ok'])},{int(r['ok'])}")
    if not all(r["ok"] and r["cost_ok"] for r in records):
        raise SystemExit(
            "autoplan diverged from the static plan or was costed worse")


def run(csv=print, smoke: bool = True) -> dict:
    """Spawn the multi-device child and emit its CSV block."""
    csv("case,impl,auto_plan,static_us,auto_us,tput_ratio,cost_ok,ok")
    json_path = os.path.join(BENCH_DIR, "plan_autoplan.json")
    run_child(__file__, json_path, smoke, csv, "plan")
    with open(json_path) as f:
        return json.load(f)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true",
                    help="internal: run the bench body in this process")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--json",
                    default=os.path.join(BENCH_DIR, "plan_autoplan.json"))
    args = ap.parse_args()
    args.smoke = args.smoke or not args.full
    if args.child:
        _child_main(args)
    else:
        run(smoke=args.smoke)


if __name__ == "__main__":
    main()
