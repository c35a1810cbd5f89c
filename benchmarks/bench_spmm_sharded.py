"""Sharded SpMM benchmark: 1 vs N virtual devices over the `data` axis.

Multi-device CPU execution needs ``xla_force_host_platform_device_count``
set *before* jax initializes, so ``run()`` re-executes this file in a
child process with the flag injected (the other harnesses in ``run.py``
have already initialized the parent's 1-device jax by then).  The child
runs every impl x device-count cell through the one
``repro.exec.execute`` path — single-device and sharded are the same
code — checks parity against the single-device reference, prints the
usual CSV block, and writes the records in the standard BENCH json format
(one record per cell, like ``launch.dryrun``'s result cells) to
``results/bench/spmm_sharded.json`` (``REPRO_BENCH_DIR`` to relocate).

Smoke mode (CI) keeps one small case; ``--full`` adds the larger ones.
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
IMPLS = ("reference", "pallas", "pallas_sparse")
DEVICE_COUNTS = (1, 2, 4)

SMOKE_CASES = [(256, 2_000, 4, 32)]                    # (n, nnz, tau, fdim)
FULL_CASES = SMOKE_CASES + [(512, 6_000, 6, 64)]


def _bench_records(smoke: bool):
    """Child-process body: runs with N virtual devices available."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import preprocess, random_power_law_csr, spmm_ell
    from repro.exec import SpmmPlan, SpmmOperands, execute
    from repro.launch.mesh import make_data_mesh

    records = []
    for n, nnz, tau, fdim in (SMOKE_CASES if smoke else FULL_CASES):
        adj = random_power_law_csr(n, n, nnz, seed=0)
        res = preprocess(adj, tau=tau, tile_rows=16, pad_rows_to=64)
        dense = jnp.asarray(
            np.random.default_rng(1).standard_normal((n, fdim)), jnp.float32
        )
        ref = np.asarray(spmm_ell(res.ell, dense, impl="reference"))
        operands = SpmmOperands.from_ell(res.ell)
        for impl in IMPLS:
            for n_dev in DEVICE_COUNTS:
                if n_dev > jax.device_count():
                    continue
                mesh = make_data_mesh(n_dev) if n_dev > 1 else None
                plan = SpmmPlan(
                    impl=impl, block_rows=64, block_k=64, block_f=64,
                    mesh=mesh,
                )

                def step():
                    return execute(plan, operands, dense)

                out = np.asarray(step())  # warm/compile
                # Each rep is blocked individually and, on sharded cells,
                # includes the host-side shard split + schedule planning +
                # retrace: the reported figure is end-to-end dispatch
                # latency, not bare kernel time (the honest unit on this
                # interpret-mode CPU harness; parity is the primary metric).
                t0 = time.perf_counter()
                reps = 3
                for _ in range(reps):
                    jax.block_until_ready(step())
                us = (time.perf_counter() - t0) / reps * 1e6
                err = float(np.abs(out - ref).max())
                records.append({
                    "case": f"n{n}_nnz{nnz}",
                    "impl": impl,
                    "n_devices": n_dev,
                    "us": round(us, 1),
                    "max_abs_err_vs_reference": err,
                    "ok": bool(err < 1e-4),
                })
    return records


def _child_main(args) -> None:
    print_device_line()
    records = _bench_records(args.smoke)
    os.makedirs(os.path.dirname(args.json), exist_ok=True)
    with open(args.json, "w") as f:
        json.dump({"benchmark": "spmm_sharded",
                   "smoke": args.smoke,
                   "records": records}, f, indent=2)
    for r in records:
        print(f"{r['case']},{r['impl']},{r['n_devices']},{r['us']:.0f},"
              f"{r['max_abs_err_vs_reference']:.2e},{int(r['ok'])}")
    if not all(r["ok"] for r in records):
        raise SystemExit("sharded output diverged from the reference")


def run(csv=print, smoke: bool = True) -> dict:
    """Spawn the multi-device child and emit its CSV block."""
    csv("case,impl,n_devices,us,max_abs_err_vs_reference,ok")
    json_path = os.path.join(BENCH_DIR, "spmm_sharded.json")
    run_child(__file__, json_path, smoke, csv, "sharded")
    with open(json_path) as f:
        return json.load(f)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true",
                    help="internal: run the bench body in this process")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--json",
                    default=os.path.join(BENCH_DIR, "spmm_sharded.json"))
    args = ap.parse_args()
    args.smoke = args.smoke or not args.full
    if args.child:
        _child_main(args)
    else:
        run(smoke=args.smoke)


if __name__ == "__main__":
    main()
