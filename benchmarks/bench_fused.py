"""Fused vs unfused GCN layers on the skewed bench cell.

One synthetic power-law graph (the ``skewed`` cell the plan/quant benches
use: n=256, nnz=2000, alpha=2.5, tau=4, fdim=32) runs the 2-layer GCN
forward twice per precision — the classic two-launch path (combination
matmul, intermediate activation written to DRAM, aggregation SpMM reads
it back) and the fused single-launch path (``exec.fused``: the
combination tile feeds the ELL aggregation inside one Pallas grid, the
intermediate never leaves VMEM).  Per (precision, mode) the bench
reports:

* modeled DRAM traffic from the ledger (eager forward; unfused =
  ``spmm_dram + combination_dram``, fused = ``fused_dram``), plus the
  ledgered ``fused_writeback_saved`` bytes — the intermediate activation
  round trip the fusion eliminated;
* measured latency through the jitted forward (what serving runs);
* parity of the fused output with the unfused one at the same
  precision: bitwise at bf16/int8, within ``F32_REL_TOL`` of the
  output's largest magnitude at f32 (the two paths block the f32
  combination sum differently; see ``tests/test_fused.py``).

``--check`` gates the fusion claim: fused ledger DRAM < 0.8x unfused on
every case at f32, outputs at parity at every precision, and
every fused layer ledgered an explicit 0-byte activation writeback
record.  Writes the standard BENCH json to
``results/bench/fused_layers.json`` (``REPRO_BENCH_DIR`` to relocate).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os

BENCH_DIR = os.environ.get("REPRO_BENCH_DIR", "results/bench")
FUSED_DRAM_GATE = 0.8         # fused bytes must be < gate * unfused bytes
F32_REL_TOL = 1e-5            # f32 parity bound, fraction of max |output|

#              name       n    nnz   alpha  tau  fdim
SMOKE_CASES = [("skewed", 256, 2_000, 2.5, 4, 32)]
FULL_CASES = SMOKE_CASES + [("skewed-large", 512, 8_000, 2.5, 6, 64)]

PRECISIONS = ("f32", "bf16", "int8")


def _matches(a, b, precision: str) -> bool:
    import numpy as np

    if precision == "f32":
        return bool(np.allclose(a, b, rtol=0,
                                atol=F32_REL_TOL * np.abs(b).max()))
    return bool(np.array_equal(a, b))


def _bench_records(smoke: bool):
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.sparse_formats import random_power_law_csr
    from repro.dist.collectives import LEDGER
    from repro.exec import plan_for_config
    from repro.models.gcn import GCNConfig, GCNGraph, gcn_forward, init_params

    records = []
    for name, n, nnz, alpha, tau, fdim in (SMOKE_CASES if smoke
                                           else FULL_CASES):
        adj = random_power_law_csr(n, n, nnz, alpha=alpha, seed=0)
        cfg = GCNConfig(in_dim=fdim, hidden_dim=fdim, out_dim=fdim, tau=tau,
                        spmm_impl="pallas")
        graph = GCNGraph.build(adj, cfg)
        params = init_params(cfg, jax.random.PRNGKey(0))
        feats = jnp.asarray(
            np.random.default_rng(1).standard_normal((n, fdim)), jnp.float32)

        for precision in PRECISIONS:
            base = dataclasses.replace(
                plan_for_config(cfg), precision=precision)
            row = {"case": name, "precision": precision}
            outs = {}
            for fused in (False, True):
                plan = dataclasses.replace(base, fused=fused)
                LEDGER.reset()
                eager = np.asarray(
                    gcn_forward(params, graph, feats, cfg, plan=plan))
                if fused:
                    dram = LEDGER.total_bytes("fused_dram")
                    row["fused_writeback_saved"] = round(
                        LEDGER.total_bytes("fused_writeback_saved"))
                    # Every fused layer must ledger an explicit 0-byte
                    # activation writeback, not silently skip the record.
                    row["writeback_records"] = LEDGER.count("activation_dram")
                    row["writeback_bytes"] = LEDGER.total_bytes(
                        "activation_dram")
                else:
                    dram = LEDGER.total_bytes("spmm_dram", "combination_dram")
                assert dram > 0, "eager forward recorded no DRAM traffic"
                outs[fused] = eager

                fwd = jax.jit(lambda p, f, _pl=plan: gcn_forward(
                    p, graph, f, cfg, plan=_pl))
                out = np.asarray(fwd(params, feats))     # warm/compile
                assert _matches(out, eager, precision), \
                    "jitted forward diverged from eager"
                t0 = time.perf_counter()
                reps = 5
                for _ in range(reps):
                    jax.block_until_ready(fwd(params, feats))
                us = (time.perf_counter() - t0) / reps * 1e6
                mode = "fused" if fused else "unfused"
                row[f"{mode}_dram_bytes"] = round(dram)
                row[f"{mode}_time_us"] = round(us, 1)
            row["dram_ratio"] = round(
                row["fused_dram_bytes"] / row["unfused_dram_bytes"], 4)
            row["parity"] = _matches(outs[True], outs[False], precision)
            records.append(row)
    return records


def _gate(records) -> None:
    """Raise unless the fusion claims hold on every case."""
    problems = []
    for r in records:
        tag = f"{r['case']}/{r['precision']}"
        if not r["parity"]:
            problems.append(f"{tag}: fused output differs from unfused")
        if r["precision"] == "f32" and r["dram_ratio"] >= FUSED_DRAM_GATE:
            problems.append(
                f"{tag}: fused DRAM ratio {r['dram_ratio']:.3f} >= "
                f"{FUSED_DRAM_GATE}")
        if r["writeback_records"] < 1:
            problems.append(f"{tag}: fused layers ledgered no "
                            "activation_dram records")
        if r["writeback_bytes"] != 0.0:
            problems.append(f"{tag}: fused activation_dram bytes "
                            f"{r['writeback_bytes']} != 0")
        if r["fused_writeback_saved"] <= 0:
            problems.append(f"{tag}: no fused_writeback_saved bytes")
    if problems:
        raise SystemExit("fused bench gate failed: " + "; ".join(problems))


def run(csv=print, smoke: bool = True, check: bool = False,
        json_path: str | None = None) -> dict:
    csv("case,precision,unfused_dram,fused_dram,dram_ratio,"
        "unfused_us,fused_us,parity")
    records = _bench_records(smoke)
    for r in records:
        csv(f"{r['case']},{r['precision']},{r['unfused_dram_bytes']},"
            f"{r['fused_dram_bytes']},{r['dram_ratio']:.3f},"
            f"{r['unfused_time_us']:.0f},{r['fused_time_us']:.0f},"
            f"{int(r['parity'])}")
    payload = {"benchmark": "fused_layers", "smoke": smoke,
               "fused_dram_gate": FUSED_DRAM_GATE,
               "records": records}
    path = json_path or os.path.join(BENCH_DIR, "fused_layers.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    if check:
        _gate(records)
    return payload


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--check", action="store_true",
                    help="fail unless fused DRAM < "
                         f"{FUSED_DRAM_GATE}x unfused at f32 and fused "
                         "outputs are at parity at every precision")
    ap.add_argument("--json",
                    default=os.path.join(BENCH_DIR, "fused_layers.json"))
    args = ap.parse_args()
    run(smoke=args.smoke or not args.full, check=args.check,
        json_path=args.json)


if __name__ == "__main__":
    main()
