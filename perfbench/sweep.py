"""Find the knee of an open-loop cell: its traffic at several fixed rates.

    python3 perfbench/sweep.py --workload pubmed-query-steady \
        --rates 50,100,200,400 [--seconds 8] [--seed 1]

One process, one engine: for each rate, the cell's own warm-up traffic
and a window at that rate, as a benchmark run makes them.  Per rate it
prints the share of requests answered within the deadline, the tails,
and how late the generator sent (mean over the first and second half of
the window: lateness that grows means a backlog on the host).  The knee
is the highest rate at which at least 90% of requests meet the deadline
and the lateness does not grow; the cell's traffic file then states 0.8
of it as a number.  Needs the TPU the cell asks for.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(ROOT, "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    from bench import device, spec

    cell = spec.load_cell(ROOT, args.workload)
    if cell.traffic["kind"] != "open_loop":
        raise SystemExit("a sweep is for open-loop cells")
    device.configure_compile_cache(ROOT)
    device.require_tpu(cell.chips)
    import numpy as np

    from bench import drivers, runner

    engine, ds, _x, _w, _dims = runner.build(cell, args.seed)
    engine.warmup()
    counter = runner.CompileCounter()
    deadline_ms = cell.traffic["deadline_ms"]
    rows = []
    for rate in [float(r) for r in args.rates.split(",")]:
        cell.traffic = dict(cell.traffic, rate_per_s=rate)
        rng = np.random.default_rng(args.seed)
        got = runner.query_window(engine, cell, args.seconds, rng, ds,
                                  counter)
        recs = got["records"]
        met = sum(r.status == "ok" and r.latency_s * 1e3 <= deadline_ms
                  for r in recs)
        late = drivers.lateness_s(recs) * 1e3
        half = len(late) // 2
        ranked = drivers.rank_latencies(recs)
        row = {
            "rate": rate, "requests": len(recs),
            "attainment": met / max(len(recs), 1),
            "p50_ms": drivers.percentile(ranked, 50),
            "p95_ms": drivers.percentile(ranked, 95),
            "late_first_half_ms": float(late[:half].mean()) if half else 0.0,
            "late_second_half_ms": float(late[half:].mean()) if half else 0.0,
            "late_p99_ms": float(np.percentile(late, 99)) if len(late) else 0,
            "status": {s: sum(r.status == s for r in recs)
                       for s in ("ok", "refused", "shed", "failed")},
        }
        rows.append(row)
        print(f"[sweep] {json.dumps(row)}", flush=True)
    print(json.dumps({"workload": args.workload, "sweep": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
