"""Readings that set a cell's limits: the program and its control, in one
process, on many seeds, at the cell's own size and load.

    python3 perfbench/calibrate.py --workload NAME --seeds 1-12 \
        --control-seeds 1-3 [--seconds 3] [--control precision=int8]

For every seed it runs the cell as a benchmark run does (a short window
at the cell's own load; the check of what the timed path produced) and
prints each number ``correct`` compares.  The control is the same run
with the program's lower-precision path switched on.  The configuration
states f32 storage with bf16 products (the chip's default precision);
the program's own ``precision="int8"`` path is the next precision below
(its ``"bf16"`` storage path computes the same products, and on a v5e
the same logits).  The last line is a JSON summary: per number, the
largest program reading (the lower reading) and the smallest control
reading (the upper one).  Needs the TPU the cell asks for.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(ROOT, "src"))


def seed_list(text: str):
    out = []
    for part in text.split(","):
        if "-" in part:
            lo, hi = part.split("-")
            out.extend(range(int(lo), int(hi) + 1))
        elif part:
            out.append(int(part))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1-12")
    ap.add_argument("--control-seeds", default="1-3")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--control", default="precision=int8",
                    help="engine keyword=value the control switches on")
    args = ap.parse_args(argv)

    from bench import device, spec

    cell = spec.load_cell(ROOT, args.workload)
    device.configure_compile_cache(ROOT)
    devs = device.require_tpu(cell.chips)
    from bench import runner

    key, value = args.control.split("=", 1)
    readings = {"program": {}, "control": {}}
    for side, seeds, over in (
            ("program", seed_list(args.seeds), {}),
            ("control", seed_list(args.control_seeds), {key: value})):
        for seed in seeds:
            res = runner.run_cell(cell, seed, args.seconds, False,
                                  time.perf_counter(), devs=devs,
                                  overrides=over)
            nums = {k: c["value"] for k, c in res["checks"].items()}
            readings[side][seed] = nums
            print(f"[calibrate] {args.workload} {side} seed {seed}: "
                  f"{nums} attempted {res['attempted']} failed "
                  f"{res['failed']}", flush=True)
    names = sorted({k for r in readings["program"].values() for k in r})
    summary = {
        n: {"lower": max(r[n] for r in readings["program"].values()),
            "upper": min(r[n] for r in readings["control"].values()),
            "program": [r[n] for r in readings["program"].values()],
            "control": [r[n] for r in readings["control"].values()]}
        for n in names}
    print(json.dumps({"workload": args.workload, "control": args.control,
                      "readings": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
