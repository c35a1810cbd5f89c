"""Run one benchmark cell once on the TPU it is started on.

    python3 perfbench/run.py --workload NAME --seed N --seconds S --trace 0|1

Reads ``BENCHMARK.json`` at the root of the checkout, finds the cell's
configuration, traffic, metric readers and limits by name under
``perfbench/``, warms up, measures ``--seconds`` of the cell's traffic,
compares what the timed path produced with the plain reference, and
prints one JSON object as the last line of standard output.  With
``--trace 1`` the window runs under the JAX profiler and the line holds
the per-layer metrics; with ``--trace 0``, the end-to-end ones.  The
numbers compared are printed beside their limits as the last lines of
standard error and, under ``checks``, last in the result.

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell needs, or when the program or a file the cell names is
missing.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(ROOT, "src"))


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _finite(obj):
    """JSON has no infinity: a number that is not finite prints as null."""
    if isinstance(obj, float) and not math.isfinite(obj):
        return None
    if isinstance(obj, dict):
        return {k: _finite(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_finite(v) for v in obj]
    return obj


def main(argv=None) -> int:
    args = parse(argv)
    from bench import spec

    try:
        cell = spec.load_cell(ROOT, args.workload)
    except spec.SpecError as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2
    try:
        import repro  # noqa: F401 - the program under test
    except ImportError as e:
        print(f"perfbench: the program is not here: {e}", file=sys.stderr)
        return 2
    from bench import device

    device.configure_compile_cache(ROOT)
    devs = device.require_tpu(cell.chips)
    from bench import runner

    result = runner.run_cell(cell, args.seed, args.seconds,
                             bool(args.trace), T_START, devs=devs)
    for name, c in result["checks"].items():
        print(f"[check] {name} {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['value'] <= c['limit'] else 'OVER'}",
              file=sys.stderr, flush=True)
    print(json.dumps(_finite(result)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
