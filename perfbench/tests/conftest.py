"""Shared set-up of the benchmark's CPU tests.

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests -q

puts ``perfbench/`` (the ``bench`` package) and ``src/`` on the path and
builds cells from the repository's own ``BENCHMARK.json``.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src"), HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

# Cora, the smallest Table III graph, stands in for a cell's graph where a
# CPU test must run the whole path quickly.
CORA = dict(dataset="cora", nodes=2708, edges=5429, feature_dim=1433,
            classes=7)


# The open-loop mix has no cell of its own yet (PERF.md, section 7); its
# path is tested on the pubmed query cell with the mix swapped in.
OPEN_LOOP = ("pubmed-query-closed", "query-steady")


def cell_for(workload, mix=None, **config):
    """A cell of ``BENCHMARK.json``, with another traffic file if ``mix``
    names one."""
    import json

    from bench import spec

    cell = spec.load_cell(ROOT, workload)
    cell.config = dict(cell.config, **config)
    if mix is not None:
        with open(os.path.join(BENCH, "traffic", f"{mix}.json")) as f:
            cell.traffic = json.load(f)
    return cell


def small_cell(workload, mix=None, **traffic):
    """``workload`` on cora, with the traffic cut to a CPU's pace."""
    cell = cell_for(workload, mix, **CORA)
    t = dict(cell.traffic)
    if t["kind"] == "open_loop":
        t.update(rate_per_s=20, warm_s=0.5, check_sample=16)
    elif t["kind"] == "closed_loop":
        t.update(clients=4, pool_size=300, warm_requests=8, warm_s=0.5)
    t.update(traffic)
    cell.traffic = t
    return cell
