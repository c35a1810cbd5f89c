"""Benchmark aggregator: one harness per paper table/figure.

Emits CSV blocks per figure (Fig 9 area, Fig 10 ablation, Fig 11
flexible-k, Fig 12 buffer sweep, Fig 13 VLEN/depth, kernel microbench).
Dataset scope via REPRO_DATASETS (default: all five; set
REPRO_DATASETS=cora,citeseer,pubmed for a quick pass).

Besides the per-bench CSV/json artifacts, every full run appends one
record per bench to ``results/bench/BENCH_summary.json``
(``REPRO_BENCH_DIR`` to relocate) — an append-only log of ``{run_at,
bench, seconds, ok, summary}`` rows, so regressions across runs are
greppable from one file without re-parsing each bench's own output.

The same run also exports a unified telemetry snapshot through
``repro.obs.export``: per-bench duration histograms and ok/failed
counters land in ``BENCH_metrics.json`` and (Prometheus text format)
``BENCH_metrics.prom`` beside the summary, written even when a bench
fails so a broken run still leaves its telemetry behind.

The three benches that need several devices (sharded SpMM, autoplan,
pipeline) run first, each in a child process forced onto the CPU's
virtual devices (``benchmarks/cpu_child.py``).  Bench modules are
imported one at a time, so the parent has not imported JAX when it
starts those children, and only then turns on the compile cache.
"""

import importlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BENCH_DIR = os.environ.get("REPRO_BENCH_DIR", "results/bench")
SUMMARY_PATH = os.path.join(BENCH_DIR, "BENCH_summary.json")
METRICS_JSON_PATH = os.path.join(BENCH_DIR, "BENCH_metrics.json")
METRICS_PROM_PATH = os.path.join(BENCH_DIR, "BENCH_metrics.prom")

# (title, module under benchmarks/), in run order.
CHILD_BENCHES = [
    ("SpMM sharded (1 vs N devices)", "bench_spmm_sharded"),
    ("Autoplan vs static plan", "bench_plan"),
    ("Pipelined multi-layer forward (sharded activations)", "bench_pipeline"),
]
IN_PROCESS_BENCHES = [
    ("Fig 9 (area)", "bench_area"),
    ("Fig 10 (ablation)", "bench_ablation"),
    ("Fig 11 (flexible k)", "bench_flexible_k"),
    ("Fig 12 (buffer sizes)", "bench_buffer_sizes"),
    ("Fig 13 (VLEN/depth)", "bench_vlen_depth"),
    ("SpMM kernel", "bench_spmm_kernel"),
    ("Quantized serving (f32/bf16/int8)", "bench_quant"),
    ("Serving engine", "bench_serve"),
    ("Async queue (open-loop Poisson)", "bench_queue"),
    ("Fleet (multi-tenant hot/cold isolation)", "bench_fleet"),
]


def export_metrics(registry,
                   json_path: str = METRICS_JSON_PATH,
                   prom_path: str = METRICS_PROM_PATH) -> None:
    """Write the harness registry in both obs export formats."""
    from repro.obs import write_metrics_json, write_prometheus

    os.makedirs(os.path.dirname(json_path), exist_ok=True)
    write_metrics_json(json_path, registry)
    write_prometheus(prom_path, registry)


def _jsonable(value):
    """The bench's return value if it survives json round-tripping."""
    try:
        json.dumps(value)
        return value
    except (TypeError, ValueError):
        return repr(value)[:500]


def append_summary(records, path: str = SUMMARY_PATH) -> None:
    """Append this run's records to the consolidated summary log.

    The file is a flat JSON list, append-only across runs: existing
    records are preserved verbatim (an unreadable/corrupt file is
    sidestepped rather than clobbered — the old content moves to a
    ``.corrupt`` sibling so no history is silently lost).
    """
    existing = []
    if os.path.exists(path):
        try:
            with open(path) as f:
                existing = json.load(f)
            if not isinstance(existing, list):
                raise ValueError("summary root is not a list")
        except (ValueError, OSError):
            os.replace(path, path + ".corrupt")
            existing = []
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(existing + list(records), f, indent=2)


def main() -> None:
    from repro.runtime.metrics import MetricsRegistry, labeled

    t0 = time.time()
    run_at = time.strftime("%Y-%m-%dT%H:%M:%S", time.gmtime())
    print(f"# datasets: {os.environ.get('REPRO_DATASETS', 'all five')}")
    metrics = MetricsRegistry()
    records = []
    for name, bench in CHILD_BENCHES + IN_PROCESS_BENCHES:
        if bench == IN_PROCESS_BENCHES[0][1]:
            from repro.serve.cache import enable_compile_cache

            print(f"# compile cache: {enable_compile_cache()}")
        mod = importlib.import_module(f"benchmarks.{bench}")
        print(f"\n## {name}")
        t = time.time()
        rec = {"run_at": run_at, "bench": bench, "title": name}
        try:
            rec["summary"] = _jsonable(mod.run())
            rec["ok"] = True
        except BaseException as e:  # noqa: BLE001 - log, then re-raise
            rec["ok"] = False
            rec["error"] = f"{type(e).__name__}: {e}"
            rec["seconds"] = round(time.time() - t, 2)
            records.append(rec)
            metrics.inc("bench_failed")
            metrics.inc(labeled("bench_failed", bench=bench))
            metrics.observe(labeled("bench_s", bench=bench),
                            time.time() - t)
            append_summary(records)
            export_metrics(metrics)
            raise
        rec["seconds"] = round(time.time() - t, 2)
        records.append(rec)
        metrics.inc("bench_ok")
        metrics.inc(labeled("bench_ok", bench=bench))
        metrics.observe(labeled("bench_s", bench=bench), rec["seconds"])
        print(f"# ({rec['seconds']:.1f}s)")
    append_summary(records)
    export_metrics(metrics)
    print(f"\n# total {time.time() - t0:.1f}s "
          f"(summary -> {SUMMARY_PATH}, metrics -> {METRICS_JSON_PATH} "
          f"+ {METRICS_PROM_PATH})")


if __name__ == "__main__":
    main()
