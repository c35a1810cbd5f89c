"""Run one cell once: set up, measure a window, check, report.

The program is built the way its launcher builds it
(``repro.launch.serve_gcn.build_engine`` from the launcher's own
defaults); the configuration sets the model and data, the traffic sets
its own parameters, and nothing else is pinned.  Full-graph cells call
``ServeEngine.full_forward`` back to back; query cells drive
``ServeRuntime.submit`` and wait on the futures.
"""

from __future__ import annotations

import dataclasses
import math
import os
import shutil
import sys
import tempfile
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from bench import check, device, drivers, inputs, spec, trace, traffic, work

# The last answers of the window may come this long after it closes.
SETTLE_S = 60.0
# Forwards of a full-graph window whose outputs are kept for the check:
# drawn from the seed among the first ones, plus the last one.
KEEP_FROM = 32
KEEP_N = 3


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read (``metrics/<name>.py``)."""

    kind: str
    setup_s: float
    window_s: float
    window_t0: float = 0.0
    forwards: int = 0
    records: List[drivers.Record] = dataclasses.field(default_factory=list)
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)
    max_batch: int = 0
    trace: Optional[dict] = None
    flops_per_forward: float = 0.0
    aggregation_least_s: float = 0.0
    peaks: Optional[dict] = None


class CompileCounter:
    """Programs compiled or loaded from the compile cache, process-wide."""

    def __init__(self):
        import jax

        self.n = 0

        def on_duration(event, _secs, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.n += 1

        def on_event(event, **_kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.n += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def log(msg: str) -> None:
    print(f"[perfbench] {msg}", file=sys.stderr, flush=True)


# -- building ---------------------------------------------------------------


def build(cell: spec.Cell, seed: int, overrides: Optional[dict] = None):
    """``(engine, dataset, features, host weights, layer widths)``."""
    from repro.graphs import load_dataset
    from repro.launch.serve_gcn import build_engine, build_parser

    cfg, t = cell.config, cell.traffic
    t0 = time.perf_counter()
    ds = load_dataset(cfg["dataset"], seed=int(cfg["graph_seed"]))
    t1 = time.perf_counter()
    published = (cfg["nodes"], cfg["edges"], cfg["feature_dim"],
                 cfg["classes"])
    got = (ds.spec.nodes, ds.spec.edges, ds.spec.feature_dim,
           ds.spec.classes)
    if got != published:
        raise spec.SpecError(f"dataset {cfg['dataset']} has sizes {got}, "
                             f"the configuration states {published}")
    dims = [cfg["feature_dim"]] + [cfg["hidden_dim"]] * (
        cfg["n_layers"] - 1) + [cfg["classes"]]
    x, layers = inputs.make_inputs(seed, cfg["nodes"], dims,
                                   cfg["feature_sparsity"])
    t2 = time.perf_counter()
    args = build_parser().parse_args(
        ["--dataset", cfg["dataset"], "--hidden", str(cfg["hidden_dim"])])
    kw = dict(params=inputs.program_params(layers))
    if t["kind"] != "full_graph":
        kw.update(fanout=t["fanout"], hops=int(t["hops"]),
                  max_batch=int(t["max_batch"]),
                  max_seeds=int(t["seeds_per_request"][1]),
                  sampler_seed=int(t["sampler_seed"]))
    kw.update(overrides or {})
    engine = build_engine(args, **kw)
    log(f"set-up: data set {t1 - t0:.3f} s, inputs {t2 - t1:.3f} s, "
        f"engine {time.perf_counter() - t2:.3f} s")
    if engine.cfg.n_layers != cfg["n_layers"]:
        raise spec.SpecError("the program's GCN depth differs from the "
                             "configuration's")
    # The seed's features replace the data set's own; the engine reads
    # them on every forward and every prepare.
    engine.features = x
    return engine, ds, x, inputs.host_weights(layers), dims


# -- windows ----------------------------------------------------------------


def full_graph_window(engine, seconds: float, rng, counter: CompileCounter):
    keep = set(int(i) for i in rng.choice(KEEP_FROM, KEEP_N, replace=False))
    kept, n, out = [], 0, None
    c0 = counter.n
    t0 = time.perf_counter()
    with drivers.annotate("window"):
        while True:
            with drivers.annotate("full_forward"):
                out = engine.full_forward()
            if n in keep:
                kept.append(out)
            n += 1
            if time.perf_counter() - t0 >= seconds:
                break
    t1 = time.perf_counter()
    kept.append(out)
    return dict(window_s=t1 - t0, forwards=n, outputs=kept,
                compiles=counter.n - c0)


def _in_background(fn, *args, **kw):
    box = {}

    def target():
        try:
            box["value"] = fn(*args, **kw)
        except BaseException as e:  # noqa: BLE001 - re-raised by join
            box["error"] = e

    th = threading.Thread(target=target, name="perfbench-traffic",
                          daemon=True)
    th.start()

    def join():
        th.join()
        if "error" in box:
            raise box["error"]
        return box["value"]

    return join


def query_window(engine, cell: spec.Cell, seconds: float, rng, ds,
                 counter: CompileCounter, on_window_start=None):
    from repro.runtime.queue import AdmissionError, DeadlineExceededError

    t = cell.traffic
    deadline_s = None if t["deadline_ms"] is None else t["deadline_ms"] / 1e3
    n = engine.graph.n_nodes
    open_loop = t["kind"] == "open_loop"
    if open_loop:
        warm_off = traffic.arrival_offsets(t, float(t["warm_s"]), rng)
        win_off = traffic.arrival_offsets(t, float(seconds), rng)
        n_warm, n_win = len(warm_off), len(win_off)
    else:
        warm_off = win_off = None
        n_warm, n_win = int(t["warm_requests"]), int(t["pool_size"])
    warm_reqs, win_reqs = traffic.plan_requests(
        t, n, n_warm, n_win, rng, graph=(ds.adj.indptr, ds.adj.indices),
        warm_offsets=warm_off, window_offsets=win_off)

    rt = engine.runtime(capacity=t["queue_capacity"])
    rt.start()
    sender = drivers.Sender(
        lambda seeds, dl: rt.submit(seeds, deadline=dl),
        refused=(AdmissionError,), shed=(DeadlineExceededError,))
    try:
        # Warm-up: the mix's own traffic on requests apart from the
        # window's, so the scheduler's estimator has seen every rung.
        with drivers.annotate("warm_traffic"):
            if open_loop:
                t_w = time.perf_counter() + 0.05
                recs = drivers.open_loop(
                    sender, warm_reqs, t_w + warm_off,
                    threads=int(t["submit_threads"]), deadline_s=deadline_s)
            else:
                recs = drivers.closed_loop(
                    sender, warm_reqs, clients=int(t["clients"]),
                    until=time.perf_counter() + float(t["warm_s"]),
                    deadline_s=deadline_s, timeout_s=SETTLE_S)
            for r in recs:
                sender.settle(r, SETTLE_S)
        if on_window_start is not None:
            on_window_start()
        before = dict(rt.metrics.snapshot()["counters"])
        c0 = counter.n
        t0 = time.perf_counter() + 0.05
        t_end = t0 + float(seconds)
        if open_loop:
            join = _in_background(
                drivers.open_loop, sender, win_reqs, t0 + win_off,
                threads=int(t["submit_threads"]), deadline_s=deadline_s)
        else:
            time.sleep(max(t0 - time.perf_counter(), 0.0))
            join = _in_background(
                drivers.closed_loop, sender, win_reqs,
                clients=int(t["clients"]), until=t_end,
                deadline_s=deadline_s, timeout_s=SETTLE_S)
        time.sleep(max(t0 - time.perf_counter(), 0.0))
        with drivers.annotate("window"):
            time.sleep(max(t_end - time.perf_counter(), 0.0))
        compiles = counter.n - c0
        recs = join()
        for r in recs:
            sender.settle(r, SETTLE_S)
        after = dict(rt.metrics.snapshot()["counters"])
    finally:
        rt.shutdown()
    wrapped = (not open_loop) and len(recs) >= len(win_reqs)
    delta = {k: after.get(k, 0) - before.get(k, 0) for k in after}
    return dict(window_s=t_end - t0, t0=t0, t_end=t_end, records=recs,
                counters=delta, compiles=compiles, pool_exhausted=wrapped)


# -- the check --------------------------------------------------------------


def reference_numbers(cell: spec.Cell, ds, x, weights, got, rng) -> dict:
    ref = spec.reference_module(cell.bench_dir, cell.config)
    a = ref.normalize(ds.adj.indptr, ds.adj.indices, cell.config["nodes"])
    t = cell.traffic
    if t["kind"] == "full_graph":
        outs = got["outputs"]
        exact = ref.forward(a, x, weights, products="exact")
        bf16 = ref.forward(a, x, weights, products="bf16")
        return {"logit_gap": check.logit_gap(
            outs, [exact] * len(outs), [bf16] * len(outs))}
    done = [r for r in got["records"] if r.status == "ok"]
    sample = t.get("check_sample")
    if sample is not None and len(done) > sample:
        pick = rng.choice(len(done), size=int(sample), replace=False)
        done = [done[i] for i in sorted(pick)]
    if not done:
        return {"logit_gap": math.inf}
    outs = [r.out for r in done]
    if t["fanout"] is None:
        # The exact k-hop answer is the full graph's row for each seed.
        full = {c: ref.forward(a, x, weights, products=c)
                for c in ("exact", "bf16")}
        refs = {c: [full[c][r.seeds] for r in done] for c in full}
    else:
        refs = {c: [ref.query(a, x, weights, r.seeds, hops=int(t["hops"]),
                              fanout=int(t["fanout"]),
                              sampler_seed=int(t["sampler_seed"]),
                              products=c) for r in done]
                for c in ("exact", "bf16")}
    return {"logit_gap": check.logit_gap(outs, refs["exact"], refs["bf16"])}


# -- one run ------------------------------------------------------------------


def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool,
             t_start: float, devs=None, overrides: Optional[dict] = None
             ) -> dict:
    """One run of ``cell``; returns the result object (not yet printed).

    ``devs`` are the devices the cell runs on (the caller has checked
    them); ``overrides`` go to ``build_engine`` (tests pass
    ``interpret=True`` to run the Pallas kernels on the CPU).
    """
    import jax

    t = traffic.validate(cell.traffic)
    devs = devs or jax.devices()[:cell.chips]
    rng = np.random.default_rng(int(seed))
    counter = CompileCounter()
    engine, ds, x, weights, dims = build(cell, seed, overrides)
    log(f"device {device.identity(devs)}; compile cache "
        f"{os.environ.get('JAX_COMPILATION_CACHE_DIR')}")

    tmp = tempfile.mkdtemp(prefix="perfbench-trace-") if traced else None
    tracing = {"on": False}

    def start_trace():
        if traced:
            opts = jax.profiler.ProfileOptions()
            opts.host_tracer_level = 1
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tmp, profiler_options=opts)
            tracing["on"] = True

    try:
        if t["kind"] == "full_graph":
            # Set-up ends where the window starts: the warm forwards
            # (the first one compiles) are set-up, as is the trace start.
            t_w = time.perf_counter()
            with drivers.annotate("warmup"):
                for _ in range(3):
                    engine.full_forward()
            log(f"set-up: warm forwards {time.perf_counter() - t_w:.3f} s")
            start_trace()
            setup_s = time.perf_counter() - t_start
            got = full_graph_window(engine, seconds, rng, counter)
        else:
            t_w = time.perf_counter()
            with drivers.annotate("warmup"):
                built = engine.warmup()
            log(f"set-up: warmup {time.perf_counter() - t_w:.3f} s built "
                f"{built} bucket executables; ladder "
                f"{[(b.nodes, b.rows) for b in engine.batcher.ladder.entries]}")
            marks = {}

            def window_start():
                marks["setup_s"] = time.perf_counter() - t_start
                start_trace()

            got = query_window(engine, cell, seconds, rng, ds, counter,
                               on_window_start=window_start)
            setup_s = marks["setup_s"]
        summary = None
        if tracing["on"]:
            t_s = time.perf_counter()
            jax.profiler.stop_trace()
            tracing["on"] = False
            t_r = time.perf_counter()
            events = trace.load_events(trace.find_xplane(tmp))
            summary = trace.summarize(events)
            log(f"trace: stop {t_r - t_s:.3f} s, read and reduce "
                f"{time.perf_counter() - t_r:.3f} s, {len(events)} events")
        peak = device.memory_peak_bytes(devs)
    finally:
        if tracing["on"]:
            jax.profiler.stop_trace()
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)

    # The program's state is freed before the reference runs.
    engine = None
    numbers = reference_numbers(cell, ds, x, weights, got, rng)
    ok = check.verdict(numbers, cell.limits)

    ident = device.identity(devs)
    peaks = device.peaks_for(cell.bench_dir, ident["kind"]) \
        if ident["platform"] == "tpu" else None
    n_nodes = cell.config["nodes"]
    nnz = int(ds.adj.nnz) + n_nodes          # self loops added once
    recs = got.get("records", [])
    run = Run(
        kind=t["kind"], setup_s=setup_s, window_s=got["window_s"],
        window_t0=got.get("t0", 0.0),
        forwards=got.get("forwards", 0), records=recs,
        counters=got.get("counters", {}),
        max_batch=int(t.get("max_batch") or 0), trace=summary,
        flops_per_forward=work.forward_flops(n_nodes, nnz, dims),
        aggregation_least_s=(work.aggregation_least_s(
            n_nodes, nnz, dims, peaks) if peaks else 0.0),
        peaks=peaks)
    metrics = {}
    for m in cell.metrics(traced):
        value = spec.metric_reader(cell.bench_dir, m.name)(run)
        if value is not None:
            metrics[m.name] = {"value": float(value), "unit": m.unit}

    if t["kind"] == "full_graph":
        attempted, failed = got["forwards"], 0
        log(f"{got['forwards']} full forwards in {got['window_s']:.6f} s; "
            f"{got['compiles']} programs compiled or loaded in the window")
    else:
        attempted = len(recs)
        failed = sum(r.status == "failed" for r in recs)
        late = drivers.lateness_s(recs)
        by = {s: sum(r.status == s for r in recs)
              for s in ("ok", "refused", "shed", "failed")}
        log(f"{attempted} requests in the window: {by}; "
            f"{got['compiles']} programs compiled or loaded in the window")
        if len(late):
            half = len(late) // 2
            log(f"generator lateness ms: p50 {np.median(late) * 1e3:.3f} "
                f"p99 {np.percentile(late, 99) * 1e3:.3f} max "
                f"{late.max() * 1e3:.3f}; first half mean "
                f"{late[:half].mean() * 1e3 if half else 0:.3f}, second half "
                f"mean {late[half:].mean() * 1e3:.3f}")
        if got.get("pool_exhausted"):
            log("the request pool ran out inside the window")
        for r in recs:
            if r.status == "failed":
                log(f"request {r.index} failed: {r.error}")
                break
    result = {"correct": bool(ok), "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics,
              "device": dict(ident, memory_peak_bytes=int(peak))}
    if summary is not None:
        result["device"].update(busy_s=summary["busy_s"],
                                window_s=summary["window_s"])
        result["breakdown"] = trace.breakdown(summary)
        log(f"kernel ops: {sorted(summary['op_s'].items(), key=lambda kv: -kv[1])[:6]}")
    result["checks"] = check.checks_block(numbers, cell.limits)
    return result
