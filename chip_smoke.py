"""Bring-up smoke test: GCN serving end to end on a TPU chip.

One chip (the default) drives the serving path the way
``python -m repro.launch.serve_gcn`` builds it, at pubmed's Table III
size (19,717 nodes, 44,338 edges, 500 features, 3 classes; hidden width
64, the launcher's default) with compiled Pallas kernels: ``pallas_sparse``
for the full-graph step, and the dense grid the batcher resolves for
served buckets.  It warms up, runs a few full-graph forwards, a few
single queries, one ``query_batch`` and one short async window, and
checks

* the full-graph logits against a host reference (scipy sparse products
  of the same normalized adjacency and weights);
* every query's seed logits against the matching full-graph rows
  (queries run with uncapped fanout, so the subgraph answer is exact);
* that nothing compiles after warmup, and that no request failed.

``--four-chips`` runs only the sharded full-graph forward on a 4-wide
data mesh, in the replicated and row-sharded output layouts, against a
one-chip forward of the same graph in the same process.

With no TPU the script exits non-zero before any work: it never falls
back to the CPU or to Pallas interpret mode.  Its last output line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

Usage:
  python chip_smoke.py
  python chip_smoke.py --four-chips
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

_ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(_ROOT, "src"))

DATASET = "pubmed"
HIDDEN = 64
SEED = 0

# The TPU runs an f32 matmul at its default precision: operands rounded to
# bf16 (8-bit mantissa, relative rounding 2^-9) and products accumulated in
# f32.  Through a 500-wide combination and two layers that leaves errors of
# a few 1e-3 of the logits' scale; the host reference is exact f64.  So
# logits must agree to 1% of the reference's largest magnitude, which a
# dropped or misplaced nonzero (an O(1) error on its row) does not meet.
CHIP_REL_TOL = 1e-2
# Sharded vs one chip: the same kernels at the same precision on every
# chip; only the order of the cross-chip partial sums and the row split of
# the combination matmul differ.
SHARDED_REL_TOL = 1e-3


def fail(msg: str) -> None:
    print(f"FAIL: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def check(name: str, test, ref, tol: float) -> None:
    """Max-abs error of ``test`` relative to ``ref``'s largest magnitude
    (``exec.quant.logit_error``) must be at most ``tol``."""
    from repro.exec.quant import logit_error

    err = logit_error(ref, test)
    ok = err <= tol
    print(f"[check] {name}: rel err {err:.3e} (tol {tol:.0e}) "
          f"{'ok' if ok else 'MISMATCH'}", flush=True)
    if not ok:
        fail(f"{name} differs from its reference by {err:.3e} > {tol:.0e}")


def tpu_devices():
    """The chip's devices; exits non-zero when JAX finds no TPU."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        fail(f"JAX found no devices: {e}")
    if devs[0].platform != "tpu":
        fail(f"no TPU: JAX runs on {devs[0].platform}; this smoke test "
             "never falls back to the CPU")
    return devs


class CompileCounter:
    """Process-wide count of programs compiled or loaded from the cache."""

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


def host_reference(adj_norm, features, params):
    """``A relu(A (X W0 + b0)) W1 + b1``-style GCN logits in f64 on the
    host: scipy sparse products, independent of the code under test."""
    import numpy as np
    import scipy.sparse as sp

    a = sp.csr_matrix(
        (np.asarray(adj_norm.data, np.float64), adj_norm.indices,
         adj_norm.indptr), shape=adj_norm.shape)
    x = np.asarray(features, np.float64)
    n_layers = len(params)
    for i in range(n_layers):
        layer = params[f"layer_{i}"]
        w = np.asarray(layer["w"], np.float64)
        b = np.asarray(layer["b"], np.float64)
        x = a @ (x @ w + b)
        if i < n_layers - 1:
            x = np.maximum(x, 0.0)
    return x


def timed(label: str, device: str, fn):
    t0 = time.perf_counter()
    out = fn()
    print(f"[time] {label}: {time.perf_counter() - t0:.3f} s on {device}",
          flush=True)
    return out


def one_chip(device: str, interpret: bool = False,
             dataset: str = DATASET) -> None:
    """The serving path on one device, checked end to end."""
    import numpy as np

    from repro.launch.serve_gcn import build_engine, build_parser, \
        run_async_scenario

    counter = CompileCounter()
    args = build_parser().parse_args([
        "--dataset", dataset, "--hidden", str(HIDDEN),
        "--impl", "pallas_sparse", "--qps", "50", "--deadline-ms", "5000",
        "--requests", "24",
    ])
    engine = timed("build (dataset + preprocessing)", device, lambda: build_engine(
        args, fanout=None, interpret=interpret))
    n = engine.graph.n_nodes
    print(f"[graph] {dataset}: {n} nodes, {engine.adj_norm.nnz} nonzeros, "
          f"{engine.features.shape[1]} features, "
          f"{engine.graph.pre.ell.cols.shape[0]} ELL rows", flush=True)

    rng = np.random.default_rng(SEED)
    requests = [
        rng.choice(n, size=int(rng.integers(1, args.seeds_per_request + 1)),
                   replace=False)
        for _ in range(args.requests)
    ]
    # Warm exactly the rungs these requests reach: uncapped fanout would
    # otherwise warm every rung up to the whole graph at every batch size.
    top = max(engine._prepare(s).bucket.nodes for s in requests)
    built = timed("warmup", device,
                  lambda: engine.warmup(max_nodes=top))
    after_warmup = counter.n
    full_plan, bucket_plan = engine.full_plan, engine.batcher.plan
    print(f"[plans] full-graph: {full_plan.effective_impl} "
          f"(interpret={full_plan.interpret}); served buckets: "
          f"{bucket_plan.effective_impl}"
          + (f" (degraded from {bucket_plan.impl}: "
             f"{bucket_plan.degraded_reason})" if bucket_plan.degraded
             else ""), flush=True)
    print(f"[warmup] {built} bucket executables for rungs up to {top} "
          f"nodes; ladder {[(b.nodes, b.rows) for b in engine.batcher.ladder.entries]}",
          flush=True)

    full = None
    for i in range(3):
        full = timed(f"full_forward #{i}", device, engine.full_forward)
    if full.shape != (n, engine.cfg.out_dim) or not np.isfinite(full).all():
        fail(f"full-graph logits have shape {full.shape} or are not finite")
    ref = host_reference(engine.adj_norm, engine.features, engine.params)
    check("full-graph logits vs host reference", full, ref, CHIP_REL_TOL)

    def check_queries(name, seeds_list, outs):
        rows = np.concatenate([full[np.asarray(s)] for s in seeds_list])
        got = np.concatenate(outs)
        if not np.isfinite(got).all():
            fail(f"{name} logits are not finite")
        check(f"{name} seed logits vs full-graph rows", got, rows,
              CHIP_REL_TOL)

    singles = requests[:8]
    outs = timed(f"query x{len(singles)}", device,
                 lambda: [engine.query(s) for s in singles])
    check_queries("query", singles, outs)
    batch = requests[8:]
    outs = timed(f"query_batch of {len(batch)}", device,
                 lambda: engine.query_batch(batch))
    check_queries("query_batch", batch, outs)
    # run_async_scenario exits non-zero itself when any request failed.
    timed(f"async window ({len(requests)} requests @ {args.qps:.0f} qps)",
          device, lambda: run_async_scenario(engine, requests, args))

    post = engine.compile_count - built
    programs = counter.n - after_warmup
    print(f"[compiles] warmup built {built} bucket executables; after "
          f"warmup: {post} bucket executables, {programs} programs "
          "compiled or loaded process-wide", flush=True)
    if post or programs:
        fail(f"{post} bucket executables and {programs} programs compiled "
             "after warmup")


def four_chips(devs, device: str, interpret: bool = False,
               dataset: str = DATASET) -> None:
    """Sharded full-graph forward on a 4-wide data mesh vs one chip."""
    import jax
    import numpy as np

    from repro.exec import plan_for_config
    from repro.graphs import load_dataset
    from repro.launch.mesh import make_data_mesh
    from repro.models.gcn import GCNConfig, GCNGraph, gcn_forward, \
        init_params

    if len(devs) < 4:
        fail(f"--four-chips needs 4 devices, JAX has {len(devs)}")
    ds = timed("dataset", device, lambda: load_dataset(dataset))
    cfg = GCNConfig(in_dim=ds.spec.feature_dim, hidden_dim=HIDDEN,
                    out_dim=ds.spec.classes, spmm_impl="pallas_sparse")
    graph = timed("preprocessing", device,
                  lambda: GCNGraph.build(ds.adj_norm, cfg))
    params = init_params(cfg, jax.random.PRNGKey(SEED))
    feats = jax.numpy.asarray(ds.features)
    n = graph.n_nodes

    def forward(mesh, layout):
        plan = plan_for_config(cfg, mesh=mesh, interpret=interpret)
        step = jax.jit(lambda p, x: gcn_forward(
            p, graph, x, cfg, plan=plan, out_layout=layout))
        out = np.asarray(step(params, feats))
        return out[:n][graph.inv] if layout == "row_sharded" else out

    one = timed("one-chip forward", device, lambda: forward(None, "replicated"))
    ref = host_reference(ds.adj_norm, ds.features, params)
    check("one-chip logits vs host reference", one, ref, CHIP_REL_TOL)
    mesh = make_data_mesh(4)
    for layout in ("replicated", "row_sharded"):
        out = timed(f"4-chip forward ({layout})", device,
                    lambda: forward(mesh, layout))
        if out.shape != one.shape or not np.isfinite(out).all():
            fail(f"4-chip {layout} logits have shape {out.shape} or are "
                 "not finite")
        check(f"4-chip {layout} logits vs one chip", out, one,
              SHARDED_REL_TOL)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded forward on 4 chips and its "
                         "one-chip comparison")
    args = ap.parse_args(argv)

    # Preprocessing artifacts go to a fresh directory: stale pickles left
    # in a checkout are never read.
    scratch = tempfile.mkdtemp(prefix="chip_smoke_")
    os.environ["REPRO_CACHE"] = scratch
    try:
        from repro.serve.cache import enable_compile_cache

        cache = enable_compile_cache()
        devs = tpu_devices()
        d0 = devs[0]
        device = f"{d0.platform}/{d0.device_kind} x{len(devs)}"
        print(f"[device] {d0.platform} {d0.device_kind}, {len(devs)} "
              f"devices; compile cache {cache}", flush=True)
        if args.four_chips:
            four_chips(devs, device, interpret=False)
        else:
            one_chip(device, interpret=False)
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
