import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: AOT lower + compile every (arch x shape x mesh) cell.

The two lines above MUST run before any other import (jax locks the device
count on first init); this module is the only place the 512 placeholder
devices exist — tests and benches see 1 device.

Single-cell mode (the default) lowers one (arch, shape, mesh) combination,
prints memory_analysis / cost_analysis, parses collective bytes from the
partitioned HLO, and writes a JSON record.  ``--all`` drives every cell in
a fresh subprocess (isolation: one XLA universe per cell, cached results
skipped), which is how EXPERIMENTS.md §Dry-run and §Roofline are produced.

Mesh cells are planned through ``dist.topology.viable_mesh_shapes``:
``--chips``/``--model-parallel`` pick the widest viable (data, model)
factorization (defaults reproduce the historical 16x16 and 2x16x16
cells), so awkward chip counts degrade the model axis instead of failing.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b \
      --shape train_4k --chips 250 --model-parallel 16   # degrades to 25x10
"""

import argparse
import dataclasses
import json
import math
import subprocess
import sys
import time
import traceback
from typing import Dict, Optional

RESULT_DIR = os.environ.get("REPRO_DRYRUN_DIR", "results/dryrun")

# The 512 placeholder devices above bound what any planned mesh may use.
MAX_VIRTUAL_CHIPS = 512
POD_FACTOR = 2  # multi-pod runs replicate the planned pod over this many pods


def planned_mesh_shape(chips: int, model_parallel: int,
                       multi_pod: bool) -> tuple:
    """Mesh shape for one dry-run cell, via ``dist.topology``.

    Instead of the historical hard-coded 16x16 / 2x16x16 cells, the
    (data, model) factorization comes from ``viable_mesh_shapes`` — the
    widest model axis that divides the chip count — so awkward slices
    (prime counts, TP wider than the slice) degrade instead of asserting.
    """
    from repro.dist.topology import viable_mesh_shapes

    total = chips * (POD_FACTOR if multi_pod else 1)
    if total > MAX_VIRTUAL_CHIPS:
        raise ValueError(
            f"{total} chips exceed the {MAX_VIRTUAL_CHIPS} virtual devices "
            f"this module forces at import"
        )
    data, model = viable_mesh_shapes(chips, model_parallel)[0]
    return (POD_FACTOR, data, model) if multi_pod else (data, model)


def mesh_label(shape: tuple) -> str:
    return "x".join(str(s) for s in shape)


def _lower_and_analyze(cfg, shape, mesh, plan, donate: bool):
    """Lower+compile one step for (cfg, shape) -> (record_fields, compiled)."""
    import jax

    from repro.launch.shapes import input_specs
    from repro.launch.steps import step_for
    from repro.roofline.analysis import collective_bytes

    specs = input_specs(cfg, shape, mesh, plan)
    step = step_for(cfg, shape.kind, mesh=mesh)
    if shape.kind == "train":
        args = (specs["params"], specs["opt_state"], specs["tokens"])
        if "memory" in specs:
            args = args + (specs["memory"],)
        donate_argnums = (0, 1) if donate else ()
    elif shape.kind == "prefill":
        args = (specs["params"], specs["tokens"])
        if "memory" in specs:
            args = args + (specs["memory"],)
        donate_argnums = ()
    else:
        args = (specs["params"], specs["cache"], specs["tokens"],
                specs["pos"])
        donate_argnums = (1,) if donate else ()

    t0 = time.time()
    with jax.set_mesh(mesh):
        lowered = jax.jit(step, donate_argnums=donate_argnums).lower(*args)
        t1 = time.time()
        compiled = lowered.compile()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    return {
        "lower_s": round(t1 - t0, 2),
        "compile_s": round(time.time() - t1, 2),
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "coll": coll,
        "hlo_lines": hlo.count("\n"),
    }, compiled


def _reduced_depth(cfg, periods: int):
    """Same config with `periods` pattern repetitions, scans unrolled."""
    first = cfg.moe.first_dense if cfg.moe else 0
    enc = periods if cfg.encoder_layers else 0
    return dataclasses.replace(
        cfg,
        n_layers=first + periods * len(cfg.pattern),
        encoder_layers=enc,
        scan_unroll=max(periods, 2),
    )


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             fsdp: Optional[bool] = None, donate: bool = True,
             body_correction: bool = True, chips: int = 256,
             model_parallel: int = 16) -> Dict:
    import jax

    from repro.configs import get_config
    from repro.dist.sharding import ShardingPlan
    from repro.launch.mesh import make_production_mesh
    from repro.launch.shapes import SHAPES, input_specs, skip_reason
    from repro.launch.steps import step_for
    from repro.models.lm import n_body_periods
    from repro.roofline.analysis import (
        active_param_count, collective_bytes, model_flops,
        ssm_time_scan_flops)

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_shape = planned_mesh_shape(chips, model_parallel, multi_pod)
    data_w, model_w = mesh_shape[-2], mesh_shape[-1]
    record: Dict = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_label(mesh_shape),
        "chips": int(math.prod(mesh_shape)),
        "kind": shape.kind,
        "params_total": cfg.param_count(),
        "params_active": active_param_count(cfg),
    }
    reason = skip_reason(cfg, shape)
    if reason:
        record["skipped"] = reason
        return record

    mesh = make_production_mesh(multi_pod=multi_pod, data=data_w,
                                model=model_w, pods=POD_FACTOR)
    # FSDP for multi-B models; tiny models stay pure TP+DP.
    if fsdp is None:
        fsdp = cfg.param_count() > 4e9
    plan = ShardingPlan(mesh, fsdp=fsdp)
    record["fsdp"] = fsdp

    main, compiled = _lower_and_analyze(cfg, shape, mesh, plan, donate)
    record.update(lower_s=main["lower_s"], compile_s=main["compile_s"],
                  hlo_lines=main["hlo_lines"])

    ma = compiled.memory_analysis()
    record["memory_per_device"] = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "peak_bytes_est": int(ma.argument_size_in_bytes
                              + ma.output_size_in_bytes
                              + ma.temp_size_in_bytes
                              - ma.alias_size_in_bytes),
    }
    record["collectives"] = dict(main["coll"])

    # --- scan trip-count correction -----------------------------------
    # XLA cost analysis counts a while body once; lower 1- and 2-period
    # fully-unrolled variants and take the difference as the per-period
    # body cost, then scale to the real depth (DESIGN.md §5.6).
    t_periods = n_body_periods(cfg)
    flops, bytes_, coll_total = main["flops"], main["bytes"], \
        main["coll"]["total"]
    if body_correction and t_periods > 1:
        r1, _ = _lower_and_analyze(_reduced_depth(cfg, 1), shape, mesh,
                                   plan, donate=False)
        r2, _ = _lower_and_analyze(_reduced_depth(cfg, 2), shape, mesh,
                                   plan, donate=False)
        body = {
            "flops": max(r2["flops"] - r1["flops"], 0.0),
            "bytes": max(r2["bytes"] - r1["bytes"], 0.0),
            "coll": max(r2["coll"]["total"] - r1["coll"]["total"], 0.0),
        }
        record["body_per_period"] = body
        flops = flops + (t_periods - 1) * body["flops"]
        bytes_ = bytes_ + (t_periods - 1) * body["bytes"]
        coll_total = coll_total + (t_periods - 1) * body["coll"]
    # recurrent time scans (Mamba/xLSTM) are also counted once per step
    ssm_fix = ssm_time_scan_flops(cfg, shape) / record["chips"]
    record["cost_analysis"] = {
        "flops_per_device_raw": main["flops"],
        "flops_per_device": flops + ssm_fix,
        "bytes_per_device": bytes_,
        "collective_bytes_per_device": coll_total,
        "ssm_time_scan_fix_per_device": ssm_fix,
        "scan_periods": t_periods,
    }
    record["model_flops"] = model_flops(cfg, shape)
    return record


def cell_path(arch: str, shape: str, mesh: str) -> str:
    return os.path.join(RESULT_DIR, f"{arch}__{shape}__{mesh}.json")


def drive_all(mesh_mode: str, archs, shapes, timeout: int,
              workers: int = 2, chips: int = 256,
              model_parallel: int = 16) -> None:
    from concurrent.futures import ThreadPoolExecutor

    from repro.configs import list_archs
    from repro.launch.shapes import SHAPES

    archs = archs or list_archs()
    shapes = shapes or list(SHAPES.keys())
    meshes = {"pod": [False], "multipod": [True],
              "both": [False, True]}[mesh_mode]
    os.makedirs(RESULT_DIR, exist_ok=True)
    # single-pod first: those feed the roofline table
    todo = [(a, s, mp) for mp in meshes for a in archs for s in shapes]
    counts = {"ok": 0, "failed": 0}

    def one(cell):
        arch, shp, mp = cell
        mesh_name = mesh_label(planned_mesh_shape(chips, model_parallel, mp))
        out = cell_path(arch, shp, mesh_name)
        if os.path.exists(out):
            counts["ok"] += 1
            return
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", arch, "--shape", shp, "--out", out,
               "--chips", str(chips), "--model-parallel",
               str(model_parallel)]
        if mp:
            # the multipod pass proves the pod axis shards + memory; the
            # roofline table is single-pod, so skip the 3x body compiles
            cmd += ["--multi-pod", "--no-body-correction"]
        print(f"[dryrun] {arch} x {shp} x {mesh_name} ...", flush=True)
        try:
            r = subprocess.run(cmd, timeout=timeout, capture_output=True,
                               text=True)
            if r.returncode != 0:
                counts["failed"] += 1
                with open(out + ".err", "w") as f:
                    f.write(r.stderr or "")
                tail = (r.stderr or "").strip().splitlines()[-2:]
                print(f"[dryrun]   FAILED {arch}x{shp}x{mesh_name}: "
                      f"{' | '.join(tail)}", flush=True)
            else:
                counts["ok"] += 1
                print(f"[dryrun]   ok {arch}x{shp}x{mesh_name}", flush=True)
        except subprocess.TimeoutExpired:
            counts["failed"] += 1
            with open(out + ".err", "w") as f:
                f.write(f"timeout after {timeout}s")
            print(f"[dryrun]   TIMEOUT {arch}x{shp}x{mesh_name}", flush=True)

    with ThreadPoolExecutor(max_workers=workers) as ex:
        list(ex.map(one, todo))
    print(f"[dryrun] complete: {counts['ok']} ok, "
          f"{counts['failed']} failed of {len(todo)}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--mesh", default="both",
                    choices=["pod", "multipod", "both"])
    ap.add_argument("--archs", help="comma list (with --all)")
    ap.add_argument("--shapes", help="comma list (with --all)")
    ap.add_argument("--out")
    ap.add_argument("--timeout", type=int, default=3000)
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--no-body-correction", action="store_true")
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--chips", type=int, default=256,
                    help="chips per pod; the (data, model) factorization "
                         "comes from dist.topology.viable_mesh_shapes")
    ap.add_argument("--model-parallel", type=int, default=16,
                    help="upper bound on the model axis width (degrades "
                         "downward until it divides --chips)")
    args = ap.parse_args()

    if args.all:
        drive_all(args.mesh,
                  args.archs.split(",") if args.archs else None,
                  args.shapes.split(",") if args.shapes else None,
                  args.timeout, workers=args.workers, chips=args.chips,
                  model_parallel=args.model_parallel)
        return

    record = run_cell(args.arch, args.shape, args.multi_pod,
                      fsdp=False if args.no_fsdp else None,
                      body_correction=not args.no_body_correction,
                      chips=args.chips, model_parallel=args.model_parallel)
    text = json.dumps(record, indent=2, default=str)
    print(text)
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text)


if __name__ == "__main__":
    main()
