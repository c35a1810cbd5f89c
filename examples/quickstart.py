"""Quickstart: FlexVector SpMM for one GCN aggregation on a Cora-scale graph.

Shows the full public API surface in ~60 lines:
  dataset -> hybrid preprocessing (edge-cut + vertex-cut) -> bounded-row
  ELL -> SpMM (reference and Pallas kernel) -> PPA estimate from the
  instruction-driven simulator.

Run:  PYTHONPATH=src python examples/quickstart.py [--impl pallas_sparse]
"""

import argparse
import time

import jax.numpy as jnp
import numpy as np

from repro.core import preprocess, spmm_ell
from repro.graphs import load_dataset
from repro.sim import GROWConfig, HWConfig, simulate_flexvector, simulate_grow


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="cora")
    ap.add_argument("--impl", default="reference",
                    choices=["reference", "pallas", "pallas_sparse"])
    ap.add_argument("--tau", type=int, default=6)
    args = ap.parse_args()

    ds = load_dataset(args.dataset)
    print(f"{ds.spec.name}: {ds.spec.nodes} nodes, {ds.adj.nnz // 2} edges, "
          f"F={ds.spec.feature_dim}")

    # 1. hybrid preprocessing (Section IV): edge-cut + vertex-cut -> ELL
    t0 = time.perf_counter()
    pre = preprocess(ds.adj_norm, tau=args.tau, tile_rows=16,
                     edge_cut="rcm", pad_rows_to=128)
    print(f"preprocess: {time.perf_counter() - t0:.2f}s -> "
          f"{pre.ell.padded_rows} sub-rows, tau={pre.ell.tau}, "
          f"{-(-ds.spec.nodes // pre.tile_rows)} tiles")

    # 2. aggregation SpMM: A_hat @ X
    x = jnp.asarray(ds.features[pre.perm])
    t0 = time.perf_counter()
    out = spmm_ell(pre.ell, x, impl=args.impl)
    out.block_until_ready()
    print(f"spmm[{args.impl}]: {time.perf_counter() - t0:.2f}s, "
          f"out shape {out.shape}")

    # 3. validate against the scipy oracle
    want = (ds.adj_norm.to_scipy() @ np.asarray(ds.features))[pre.perm]
    err = np.abs(np.asarray(out, np.float64) - want).max()
    print(f"max |err| vs scipy oracle: {err:.2e}")

    # 4. PPA estimate (paper's evaluation vehicle) under the METIS-like
    #    label-propagation edge-cut the benchmarks use
    from repro.core.preprocessing import apply_symmetric_permutation
    from repro.graphs.partition import label_propagation_permutation
    lp = label_propagation_permutation(ds.adj_norm)
    padj = apply_symmetric_permutation(ds.adj_norm, lp)
    fv = simulate_flexvector(padj, ds.spec.feature_dim, HWConfig())
    gl = simulate_grow(padj, ds.spec.feature_dim, GROWConfig())
    print(f"FlexVector : {fv.cycles:.3e} cycles, {fv.energy_j * 1e6:.1f} uJ, "
          f"{fv.area_um2 / 1e3:.1f} K um^2")
    print(f"GROW-like  : {gl.cycles:.3e} cycles, {gl.energy_j * 1e6:.1f} uJ, "
          f"{gl.area_um2 / 1e3:.1f} K um^2")
    print(f"speedup {gl.cycles / fv.cycles:.2f}x, "
          f"energy -{(1 - fv.energy_pj / gl.energy_pj) * 100:.1f}%")


if __name__ == "__main__":
    main()
