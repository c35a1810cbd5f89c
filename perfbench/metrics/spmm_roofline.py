"""Aggregation kernels' share of their roofline, in %: the least time the
chip could take for the window's aggregations (per layer the larger of
operations over peak FLOP/s and bytes over peak bandwidth, from
``bench/work.py``) over the summed device time of the SpMM kernel
launches in the trace.

The kernels carry no name of their own yet: a v5e trace shows each
Pallas launch as an ``XLA Ops`` event whose name is its HLO instruction,
named after the enclosing function (``_lambda_`` in the full-graph step,
``fwd`` in a bucket step), with ``custom_call_target="tpu_custom_call"``.
The program's only Pallas kernels are its four SpMM kernels (dense grid,
sparse grid, two fused), so every such launch counts.  With none found
the metric is left out.
"""

import re

KERNELS = re.compile(r'custom_call_target="tpu_custom_call"')


def read(run):
    if run.trace is None or not run.forwards or not run.peaks:
        return None
    detail = run.trace["op_detail"]
    kernel_s = sum(s for name, s in run.trace["op_s"].items()
                   if KERNELS.search(name) or KERNELS.search(detail[name]))
    if kernel_s <= 0:
        return None
    return run.aggregation_least_s * run.forwards / kernel_s * 100
