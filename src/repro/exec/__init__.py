"""Execution-plan layer: one planned pipeline behind every SpMM entry point.

Before this package existed the repo had three divergent SpMM paths
(``spmm_ell``, ``spmm_ell_arrays`` and the serving batcher's AOT trace),
each with its own pad/dispatch/segment-accumulate copy.  ``repro.exec``
captures all launch decisions once in an :class:`SpmmPlan` — impl choice,
block sizes, interpret mode, device placement — and funnels every caller
through a single :func:`execute` path that runs single-device or sharded
over the ``data`` mesh axis from the same code:

* ``plan``     — :class:`SpmmPlan` (+ :func:`plan_for_config`) and the
                 impl-resolution rules, including the recorded
                 ``pallas_sparse`` -> ``pallas`` degradation under trace;
* ``operands`` — :class:`SpmmOperands` (array triple + optional host
                 :class:`~repro.core.sparse_formats.TiledELL` for grid
                 scheduling) and the per-shard sub-row splitter;
* ``dispatch`` — :func:`execute`, the one pad/dispatch/segment-accumulate
                 implementation shared by all entry points, and
                 :func:`execute_layer`, the layer-level entry that runs
                 combination + aggregation as two launches;
* ``sharded``  — :func:`execute_sharded`, ``shard_map`` over the ``data``
                 axis with a pluggable epilogue: ``segment_psum``
                 (replicated output) or ``segment_reduce_scatter``
                 (row-sharded output for a following sharded layer), plus
                 optional feature-axis sharding of the dense operand;
* ``pipeline`` — :class:`GcnPipelinePlan` / :func:`plan_pipeline` /
                 :func:`pipeline_forward`: joint planning of a whole GCN
                 stack — per-layer impl/blocks, one data-mesh width, and
                 the activation layout at every layer boundary — so
                 activations stay sharded end-to-end;
* ``quant``    — storage-precision policy (f32 | bf16 | int8): symmetric
                 per-row-block int8 quantization with exact dequant,
                 bf16 casting for values/activations/weights, and the
                 :class:`~repro.exec.quant.QuantizedELL` host artifact
                 the registry caches — kernels always accumulate in f32.

Layering: ``exec`` imports ``core``, ``kernels`` and ``dist``; ``core``
reaches back only through deferred imports inside ``spmm_ell`` /
``spmm_ell_arrays`` so the import graph stays acyclic.
"""

from repro.exec.plan import (
    SpmmPlan,
    plan_for_config,
    reset_degradation_warnings,
)
from repro.exec import quant
from repro.exec.quant import QuantizedELL, quantize_ell
from repro.exec.operands import ShardedOperands, SpmmOperands, shard_operands
from repro.exec.dispatch import (
    execute,
    execute_layer,
    prepare_precision,
    sub_row_products,
)
from repro.exec.sharded import execute_sharded
from repro.exec.pipeline import (
    GcnPipelinePlan,
    LayerPlan,
    chain_layouts,
    pipeline_forward,
    plan_pipeline,
    static_pipeline,
)

__all__ = [
    "GcnPipelinePlan",
    "LayerPlan",
    "QuantizedELL",
    "chain_layouts",
    "static_pipeline",
    "ShardedOperands",
    "SpmmOperands",
    "SpmmPlan",
    "execute",
    "execute_layer",
    "execute_sharded",
    "pipeline_forward",
    "plan_for_config",
    "plan_pipeline",
    "prepare_precision",
    "quant",
    "quantize_ell",
    "reset_degradation_warnings",
    "shard_operands",
    "sub_row_products",
]
