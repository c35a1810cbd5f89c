"""The comparison that decides ``correct``.

One number per cell, ``logit_gap``: the widest gap between an answer the
timed path produced and the f64 reference, over every answer checked,
as a share of the reference's largest magnitude.  The reference is taken
twice: exact, and at the configuration's stated arithmetic, every
product's operands rounded to bfloat16 (the TPU's default precision for
an f32 matmul, in XLA and in Pallas kernels alike).  The gap is the
smaller of the two, so a program whose products run at the chip's
default precision and one that runs them exactly both pass, while one
that computes below it (int8 values or weights) fails.  What is left
between a sound program and the bf16-product reference is f32
accumulation, and the rare bf16 rounding it flips.  The features and
weights are drawn exactly representable in bfloat16
(``bench/inputs.py``), so the two references differ only where the
graph's values and the program's own activations enter a product.
"""

from __future__ import annotations

import math
from typing import Dict, Sequence

import numpy as np


def widest_gap(outs: Sequence[np.ndarray], refs: Sequence[np.ndarray]
               ) -> float:
    """``max |out - ref| / max |ref|`` over all pairs; ``inf`` where an
    answer is missing, misshapen or not finite."""
    if not outs or len(outs) != len(refs):
        return math.inf
    scale = max(float(np.max(np.abs(r))) for r in refs)
    gap = 0.0
    for o, r in zip(outs, refs):
        o = np.asarray(o, dtype=np.float64)
        if o.shape != r.shape or not np.all(np.isfinite(o)):
            return math.inf
        gap = max(gap, float(np.max(np.abs(o - r))))
    return gap / max(scale, 1e-30)


def logit_gap(outs, refs_exact, refs_bf16) -> float:
    return min(widest_gap(outs, refs_exact), widest_gap(outs, refs_bf16))


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Every number at or under its limit, and every limit read."""
    return set(numbers) == set(limits) and all(
        numbers[k] <= limits[k] for k in limits)


def checks_block(numbers: Dict[str, float], limits: Dict[str, float]
                 ) -> Dict[str, dict]:
    return {k: {"value": numbers.get(k, math.inf), "limit": limits[k]}
            for k in sorted(limits)}
