"""Whole full-graph step's share of the chip's bf16 peak, in %: the
algorithmic operations of one forward (``bench/work.py``) times the
forwards per second of the traced window, over the peak."""


def read(run):
    if run.kind != "full_graph" or not run.forwards or not run.peaks:
        return None
    rate = run.forwards / run.window_s
    return run.flops_per_forward * rate / run.peaks["bf16_flops_per_s"] * 100
