"""The trace reduction: busy/idle union, kernel device time, gap naming.

Two inputs: a hand-made event list whose answers are worked out below,
and ``data/v5e_pubmed_full.json``, the events of a short window of
``pubmed-full`` recorded on a v5e (a handful of forwards, trimmed to the
device's ``XLA Ops`` and the harness's spans).
"""

import json
import os

import pytest

from bench import spec, trace

from conftest import BENCH, HERE

T = "/device:TPU:0"
OPS = "XLA Ops"
HOST = "/host:CPU"
KERNEL = 'custom_call_target="tpu_custom_call"'


def ev(plane, name, start, dur, detail=""):
    line = OPS if plane.startswith("/device") else "python"
    return (plane, line, name, float(start), float(dur), detail)


EVENTS = [
    ev(HOST, "perfbench.window", 100, 1000),
    ev(HOST, "perfbench.full_forward", 100, 500),
    ev(HOST, "perfbench.full_forward", 600, 300),
    ev(HOST, "perfbench.submit", 900, 200),
    ev(T, "fusion.1", 50, 100),                 # half before the window
    ev(T, "custom-call.7", 200, 100, KERNEL),
    ev(T, "copy.3", 250, 100),                  # overlaps the kernel
    ev(T, "custom-call.7", 700, 100, KERNEL),
    ev(T, "fusion.1", 1050, 100),               # half after the window
]


def test_busy_is_the_union_inside_the_window():
    s = trace.summarize(EVENTS)
    assert s["window_s"] == pytest.approx(1000e-9)
    # [100,150) + [200,350) + [700,800) + [1050,1100)
    assert s["busy_s"] == pytest.approx(350e-9)
    assert s["chips"] == 1


def test_op_time_is_clipped_to_the_window():
    s = trace.summarize(EVENTS)
    assert s["op_s"]["fusion.1"] == pytest.approx(100e-9)
    assert s["op_s"]["custom-call.7"] == pytest.approx(200e-9)
    assert s["op_n"]["custom-call.7"] == 2
    assert s["op_detail"]["custom-call.7"] == KERNEL


def test_gaps_are_named_by_the_host_span_overlapping_most():
    s = trace.summarize(EVENTS)
    # gaps: [150,200) [350,600) in the first forward; [600,700) in the
    # second; [800,1050) is 100 of the second forward and 150 of submit.
    assert s["idle_s"]["full_forward"] == pytest.approx((50 + 250 + 100) * 1e-9)
    assert s["idle_s"]["submit"] == pytest.approx(250e-9)
    assert sum(s["idle_s"].values()) == pytest.approx(
        s["window_s"] - s["busy_s"])
    b = trace.breakdown(s)
    assert b["idle_gaps"][0][0] == "full_forward"
    assert b["device_ops"][0][0] == "custom-call.7"


def test_two_chips_average_their_busy_time():
    two = EVENTS + [ev("/device:TPU:1", "fusion.2", 100, 1000)]
    s = trace.summarize(two)
    assert s["chips"] == 2
    assert s["busy_s"] == pytest.approx((350e-9 + 1000e-9) / 2)


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError):
        trace.summarize([e for e in EVENTS if e[2] != "perfbench.window"])


def _reader(name):
    return spec.metric_reader(BENCH, name)


class _Run:
    kind, forwards, aggregation_least_s = "full_graph", 2, 50e-9
    peaks = {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}


def test_spmm_roofline_reads_kernel_time_by_name():
    run = _Run()
    run.trace = trace.summarize(EVENTS)
    # least 50 ns a forward, two forwards, 200 ns of kernel time
    assert _reader("spmm_roofline")(run) == pytest.approx(50.0)
    assert _reader("idle_share.forward")(run) == pytest.approx(65.0)
    run.trace = trace.summarize([e for e in EVENTS if "custom" not in e[2]])
    assert _reader("spmm_roofline")(run) is None


RECORDED = os.path.join(HERE, "data", "v5e_pubmed_full.json")


def test_recorded_v5e_trace():
    """Busy time against a 10 ns mask of the same events, kernel time
    against a plain sum of the Pallas launches' clipped durations."""
    import numpy as np

    with open(RECORDED) as f:
        rec = json.load(f)
    events = [tuple(e) for e in rec["events"]]
    s = trace.summarize(events)
    (lo, hi), = [(e[3], e[3] + e[4]) for e in events
                 if e[2] == trace.WINDOW]
    step = 10.0
    mask = np.zeros(int((hi - lo) / step) + 1, dtype=bool)
    kernel_ns, n_dev = 0.0, 0
    for plane, _l, name, start, dur, _d in events:
        if not plane.startswith(trace.DEVICE_PREFIX):
            continue
        n_dev += 1
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            mask[int((a - lo) / step):int(np.ceil((b - lo) / step))] = True
            if 'custom_call_target="tpu_custom_call"' in name:
                kernel_ns += b - a
    assert s["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert s["busy_s"] == pytest.approx(mask.sum() * step * 1e-9,
                                        abs=2 * step * n_dev * 1e-9)
    assert 0.5 < s["busy_s"] / s["window_s"] < 1.0
    assert sum(s["idle_s"].values()) == pytest.approx(
        s["window_s"] - s["busy_s"])
    assert set(s["idle_s"]) <= {"full_forward", "host_unannotated"}
    # two sparse-grid launches a forward
    launches = sum(n for op, n in s["op_n"].items()
                   if "tpu_custom_call" in op)
    assert launches == 2 * rec["forwards"]
    run = _Run()
    run.trace, run.forwards = s, rec["forwards"]
    run.aggregation_least_s = rec["aggregation_least_s"]
    share = _reader("spmm_roofline")(run)
    assert share == pytest.approx(
        rec["aggregation_least_s"] * rec["forwards"] / (kernel_ns * 1e-9)
        * 100)
    assert 0 < share < 100
