"""Reduce a JAX profiler trace to busy time, kernel time and idle gaps.

The window is the host span ``perfbench.window`` the harness writes
around the measured loop.  Device work is every event on an ``XLA Ops``
line of a ``/device:TPU:<n>`` plane; busy time is the union of those
intervals inside the window, averaged over the chips.  Each idle gap on
a chip is named after the harness span (``perfbench.*``) that overlaps
it most, which says what the host was doing meanwhile.

Events are plain tuples ``(plane, line, name, start_ns, dur_ns, detail)``
so that the reduction runs, and is tested, without a chip.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Sequence, Tuple

Event = Tuple[str, str, str, float, float, str]

DEVICE_PREFIX = "/device:TPU:"
DEVICE_LINES = ("XLA Ops",)
HOST_PREFIX = "perfbench."
WINDOW = "perfbench.window"


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load_events(path: str) -> List[Event]:
    """Device op events and the harness's host spans of one trace."""
    from jax.profiler import ProfileData

    out: List[Event] = []
    details: Dict[str, str] = {}
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if device and line.name not in DEVICE_LINES:
                continue
            for ev in line.events:
                name = ev.name
                if not device and not name.startswith(HOST_PREFIX):
                    continue
                detail = ""
                if device:
                    # One op name is one HLO instruction: read its string
                    # stats once, not for every launch.
                    detail = details.get(name)
                    if detail is None:
                        detail = details[name] = " ".join(
                            str(v) for _k, v in ev.stats
                            if isinstance(v, str))
                out.append((plane.name, line.name, name,
                            float(ev.start_ns), float(ev.duration_ns),
                            detail))
    return out


def merge(intervals: Iterable[Tuple[float, float]], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    """Union of ``(start, end)`` intervals clipped to ``[lo, hi]``."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if at < hi:
        out.append((at, hi))
    return out


def _name_gaps(gap_list: Sequence[Tuple[float, float]],
               spans: Sequence[Tuple[str, float, float]], weight: float,
               idle: Dict[str, float]) -> None:
    """Add each gap's seconds (times ``weight``) to the harness span that
    overlaps it most; ``gap_list`` and ``spans`` are sorted by start.  One
    sweep: a span that ends before a gap starts overlaps no later gap."""
    active: List[Tuple[str, float, float]] = []
    nxt = 0
    for g0, g1 in gap_list:
        while nxt < len(spans) and spans[nxt][1] < g1:
            active.append(spans[nxt])
            nxt += 1
        active = [sp for sp in active if sp[2] > g0]
        best, best_ov = "host_unannotated", 0.0
        for name, s, e in active:
            ov = min(e, g1) - max(s, g0)
            if ov > best_ov:
                best, best_ov = name, ov
        idle[best] += (g1 - g0) * 1e-9 * weight


def summarize(events: Sequence[Event]) -> dict:
    """Busy and window seconds, per-op device seconds inside the window,
    and idle seconds by what the host was doing."""
    windows = [(s, s + d) for _p, _l, n, s, d, _x in events if n == WINDOW]
    if not windows:
        raise ValueError(f"trace has no {WINDOW} span")
    lo, hi = windows[0]
    spans = sorted(((n[len(HOST_PREFIX):], s, s + d)
                    for _p, _l, n, s, d, _x in events
                    if n.startswith(HOST_PREFIX) and n != WINDOW
                    and s < hi and s + d > lo), key=lambda sp: sp[1])
    per_chip: Dict[str, list] = defaultdict(list)
    op_s: Dict[str, float] = defaultdict(float)
    op_n: Dict[str, int] = defaultdict(int)
    op_detail: Dict[str, str] = {}
    for plane, _line, name, s, d, detail in events:
        if not plane.startswith(DEVICE_PREFIX):
            continue
        e = s + d
        if e <= lo or s >= hi:
            continue
        per_chip[plane].append((s, e))
        op_s[name] += (min(e, hi) - max(s, lo)) * 1e-9
        op_n[name] += 1
        op_detail.setdefault(name, detail)
    window_s = (hi - lo) * 1e-9
    busy, idle = [], defaultdict(float)
    for plane in sorted(per_chip):
        merged = merge(per_chip[plane], lo, hi)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        _name_gaps(gaps(merged, lo, hi), spans, 1.0 / len(per_chip), idle)
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "chips": len(per_chip),
        "op_s": dict(op_s),
        "op_n": dict(op_n),
        "op_detail": op_detail,
        "idle_s": dict(idle),
    }


def breakdown(summary: dict, top: int = 10) -> dict:
    ops = sorted(summary["op_s"].items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(summary["idle_s"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle]}
