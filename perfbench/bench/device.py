"""The chip: compile cache, presence check, identity, peaks, memory.

The benchmark measures a TPU and nothing else: with no TPU, or fewer
chips than the cell asks for, it exits non-zero before any work, with
no CPU or interpreter fallback.
"""

from __future__ import annotations

import json
import os
import sys
from typing import List

# A fixed directory inside the checkout, so that every run of a cell in
# that checkout finds what the first one compiled.  The path is part of
# the cache's key: never a temporary name, a pid or the time.
CACHE_SUBDIR = os.path.join(".cache", "perfbench-jax")


def configure_compile_cache(root: str) -> str:
    """Point JAX's persistent compilation cache into the checkout.

    Must run before JAX is imported: the program's own helper keeps
    ``JAX_COMPILATION_CACHE_DIR`` when it is set, so setting it here makes
    the program take this directory too.  Every program is cached,
    however fast it compiled, so a cell's second run compiles nothing.
    """
    path = os.path.join(root, CACHE_SUBDIR)
    os.makedirs(path, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class NoChip(SystemExit):
    """No TPU, or fewer chips than the cell needs."""

    def __init__(self, msg: str):
        print(f"perfbench: {msg}", file=sys.stderr, flush=True)
        super().__init__(3)


def require_tpu(chips: int) -> List:
    """The first ``chips`` TPU devices; raises :class:`NoChip` otherwise."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no devices: {e}") from e
    if not devs or devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {devs[0].platform if devs else None};"
                     " the benchmark never falls back to the CPU")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX has {len(devs)}")
    return devs[:chips]


def identity(devs) -> dict:
    d0 = devs[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def memory_peak_bytes(devs) -> int:
    """Peak bytes in use on the fullest chip, as the backend reports it."""
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def peaks_for(bench_dir: str, kind: str) -> dict:
    """Published peaks of ``kind`` from ``peaks.json``; a device that is
    not in the table is an error, never a default."""
    with open(os.path.join(bench_dir, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json "
                       f"(have {sorted(table)})")
    return table[kind]
