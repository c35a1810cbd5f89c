"""Ensure the repo root (for `benchmarks.*`) and src/ are importable when
running `PYTHONPATH=src pytest tests/` from any directory, and give every
test process four virtual CPU devices for the multi-device cases.

pytest imports this file before any test module, so the flag is in
``XLA_FLAGS`` before JAX starts in this process (or in any xdist worker,
each of which imports it too).  Flags already set are kept, and a device
count set by the caller wins.
"""

import os
import sys

_DEVICES_FLAG = "--xla_force_host_platform_device_count"
_flags = os.environ.get("XLA_FLAGS", "")
if _DEVICES_FLAG not in _flags:
    os.environ["XLA_FLAGS"] = f"{_flags} {_DEVICES_FLAG}=4".strip()

_ROOT = os.path.dirname(os.path.abspath(__file__))
for p in (_ROOT, os.path.join(_ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
