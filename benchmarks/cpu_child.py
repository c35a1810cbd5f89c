"""Run a bench body in a child process on virtual CPU devices.

The sharded, plan and pipeline benches need several devices, which the
CPU backend can fake.  Their children are forced onto the CPU whatever
the parent runs on, and say so on their first output line: their times
are CPU times, never chip times.  Importing this module imports no JAX,
so a parent that starts children from it does not hold the chip.
"""

from __future__ import annotations

import os
import subprocess
import sys

N_VIRTUAL_DEVICES = 8
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_child(script: str, json_path: str, smoke: bool, csv, name: str):
    """Run ``script --child`` on the CPU and relay its output to ``csv``."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") +
        f" --xla_force_host_platform_device_count={N_VIRTUAL_DEVICES}"
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(_ROOT, "src"), _ROOT, env.get("PYTHONPATH", "")])
    cmd = [sys.executable, os.path.abspath(script), "--child",
           "--json", json_path, "--smoke" if smoke else "--full"]
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=1800)
    for line in (r.stdout or "").strip().splitlines():
        csv(line)
    if r.returncode != 0:
        tail = (r.stderr or "").strip().splitlines()[-3:]
        raise RuntimeError(f"{name} bench child failed: {' | '.join(tail)}")


def print_device_line() -> None:
    """A child's first output line: the devices its numbers come from."""
    import jax

    devs = jax.devices()
    print(f"# device: {devs[0].platform} x{len(devs)} virtual "
          "(forced CPU: times below are CPU times, not chip times)")
