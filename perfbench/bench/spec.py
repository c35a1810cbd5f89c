"""Find a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix, metric or cell
lives in a file of its own under ``perfbench/``, found by name:

* ``configs/<config>.json``      the configuration as it is run;
* ``references/<reference>.py``  its plain reference (named by the config);
* ``traffic/<traffic>.json``     the traffic mix's parameters;
* ``metrics/<metric>.py``        one reader per metric, end to end or per layer;
* ``limits/<workload>.json``     the limit of each number ``correct`` compares.

So a later cell, mix or metric is new files and new entries, never an
edit of a file that is already here.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class SpecError(ValueError):
    """``BENCHMARK.json`` or a file it names is missing or malformed."""


@dataclasses.dataclass(frozen=True)
class MetricSpec:
    name: str
    unit: str


@dataclasses.dataclass
class Cell:
    workload: str
    chips: int
    config: dict
    traffic: dict
    limits: Dict[str, float]
    end_to_end: List[MetricSpec]
    per_layer: List[MetricSpec]
    bench_dir: str = BENCH_DIR

    def metrics(self, trace: bool) -> List[MetricSpec]:
        return self.per_layer if trace else self.end_to_end


def _read_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError as e:
        raise SpecError(f"missing file {path}") from e
    except json.JSONDecodeError as e:
        raise SpecError(f"{path} is not valid JSON: {e}") from e


def _applies(entry: dict, workload: str) -> bool:
    """A metric without ``workloads`` is reported in every cell."""
    cells = entry.get("workloads")
    return cells is None or workload in cells


def load_cell(root: str, workload: str,
              bench_dir: Optional[str] = None) -> Cell:
    """The cell named ``workload`` in ``<root>/BENCHMARK.json``."""
    bench_dir = bench_dir or BENCH_DIR
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench.get("workloads", [])}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json "
                        f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench.get("configs", [])}
    if w["config"] not in configs:
        raise SpecError(f"workload {workload!r} names unknown config "
                        f"{w['config']!r}")
    config = _read_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _read_json(
        os.path.join(bench_dir, "traffic", f"{w['traffic']}.json"))
    limits = _read_json(os.path.join(bench_dir, "limits", f"{workload}.json"))

    def metric_list(kind: str) -> List[MetricSpec]:
        return [MetricSpec(m["name"], m["unit"])
                for m in bench.get(kind, []) if _applies(m, workload)]

    return Cell(
        workload=workload,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        limits={k: float(v) for k, v in limits["limits"].items()},
        end_to_end=metric_list("end_to_end"),
        per_layer=metric_list("per_layer"),
        bench_dir=bench_dir,
    )


def load_module(path: str, name: str):
    """Import one file by path (metric readers and references have names
    that are not Python identifiers, such as ``mfu.forward``)."""
    if not os.path.isfile(path):
        raise SpecError(f"missing file {path}")
    mod_spec = importlib.util.spec_from_file_location(
        "perfbench_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def metric_reader(bench_dir: str, name: str):
    """The ``read(run) -> float | None`` of ``metrics/<name>.py``."""
    return load_module(os.path.join(bench_dir, "metrics", f"{name}.py"),
                       f"metric_{name}").read


def reference_module(bench_dir: str, config: dict):
    """The plain reference the configuration names."""
    name = config["reference"]
    return load_module(os.path.join(bench_dir, "references", f"{name}.py"),
                       f"reference_{name}")
