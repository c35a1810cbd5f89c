"""The whole run on the CPU at a small size, past the harness's look for a
chip: a sound program is correct; its int8 control and planted faults
are not; a cell, a mix or a metric is added by files and entries alone;
and the command itself refuses to run without a TPU.
"""

import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from conftest import BENCH, OPEN_LOOP, ROOT, small_cell

# The Pallas kernels' interpreter is slow on the CPU: the small runs use
# the program's own reference SpMM path, and the kernels are covered by
# test_compile_v5e.py and by the chip.
FAST = dict(spmm_impl="reference", interpret=True)


def run(cell, seed=2**40 + 9, seconds=0.5, traced=False, **over):
    from bench import runner

    return runner.run_cell(cell, seed, seconds, traced, time.perf_counter(),
                           overrides=dict(FAST, **over))


ALL = [("pubmed-full", None), ("citeseer-full", None),
       ("pubmed-query-closed", None), OPEN_LOOP]


@pytest.mark.parametrize("workload,mix", ALL)
def test_sound_program_is_correct(workload, mix):
    res = run(small_cell(workload, mix))
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    names = set(res["metrics"])
    assert "setup_s" in names and len(names) >= 2


@pytest.mark.parametrize("workload,mix", ALL)
def test_int8_control_is_not_correct(workload, mix):
    res = run(small_cell(workload, mix), precision="int8")
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("workload,mix", [("pubmed-full", None), OPEN_LOOP])
def test_bf16_storage_computes_the_stated_products(workload, mix):
    """bf16 storage rounds exactly the operands the chip's default
    precision rounds, so it meets the bf16-product reference."""
    res = run(small_cell(workload, mix), precision="bf16")
    assert res["correct"], res["checks"]


def test_altered_full_graph_answer_is_not_correct(monkeypatch):
    from repro.serve.engine import ServeEngine

    real = ServeEngine.full_forward

    def altered(self):
        out = real(self).copy()
        out[17, 1] += 0.05 * np.abs(out).max()
        return out

    monkeypatch.setattr(ServeEngine, "full_forward", altered)
    assert not run(small_cell("pubmed-full"))["correct"]


@pytest.mark.parametrize("workload,mix",
                         [("pubmed-query-closed", None), OPEN_LOOP])
def test_altered_query_answer_is_not_correct(monkeypatch, workload, mix):
    from repro.serve.batcher import MicroBatcher

    real = MicroBatcher.run

    def altered(self, params, reqs):
        outs = real(self, params, reqs)
        outs[0] = outs[0] + 0.05 * np.abs(outs[0]).max()
        return outs

    monkeypatch.setattr(MicroBatcher, "run", altered)
    assert not run(small_cell(workload, mix), seconds=1.0)["correct"]


def test_traced_run_reports_per_layer_metrics():
    res = run(small_cell("pubmed-query-closed"), traced=True)
    assert {"prep_ms", "batch_fill"} <= set(res["metrics"])
    assert "busy_s" in res["device"] and "window_s" in res["device"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def _copy_benchmark(tmp_path):
    """The benchmark's files alone, as a checkout of them would hold."""
    dst = tmp_path / "checkout"
    shutil.copytree(BENCH, dst / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", ".cache"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    return dst


def test_new_config_traffic_and_metric_are_files_and_entries(tmp_path):
    from bench import runner, spec

    root = _copy_benchmark(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    pb = root / "perfbench"
    cfg = json.loads((pb / "configs" / "gcn2-pubmed.json").read_text())
    cfg.update(name="gcn2-cora", dataset="cora", nodes=2708, edges=5429,
               feature_dim=1433, classes=7)
    (pb / "configs" / "gcn2-cora.json").write_text(json.dumps(cfg))
    mix = json.loads((pb / "traffic" / "query-closed.json").read_text())
    mix.update(clients=2, pool_size=200, warm_requests=4, warm_s=0.3,
               fanout=8)
    (pb / "traffic" / "query-pair.json").write_text(json.dumps(mix))
    (pb / "limits" / "cora-query-pair.json").write_text(
        json.dumps({"limits": {"logit_gap": 1e-4}}))
    (pb / "metrics" / "answered.py").write_text(
        "def read(run):\n"
        "    return sum(r.status == 'ok' for r in run.records)\n")
    bench["configs"].append(dict(bench["configs"][0], name="gcn2-cora",
                                 file="perfbench/configs/gcn2-cora.json"))
    bench["workloads"].append({"name": "cora-query-pair",
                               "config": "gcn2-cora",
                               "traffic": "query-pair", "chips": 1,
                               "why": "a test cell"})
    bench["end_to_end"].append({"name": "answered", "unit": "req",
                                "better": "higher", "bound": 0.1,
                                "source": "host_clock",
                                "workloads": ["cora-query-pair"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load_cell(str(root), "cora-query-pair", bench_dir=str(pb))
    res = runner.run_cell(cell, 5, 0.5, False, time.perf_counter(),
                          overrides=FAST)
    assert res["correct"], res["checks"]
    assert res["metrics"]["answered"]["value"] > 0
    assert "setup_s" in res["metrics"]


def _command(cwd, *extra_env):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "pubmed-full",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_exits_nonzero_without_a_tpu():
    out = _command(ROOT)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert not out.stdout.strip()


def test_command_exits_nonzero_with_only_the_benchmark(tmp_path):
    out = _command(_copy_benchmark(tmp_path))
    assert out.returncode != 0
    assert not out.stdout.strip()
