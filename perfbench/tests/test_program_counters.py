"""The readers of the program's span counters: ``prep_cpu_ms``,
``exec_ms`` and ``exec_cpu_ms``.

They read the window's deltas of the runtime's ``prepare_*`` and
``execute_*`` counters (``Run.counters``), and read nothing where there
is nothing: no requests, a full-graph run, or a program without the
counters.

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests/test_program_counters.py
"""

import pytest

from bench import runner, spec
from conftest import BENCH, ROOT, small_cell

READERS = ("prep_cpu_ms", "exec_ms", "exec_cpu_ms")


def _reader(name):
    return spec.metric_reader(BENCH, name)


def _run(kind="closed_loop", **counters):
    return runner.Run(kind=kind, setup_s=1.0, window_s=10.0,
                      counters=counters, max_batch=8)


def test_readers_divide_by_their_counts():
    run = _run(prepare_n=4, prepare_ns=40_000_000,
               prepare_cpu_ns=10_000_000, execute_n=2,
               execute_ns=30_000_000, execute_cpu_ns=5_000_000)
    assert _reader("prep_cpu_ms")(run) == pytest.approx(2.5)
    assert _reader("exec_ms")(run) == pytest.approx(15.0)
    assert _reader("exec_cpu_ms")(run) == pytest.approx(2.5)


@pytest.mark.parametrize("name", READERS)
def test_zero_counts_read_nothing(name):
    run = _run(prepare_n=0, prepare_ns=0, prepare_cpu_ns=0, execute_n=0,
               execute_ns=0, execute_cpu_ns=0, completed=0)
    assert _reader(name)(run) is None


@pytest.mark.parametrize("name", READERS)
def test_full_graph_runs_read_nothing(name):
    assert _reader(name)(_run(kind="full_graph")) is None


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_counters_reads_nothing(name):
    assert _reader(name)(_run(completed=12, batches_full=3)) is None


def test_the_metrics_are_declared_for_the_query_cell_alone():
    cell = spec.load_cell(ROOT, "pubmed-query-closed")
    names = {m.name for m in cell.metrics(True)}
    assert set(READERS) <= names
    full = spec.load_cell(ROOT, "pubmed-full")
    assert not set(READERS) & {m.name for m in full.metrics(True)}


def test_traced_query_run_reports_work_within_wall_time():
    from test_harness import run

    res = run(small_cell("pubmed-query-closed"), traced=True)
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(READERS) <= set(m)
    assert 0 < m["prep_cpu_ms"]
    assert 0 < m["exec_cpu_ms"] <= m["exec_ms"] * 1.05
