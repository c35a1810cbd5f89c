"""Mean CPU time of the runtime's worker thread per executed batch, in
ms, from the program's span counters over the window:
``execute_cpu_ns`` over ``execute_n``.  Beside ``exec_ms`` it says how
much of a batch the worker spends working and how much waiting."""


def read(run):
    n = run.counters.get("execute_n", 0)
    if not n:
        return None
    return run.counters.get("execute_cpu_ns", 0) / n / 1e6
