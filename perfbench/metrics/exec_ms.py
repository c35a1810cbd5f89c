"""Mean wall time per executed batch (stack, the executable call with
its copies, the fetch of the answers), in ms, from the program's span
counters over the window: ``execute_ns`` over ``execute_n``."""


def read(run):
    n = run.counters.get("execute_n", 0)
    if not n:
        return None
    return run.counters.get("execute_ns", 0) / n / 1e6
