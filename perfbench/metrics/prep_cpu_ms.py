"""Mean CPU time of the submitting thread per prepare (sampling,
vertex-cut, bucket padding), in ms, from the program's span counters
over the window: ``prepare_cpu_ns`` over ``prepare_n``.  Beside
``prep_ms`` (wall) it splits a prepare into work and waiting."""


def read(run):
    n = run.counters.get("prepare_n", 0)
    if not n:
        return None
    return run.counters.get("prepare_cpu_ns", 0) / n / 1e6
