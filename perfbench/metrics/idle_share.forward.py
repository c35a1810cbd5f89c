"""Share of the traced full-graph window in which no operation ran on the
chip, in % (device trace: 1 - busy / window, averaged over the chips)."""


def read(run):
    if run.trace is None or run.kind != "full_graph":
        return None
    t = run.trace
    return (1.0 - t["busy_s"] / t["window_s"]) * 100
