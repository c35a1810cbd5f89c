"""Full-graph forward time: the window's seconds over the full forwards
completed in it, in ms (host clock; every forward ends when its logits
are on the host)."""


def read(run):
    if run.kind != "full_graph" or not run.forwards:
        return None
    return run.window_s / run.forwards * 1e3
