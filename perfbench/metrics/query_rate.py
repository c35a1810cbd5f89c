"""Answers completed inside the window over the window's seconds
(requests/s, host clock)."""


def read(run):
    if run.kind == "full_graph":
        return None
    hi = run.window_t0 + run.window_s
    done = sum(r.status == "ok" and r.done <= hi for r in run.records)
    return done / run.window_s if done else None
