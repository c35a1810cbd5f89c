"""Median time an answered window request spent queued, from admission
to the close of its batch, in ms (the runtime's own ``Request.wait_s``)."""

import statistics


def read(run):
    waits = [r.wait_s for r in run.records
             if r.status == "ok" and r.wait_s is not None]
    return statistics.median(waits) * 1e3 if waits else None
