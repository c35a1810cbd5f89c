"""Median query latency in ms over every request due in the window, from
its due time to its answer; a refused or shed request counts as slower
than every answered one (host clock)."""

from bench.drivers import percentile, rank_latencies


def read(run):
    if run.kind == "full_graph" or not run.records:
        return None
    return percentile(rank_latencies(run.records), 50)
