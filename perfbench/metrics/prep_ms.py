"""Median host time an answered window request spent in ``submit``'s
prepare (sampling, vertex-cut, bucket padding), in ms (the runtime's own
``Request.prep_s``)."""

import statistics


def read(run):
    preps = [r.prep_s for r in run.records
             if r.status == "ok" and r.prep_s is not None]
    return statistics.median(preps) * 1e3 if preps else None
