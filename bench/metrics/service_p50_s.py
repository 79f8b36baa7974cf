"""Median service time, from a query's first admission to the end of the
super-round that retired it (the program's ``quegel.query`` records), over
the queries ``latency_p50_s`` counts."""
import numpy as np

from bench.harness import program


def read(run):
    recs = program.queries(run)
    if not recs:
        return None
    return float(np.median([(r.done_ns - r.admit_ns) / 1e9 for r in recs]))
