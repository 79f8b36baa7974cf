"""Arcs the live slots' frontiers lead out of, as a share of the arcs the
window's propagate calls read (``quegel.frontier_arcs`` over
``quegel.arcs_read``, the program's per-round counters): how much of what
the round reads the queries need."""
from bench.harness import program


def read(run):
    need = program.counter_sum(run, "quegel.frontier_arcs")
    read_ = program.counter_sum(run, "quegel.arcs_read")
    if need is None or not read_:
        return None
    return 100.0 * need / read_
