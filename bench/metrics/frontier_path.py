"""Share of the window's propagate calls that read only the frontier's
out-arcs (``quegel.frontier_path`` over ``quegel.propagate_calls``, the
program's per-round counters), in percent: how often the coo plan's
frontier pass, rather than its dense pass over every arc, served a call."""
from bench.harness import program


def read(run):
    took = program.counter_sum(run, "quegel.frontier_path")
    calls = program.counter_sum(run, "quegel.propagate_calls")
    if took is None or not calls:
        return None
    return 100.0 * took / calls
