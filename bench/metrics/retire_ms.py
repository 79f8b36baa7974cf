"""Mean time per super-round in retirement (``quegel.retire``: eviction,
the ``extract_all`` dispatch and readback, bookkeeping, cache and
journal), from the program's spans."""
from bench.harness import program


def read(run):
    return program.span_ms_per_round(run, "quegel.retire")
