"""Mean time per super-round in its done/step readback (``quegel.sync``,
the round's one barrier: the wait for the device), from the program's
spans."""
from bench.harness import program


def read(run):
    return program.span_ms_per_round(run, "quegel.sync")
