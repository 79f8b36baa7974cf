"""Mean time per super-round in admission (``quegel.admit``: retry release,
scheduler pops, validation, preemption), from the program's spans."""
from bench.harness import program


def read(run):
    return program.span_ms_per_round(run, "quegel.admit")
