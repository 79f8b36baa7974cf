"""Process start to the window's start: generation, engine build,
compilation or cache loads, and warm-up."""


def read(run):
    return run.setup_s
