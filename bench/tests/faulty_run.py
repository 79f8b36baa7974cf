"""Run ``bench/run_cell.py`` with the timed path broken underneath.

    python bench/tests/faulty_run.py <fault> <run_cell arguments>

Faults, each planted in the query programs the cells serve:
  answer  an answer altered where it is produced (``extract`` adds 1)
  stale   a superstep that returns its state unchanged and votes done
  cold    no warm-up, so the window compiles (the run must exit 3)
"""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH)]
import run_cell as rc  # noqa: E402  (sets the paths)

import jax.numpy as jnp  # noqa: E402

from repro.apps.ppsp import BiBFSProgram  # noqa: E402
from repro.apps.terrain import TerrainSSSP  # noqa: E402

PROGRAMS = (BiBFSProgram, TerrainSSSP)


def answer(cls):
    orig = cls.extract

    def extract(self, state, query):
        out = orig(self, state, query)
        return dict(out, dist=out["dist"] + 1)

    cls.extract = extract


def stale(cls):
    cls.superstep = lambda self, state, ctx: (state, jnp.asarray(True))


def cold(cls):
    rc.warm_up = lambda eng, pairs, capacity: None


if __name__ == "__main__":
    fault = {"answer": answer, "stale": stale, "cold": cold}[sys.argv[1]]
    for cls in PROGRAMS:
        fault(cls)
    sys.exit(rc.main(sys.argv[2:]))
