"""The per-layer metrics read from the program's own spans and counters:
every one is reported, finite, in a traced run of its cell, none in an
untraced run; and on a trace recorded on a v5e the program's round phases
name every idle gap of the device, on the profiler's one clock."""
import json
import math
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench.harness import spec
from bench.harness import trace as tr

RUN = str(spec.BENCH / "run_cell.py")
RECORDED = Path(__file__).with_name("v5e_program_trace_excerpt.json")
PHASES = ("quegel.admit", "quegel.dispatch", "quegel.sync", "quegel.retire")


def program_metrics(workload: str) -> set:
    """The cell's per-layer metrics whose readers read the program's
    tracing records (``bench/harness/program.py``)."""
    cell = spec.Cell(spec.benchmark(), workload)
    out = set()
    for m, reader in zip(cell.per_layer, cell.readers(cell.per_layer)):
        if getattr(reader, "program", None) is not None:
            out.add(m["name"])
    return out


def run_cell(workload: str, trace: int) -> dict:
    cell = spec.Cell(spec.benchmark(), workload)
    seconds = "0.5" if cell.traffic["loop"] == "closed" else "4"
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", workload, "--seed", "3000000019",
         "--seconds", seconds, "--trace", str(trace), "--tiny"],
        cwd=spec.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("workload",
                         [w["name"] for w in spec.benchmark()["workloads"]])
def test_traced_run_reports_the_program_metrics(workload):
    names = program_metrics(workload)
    assert names, "every cell reads at least one of the program's records"
    traced = run_cell(workload, 1)
    assert traced["correct"] is True
    for name in names:
        value = traced["metrics"][name]["value"]
        assert math.isfinite(value) and value > 0, (name, value)
    untraced = run_cell(workload, 0)
    assert not names & set(untraced["metrics"])


def test_round_phases_name_every_idle_gap_of_a_recorded_v5e_trace():
    rec = json.loads(RECORDED.read_text())
    out = tr.reduce(rec["trace"], labels=PHASES)
    assert out["window_s"] == pytest.approx(rec["hand"]["window_s"])
    assert out["busy_s"] == pytest.approx(rec["hand"]["busy_s"])
    gaps = dict(out["idle_gaps"])
    assert gaps == pytest.approx(rec["hand"]["idle_gaps"])
    assert set(gaps) <= set(PHASES) and gaps
    # by the benchmark's own spans the same gaps all fall under one
    assert {n for n, _ in tr.reduce(rec["trace"])["idle_gaps"]} == {"pump"}
