"""Every cell runs end to end at its tiny sizes on the CPU through the same
code as on the chip, and without a TPU the harness refuses to measure."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench.harness import spec

RUN = str(spec.BENCH / "run_cell.py")
CELLS = [w["name"] for w in spec.benchmark()["workloads"]]
REQUIRED = ("correct", "attempted", "failed", "metrics", "device")


def run_cell(*args, cwd=spec.ROOT, timeout=240):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, RUN, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


def last_line(proc) -> dict:
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_tiny(workload):
    cell = spec.Cell(spec.benchmark(), workload)
    # an open loop needs a few arrivals at its rate; a closed one is full
    seconds = "0.5" if cell.traffic["loop"] == "closed" else "4"
    line = last_line(run_cell("--workload", workload, "--seed", "2147483653",
                              "--seconds", seconds, "--trace", "0", "--tiny"))
    for key in REQUIRED:
        assert key in line
    assert list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    for m in cell.end_to_end:
        assert line["metrics"][m["name"]]["unit"] == m["unit"]
        assert line["metrics"][m["name"]]["value"] > 0
    assert line["device"]["platform"] == "cpu"


def test_traced_run_reports_per_layer_metrics():
    """On the CPU the trace holds no device plane: the device metrics are
    left out, the program's own spans and counters are read."""
    line = last_line(run_cell("--workload", CELLS[0], "--seed", "11",
                              "--seconds", "0.5", "--trace", "1", "--tiny"))
    assert line["correct"] is True
    assert "round_ms.kron" in line["metrics"]
    assert "device_idle.kron" not in line["metrics"]


def test_no_tpu_exits_nonzero_without_a_result():
    proc = run_cell("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                    "--trace", "0")
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_bare_benchmark_directory_exits_nonzero(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's paths has
    no system to measure."""
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    for p in spec.benchmark()["paths"]:
        shutil.copytree(spec.ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "bench/run_cell.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0", "--tiny"],
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu",
                               PYTHONPATH=""),
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
