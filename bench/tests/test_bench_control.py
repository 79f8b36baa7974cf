"""``correct`` separates the program from its control and from planted
faults: at tiny sizes on the CPU, on three seeds, the program passes every
limit and the control fails one; a run with the timed path broken reads
``correct`` false."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench.harness import spec

BENCH = spec.benchmark()
CONFIGS = [c["name"] for c in BENCH["configs"]]
FIRST_CELL = {c: next(w["name"] for w in BENCH["workloads"]
                      if w["config"] == c) for c in CONFIGS}


def tiny(config):
    cell = spec.Cell(BENCH, FIRST_CELL[config])
    cfg = {k: v for k, v in cell.config.items() if k != "tiny"}
    cfg.update(cell.config["tiny"])
    return cell.deployment, cfg


def failing(checks) -> bool:
    return any(v > lim for v, lim in checks.values())


@pytest.mark.parametrize("config", CONFIGS)
@pytest.mark.parametrize("seed", [3, 2**31 + 7, 123456789])
def test_program_passes_and_control_fails(config, seed):
    dep, cfg = tiny(config)
    data = dep.build(cfg, np.random.default_rng([seed, 0]))
    pairs = dep.pool(data, cfg, np.random.default_rng([seed, 1]))
    # and a few uniform pairs, so the control is not judged on the pool alone
    rng = np.random.default_rng([seed, 2])
    n = int(np.max(data.dst)) + 1
    pairs += [rng.integers(0, n, 2).astype(np.int32) for _ in range(32)]
    ref = dep.reference(data, cfg, pairs)
    eng = dep.make_engine(data, cfg)
    qids = [eng.submit(p) for p in pairs]
    eng.run_until_drained()
    served = [dep.served(eng.poll(q)[1]) for q in qids]
    program, wrong = dep.compare(served, ref, cfg["limits"])
    assert not failing(program) and wrong == 0, program
    control, _ = dep.compare(dep.control(data, cfg, pairs, ref), ref,
                             cfg["limits"])
    assert failing(control), control


def faulty_run(fault, config):
    return subprocess.run(
        [sys.executable, str(spec.BENCH / "tests" / "faulty_run.py"), fault,
         "--workload", FIRST_CELL[config], "--seed", "5", "--seconds", "0.5",
         "--trace", "0", "--tiny"],
        cwd=spec.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=240)


@pytest.mark.parametrize("config", CONFIGS)
@pytest.mark.parametrize("fault", ["answer", "stale"])
def test_broken_timed_path_reads_incorrect(config, fault):
    proc = faulty_run(fault, config)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is False
    assert line["failed"] > 0


def test_window_that_compiles_exits_nonzero():
    proc = faulty_run("cold", CONFIGS[0])
    assert proc.returncode == 3, proc.stderr[-3000:]
    assert proc.stdout.strip() == ""
    assert "the window compiled" in proc.stderr
