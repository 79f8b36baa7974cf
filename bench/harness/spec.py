"""Resolve a cell of ``BENCHMARK.json`` to its files, by name.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own, so a later cell or metric is added by
adding files and entries, never by editing one that is there:

  configuration  the ``file`` of its ``configs`` entry (sizes, limits), and
                 ``bench/configs/<deployment>.py`` named inside it (graph
                 generator, engine factory, query sampler, plain reference)
  traffic        ``bench/traffic/<traffic>.json``
  metric         ``bench/metrics/<name>.py`` for each metric, with
                 ``read(run) -> float | None``; where there is none, the
                 reader of the name up to its first '.', so that
                 ``device_idle.kron`` and ``device_idle.steady`` (one
                 quantity, split by the end-to-end metric it moves) share
                 ``device_idle.py``
"""
from __future__ import annotations

import importlib.util
import json
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"


def load_json(path) -> dict:
    return json.loads(Path(path).read_text())


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def load_module(path: Path):
    """Import a file by path (names may hold '.' and '-')."""
    path = Path(path)
    if not path.is_file():
        raise FileNotFoundError(path)
    name = "bench_" + re.sub(r"\W", "_", str(path.relative_to(BENCH)))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


class Cell:
    """One ``workloads`` entry with its configuration, traffic, deployment
    module and the metrics it reports."""

    def __init__(self, bench: dict, workload: str):
        try:
            self.workload = next(w for w in bench["workloads"]
                                 if w["name"] == workload)
        except StopIteration:
            raise KeyError(f"no workload named {workload!r}") from None
        self.name = workload
        self.chips = int(self.workload["chips"])
        entry = next(c for c in bench["configs"]
                     if c["name"] == self.workload["config"])
        self.config = load_json(ROOT / entry["file"])
        self.traffic = load_json(BENCH / "traffic" /
                                 f"{self.workload['traffic']}.json")
        self.deployment = load_module(
            BENCH / "configs" / f"{self.config['deployment']}.py")
        self.end_to_end = [m for m in bench["end_to_end"]
                           if applies(m, workload)]
        self.per_layer = [m for m in bench["per_layer"]
                          if applies(m, workload)]

    @staticmethod
    def readers(metrics: list) -> list:
        """The reader module of each metric: ``bench/metrics/<name>.py``,
        else that of the name up to its first '.'."""
        out = []
        for m in metrics:
            path = BENCH / "metrics" / f"{m['name']}.py"
            if not path.is_file():
                path = path.with_name(f"{m['name'].split('.')[0]}.py")
            out.append(load_module(path))
        return out
