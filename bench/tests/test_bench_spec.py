"""BENCHMARK.json resolves by name: every cell's configuration, traffic,
deployment module and metric readers exist, and the file keeps to the
benchmark's contract on names, units and keys.  A later PR that adds a
cell or a metric is checked by the same tests."""
import re

import pytest

from bench.harness import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = spec.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert (spec.ROOT / p).is_dir()
    assert (spec.ROOT / BENCH["command"][1]).is_file()
    assert BENCH["command"][1].startswith(tuple(BENCH["paths"]))


@pytest.mark.parametrize("workload", CELLS)
def test_cell_resolves(workload):
    cell = spec.Cell(BENCH, workload)
    assert cell.chips in (1, 4)
    for fn in ("build", "make_engine", "pool", "served", "reference",
               "control", "compare"):
        assert callable(getattr(cell.deployment, fn)), fn
    assert cell.traffic["loop"] in ("closed", "open")
    assert cell.config["limits"]
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in e2e, (m["name"], m["moves"])
    for reader in cell.readers(cell.end_to_end + cell.per_layer):
        assert callable(reader.read)


def test_names_units_and_entries():
    names = set()
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[key]:
            assert NAME.match(entry["name"]), entry["name"]
            assert entry["name"] not in names
            names.add(entry["name"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        cfg = spec.load_json(spec.ROOT / c["file"])
        assert cfg["name"] == c["name"]
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert cfg["assumed"]
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_reader_falls_back_to_the_base_name():
    own, shared, other = spec.Cell.readers(
        [{"name": "qps"}, {"name": "device_idle.kron"},
         {"name": "device_idle.steady"}])
    assert own.__file__.endswith("metrics/qps.py")
    assert shared.__file__.endswith("metrics/device_idle.py")
    assert other.__file__ == shared.__file__
    with pytest.raises(FileNotFoundError):
        spec.Cell.readers([{"name": "no_such_metric.kron"}])


def test_round_wait_reads_the_generator_lag():
    import types

    from bench.harness.loops import Query, Window

    win = Window(1.0, {i: Query(None, a, s) for i, (a, s) in enumerate(
        [(0.0, 0.0), (0.1, 0.4), (0.2, 0.4), (0.5, 0.5), (0.6, 0.9)])}, {}, {})
    (reader,) = spec.Cell.readers([{"name": "round_wait_p50_s.steady"}])
    assert abs(reader.read(types.SimpleNamespace(window=win)) - 0.2) < 1e-12
    empty = types.SimpleNamespace(window=Window(1.0, {}, {}, {}))
    assert reader.read(empty) is None
