"""The trace reduction's busy union, idle share and gap labels against hand
values, on a hand-made trace and on a small trace recorded on a v5e."""
import json
from pathlib import Path

import pytest

from bench.harness import trace as tr

RECORDED = Path(__file__).with_name("v5e_trace_excerpt.json")


def plane(name, lines):
    return {"name": name, "lines": [{"name": n, "events": ev}
                                    for n, ev in lines.items()]}


HAND = {"planes": [
    plane("/host:CPU", {"python": [
        ["window", 1000, 10000], ["pump", 900, 4600], ["wait", 5500, 5100]]}),
    plane("/device:TPU:0", {
        "XLA Modules": [["jit_counted", 0, 20000]],
        "XLA Ops": [["a", 500, 1000], ["b", 1200, 800], ["c", 5000, 1000],
                    ["d", 10500, 1000]]}),
    plane("/device:TPU:0 SparseCore", {"XLA Ops": []}),
]}


def test_hand_trace():
    out = tr.reduce(HAND)
    # ops clipped to the window [1000, 11000]: busy union [1000, 2000],
    # [5000, 6000], [10500, 11000]; the module line is not an op line
    assert out["window_s"] == pytest.approx(10000e-9)
    assert out["busy_s"] == pytest.approx(2500e-9)
    assert dict(out["device_ops"]) == pytest.approx(
        {"a": 500e-9, "b": 800e-9, "c": 1000e-9, "d": 500e-9})
    # gaps [2000, 5000] under pump and [6000, 10500] under wait
    assert dict(out["idle_gaps"]) == pytest.approx(
        {"pump": 3000e-9, "wait": 4500e-9})


def test_op_names_keep_op_and_shape():
    assert tr.op_name("%fusion.2 = s32[8,262144]{0,1:T(8,128)} fusion(s32[8]"
                      " %x)") == "fusion.2 s32[8,262144]"
    assert tr.op_name("%sort = (s32[8]{0}, s32[8]{0}) sort(") == "sort (s32[8]"
    assert tr.op_name("a") == "a"


def test_no_device_op_reads_nothing():
    host_only = {"planes": [HAND["planes"][0]]}
    assert tr.reduce(host_only) is None
    with pytest.raises(ValueError):
        tr.reduce({"planes": [HAND["planes"][1]]})  # no window span


def test_recorded_v5e_trace():
    rec = json.loads(RECORDED.read_text())
    out = tr.reduce(rec["trace"])
    assert out["window_s"] == pytest.approx(rec["hand"]["window_s"])
    assert out["busy_s"] == pytest.approx(rec["hand"]["busy_s"])
    assert dict(out["idle_gaps"]) == pytest.approx(rec["hand"]["idle_gaps"])
