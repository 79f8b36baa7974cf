"""Open-loop serving (DESIGN.md §11): pump()/poll(), arrival processes,
the virtual-clock load generator, queue-wait/service split, env tuning."""
import math

import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps.ppsp import make_bfs_engine
from repro.core.runtime import (
    DONE, REJECTED, TIMEOUT, RoundOutcome, SlotProgram, SlotRuntime)
from repro.launch import env as envmod
from repro.launch.loadgen import (
    constant_arrivals, make_arrivals, mmpp_arrivals, poisson_arrivals,
    run_open_loop, saturation_knee, sweep_qps)


def _pairs(graph, n_pairs, seed=0):
    rng = np.random.default_rng(seed)
    return [
        (int(a), int(b))
        for a, b in rng.integers(0, graph.n_real, (n_pairs, 2))
    ]


# ----------------------------------------------------------- arrivals
@pytest.mark.parametrize("process", ["poisson", "constant", "mmpp"])
def test_arrivals_seeded_reproducible(process):
    a = make_arrivals(process, 2.0, 50, seed=7)
    b = make_arrivals(process, 2.0, 50, seed=7)
    np.testing.assert_array_equal(a, b)
    assert a.shape == (50,)
    assert np.all(np.diff(a) >= 0), "arrival times must be sorted"
    assert a[0] > 0


def test_poisson_mean_rate():
    a = poisson_arrivals(4.0, 8000, seed=1)
    rate = len(a) / a[-1]
    assert abs(rate - 4.0) / 4.0 < 0.1


def test_constant_is_exact():
    a = constant_arrivals(2.0, 4)
    np.testing.assert_allclose(a, [0.5, 1.0, 1.5, 2.0])


def test_mmpp_long_run_rate_and_burstiness():
    a = mmpp_arrivals(2.0, 6000, seed=2, burst=4.0, dwell=8.0)
    rate = len(a) / a[-1]
    assert abs(rate - 2.0) / 2.0 < 0.25
    # bursty: inter-arrival variability beats the exponential's cv=1
    gaps = np.diff(a)
    cv = gaps.std() / gaps.mean()
    assert cv > 1.1


def test_unknown_process_and_bad_rate():
    with pytest.raises(ValueError, match="unknown arrival process"):
        make_arrivals("pareto", 1.0, 4)
    with pytest.raises(ValueError):
        poisson_arrivals(0.0, 4)


# ------------------------------------------------------- pump()/poll()
def test_pump_reports_each_completion_exactly_once(small_directed):
    g = small_directed
    eng = make_bfs_engine(g, capacity=2)
    qids = [eng.submit(jnp.asarray(p, jnp.int32))
            for p in _pairs(g, 5, seed=3)]
    seen = []
    for _ in range(1000):
        seen += [q for q, _, _ in eng.pump()]
        if len(seen) == len(qids):
            break
    assert sorted(seen) == sorted(qids)
    assert eng.pump() == []  # idle pump: no work, no phantom completions


def test_pump_drain_equivalence(small_directed):
    """Same submits -> identical results/status/steps, pump vs drain —
    including a cache hit and a TIMEOUT eviction."""
    g = small_directed
    pairs = _pairs(g, 6, seed=4)

    def phase1(eng):
        return [eng.submit(jnp.asarray(p, jnp.int32)) for p in pairs[:2]]

    def phase2(eng):
        out = [eng.submit(jnp.asarray(p, jnp.int32)) for p in pairs[2:]]
        out.append(eng.submit(jnp.asarray(pairs[0], jnp.int32)))  # cache hit
        out.append(eng.submit(jnp.asarray((1, 50), jnp.int32), budget=1))
        return out

    eng_a = make_bfs_engine(g, capacity=2, result_cache=16)
    qids_a = phase1(eng_a)
    eng_a.run_until_drained()
    phase2(eng_a)
    eng_a.run_until_drained()

    eng_b = make_bfs_engine(g, capacity=2, result_cache=16)
    qids = phase1(eng_b)
    reported = {}

    def pump_until(want):
        for _ in range(1000):
            for qid, res, status in eng_b.pump():
                assert qid not in reported, "completion reported twice"
                reported[qid] = status
            if len(reported) == want:
                return
        raise AssertionError("pump loop did not converge")

    pump_until(len(qids))
    qids += phase2(eng_b)
    pump_until(len(qids))
    assert len(reported) == len(qids)
    assert eng_b.runtime.status == eng_a.runtime.status
    assert eng_b.runtime.steps == eng_a.runtime.steps
    norm = lambda res: {
        q: {k: np.asarray(v).tolist() for k, v in r.items()}
        for q, r in res.items()
    }
    assert norm(eng_b.runtime.results) == norm(eng_a.runtime.results)
    assert TIMEOUT in reported.values()
    assert eng_b.stats.cache_hits == 1


class _RejectAll(SlotProgram):
    def slot_validate(self, query):
        return (REJECTED, None)

    def slot_round(self, admitted):  # pragma: no cover - never admitted
        raise AssertionError("rejected queries must not reach a round")


def test_pump_reports_rejections():
    rt = SlotRuntime(_RejectAll(), capacity=2)
    qid = rt.submit(np.zeros(2, np.int32))
    got = rt.pump()
    assert got == [(qid, None, REJECTED)]
    assert rt.pump() == []
    assert rt.poll(qid) == (REJECTED, None)


def test_poll(small_directed):
    g = small_directed
    eng = make_bfs_engine(g, capacity=1)
    qid = eng.submit(jnp.asarray((0, 5), jnp.int32))
    assert eng.poll(qid) is None
    while eng.poll(qid) is None:
        eng.pump()
    status, res = eng.poll(qid)
    assert status == DONE and "dist" in res


# --------------------------------------------- queue wait / service split
def test_queue_wait_plus_service_equals_latency(small_directed):
    g = small_directed
    eng = make_bfs_engine(g, capacity=1, result_cache=8)
    for p in _pairs(g, 6, seed=5):
        eng.submit(jnp.asarray(p, jnp.int32))
    eng.submit(jnp.asarray(_pairs(g, 6, seed=5)[0], jnp.int32))  # hit
    eng.run_until_drained()
    st = eng.stats
    assert len(st.queue_waits) == len(st.query_latencies) == 7
    assert len(st.service_times) == 7
    for qw, sv, lat in zip(st.queue_waits, st.service_times,
                           st.query_latencies):
        assert qw >= 0 and sv >= 0
        assert qw + sv == pytest.approx(lat, abs=1e-12)
    # capacity 1: later queries actually wait in the queue
    assert max(st.queue_waits) > 0
    assert not math.isnan(st.queue_wait_percentile(95))
    assert not math.isnan(st.service_percentile(50))


def test_split_percentiles_nan_on_empty():
    from repro.core.runtime import SlotStats

    s = SlotStats()
    assert math.isnan(s.queue_wait_percentile(50))
    assert math.isnan(s.service_percentile(99))


def test_resume_preserves_first_admit(small_directed):
    """Suspend/resume must not re-charge queue wait: admit_t is pinned at
    the FIRST admission, so the split still sums to the latency."""
    g = small_directed
    eng = make_bfs_engine(g, capacity=1, scheduler="sjf", preemptive=True)
    heavy = eng.submit(jnp.asarray((0, 59), jnp.int32), budget=60)
    eng.run_round()
    light = eng.submit(jnp.asarray((2, 3), jnp.int32), budget=20)
    eng.run_until_drained()
    assert eng.status[heavy] == DONE and eng.status[light] == DONE
    assert eng.stats.preemptions >= 1
    st = eng.stats
    for qw, sv, lat in zip(st.queue_waits, st.service_times,
                           st.query_latencies):
        assert qw + sv == pytest.approx(lat, abs=1e-12)


# ------------------------------------------------------------ open loop
def _mixed_items(g, n, seed=0):
    rng = np.random.default_rng(seed)
    items = []
    for a, b in rng.integers(0, g.n_real, (n, 2)):
        items.append((jnp.asarray([int(a), int(b)], jnp.int32),
                      dict(budget=64)))
    return items


def test_open_loop_virtual_deterministic(small_directed):
    g = small_directed
    items = _mixed_items(g, 10, seed=6)
    arr = poisson_arrivals(1.0, len(items), seed=7)
    runs = []
    for _ in range(2):
        eng = make_bfs_engine(g, capacity=2)
        res = run_open_loop(eng, items, arr, offered_qps=1.0)
        runs.append(res)
    a, b = runs
    assert a.latencies == b.latencies
    assert a.ticks == b.ticks
    assert a.statuses == b.statuses
    assert a.n == 10 and len(a.latencies) == 10
    assert all(s == DONE for s in a.statuses.values())
    assert a.makespan > 0 and a.achieved_qps > 0


def test_open_loop_latency_grows_with_offered_load(small_directed):
    """The latency-throughput curve's defining property: mean latency at a
    rate far above capacity exceeds mean latency far below it."""
    g = small_directed
    items = _mixed_items(g, 12, seed=8)

    def run_at(rate):
        eng = make_bfs_engine(g, capacity=2)
        arr = poisson_arrivals(rate, len(items), seed=9)
        return run_open_loop(eng, items, arr, offered_qps=rate)

    slow = run_at(0.05)
    fast = run_at(50.0)
    assert np.mean(fast.latencies) > np.mean(slow.latencies)
    assert fast.max_backlog > slow.max_backlog


def test_open_loop_records_split_delta(small_directed):
    g = small_directed
    eng = make_bfs_engine(g, capacity=2)
    # pre-run garbage in the stats must not leak into the LoadResult
    eng.submit(jnp.asarray((0, 1), jnp.int32))
    eng.run_until_drained()
    items = _mixed_items(g, 6, seed=10)
    res = run_open_loop(eng, items, poisson_arrivals(1.0, 6, seed=11))
    assert len(res.queue_waits) == 6
    assert len(res.service_times) == 6


def test_open_loop_wall_clock_smoke(small_directed):
    g = small_directed
    eng = make_bfs_engine(g, capacity=2)
    items = _mixed_items(g, 4, seed=12)
    arr = constant_arrivals(200.0, len(items))  # fast: test stays quick
    res = run_open_loop(eng, items, arr, clock="wall", offered_qps=200.0)
    assert res.clock == "wall"
    assert len(res.latencies) == 4
    assert all(lat > 0 for lat in res.latencies)


def test_open_loop_rejects_bad_clock_and_shapes(small_directed):
    eng = make_bfs_engine(small_directed, capacity=1)
    with pytest.raises(ValueError, match="clock"):
        run_open_loop(eng, [], [], clock="logical")
    with pytest.raises(ValueError, match="one arrival per item"):
        run_open_loop(eng, [jnp.zeros(2, jnp.int32)], [1.0, 2.0])


def test_sweep_and_knee(small_directed):
    g = small_directed
    items = _mixed_items(g, 8, seed=13)
    eng = make_bfs_engine(g, capacity=2)
    swept = sweep_qps(lambda: eng, items, (0.1, 8.0), seed=14)
    assert set(swept["curve"]) == {0.1, 8.0}
    low = swept["curve"][0.1]
    assert low["busy_qps"] >= 0.1  # keeps up at the lowest point
    assert swept["knee"] >= 0.1 or math.isnan(swept["knee"])


def test_saturation_knee_reads_curve():
    curve = {
        1.0: {"busy_qps": 0.99},
        2.0: {"busy_qps": 1.95},
        4.0: {"busy_qps": 2.10},  # saturated
    }
    assert saturation_knee(curve) == 2.0
    assert math.isnan(saturation_knee({4.0: {"busy_qps": 1.0}}))
    # hand-built curves without busy_qps fall back to achieved_qps
    assert saturation_knee({1.0: {"achieved_qps": 0.95}}) == 1.0


# ----------------------------------------------------------------- env
def test_env_detect_reports_host():
    d = envmod.detect({})
    assert d["cpus"] >= 1
    assert d["tcmalloc_active"] is False


def test_env_advise_rows_and_exports():
    rows = envmod.advise(host_devices=4, env={})
    by_var = {r["var"]: r for r in rows}
    assert by_var["XLA_FLAGS"]["value"].endswith("device_count=4")
    assert "JAX_PLATFORMS" not in by_var  # the platform is never pinned
    assert all(not r["active"] for r in rows)
    exports = envmod.shell_exports(host_devices=4)
    assert "export XLA_FLAGS=" in exports
    # tcmalloc only advised when the library exists on this host
    has_lib = envmod.find_tcmalloc() is not None
    assert ("LD_PRELOAD" in by_var) == has_lib


def test_env_apply_respects_existing():
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=2"}
    applied = envmod.apply(env, host_devices=8)
    assert "XLA_FLAGS" not in applied          # already set: kept
    assert "LD_PRELOAD" not in applied         # advisory only
    assert "JAX_PLATFORMS" not in env and "JAX_PLATFORMS" not in applied
    d = envmod.detect(env)
    assert d["host_device_count"] == 2
    assert envmod.describe(env)


def test_env_active_flags_detected():
    env = {
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "JAX_PLATFORMS": "cpu",
        "TF_CPP_MIN_LOG_LEVEL": "4",
    }
    rows = {r["var"]: r for r in envmod.advise(env=env)}
    assert rows["XLA_FLAGS"]["active"]
    assert "JAX_PLATFORMS" not in rows
    assert rows["TF_CPP_MIN_LOG_LEVEL"]["active"]
