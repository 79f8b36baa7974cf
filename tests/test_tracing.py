"""The program's spans and counters (core/tracing.py): recorded only under
the JAX profiler, nested as the super-round runs, named device programs,
no compile when the profiler starts mid-serving, and a frontier-arc count
equal to what a plain reference derives from the same graph."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.configs import kron_bibfs, terrain_sssp
from bench.harness import spec
from repro.apps.ppsp import make_bfs_engine, make_bibfs_engine
from repro.core import tracing
from repro.core.graph import random_graph
from repro.kernels import ref

PHASES = (tracing.ADMIT, tracing.DISPATCH, tracing.SYNC, tracing.RETIRE)


@pytest.fixture
def clean():
    tracing.clear()
    yield
    tracing.clear()


def _warm(eng, pairs):
    """Two waves through every slot, as the benchmark warms up: rounds that
    admit, rounds that only advance, and retirements."""
    for _ in range(2):
        for p in pairs:
            eng.submit(jnp.asarray(p, jnp.int32))
        eng.run_until_drained()


def _engine():
    g = random_graph(64, 3.0, seed=5, directed=True)
    eng = make_bibfs_engine(g, capacity=4)
    pairs = [(i, 63 - i) for i in range(6)]
    _warm(eng, pairs)
    return eng, pairs


def _records(kind, name=None):
    return [r for r in tracing.records()
            if r.kind == kind and (name is None or r.name == name)]


def test_profiler_off_records_nothing(clean, monkeypatch):
    entered = []

    class Spy:
        def __init__(self, name, **kw):
            entered.append(name)

    monkeypatch.setattr(tracing, "TraceAnnotation", Spy)
    monkeypatch.setattr(tracing, "StepTraceAnnotation", Spy)
    eng, pairs = _engine()
    for p in pairs:
        eng.submit(jnp.asarray(p, jnp.int32))
    eng.run_until_drained()
    assert tracing.records() == []
    assert entered == []
    assert tracing.span(tracing.ROUND, round=0) is tracing.span(tracing.SYNC)


def test_rounds_nest_their_phases_and_queries_name_their_rounds(
        clean, tmp_path):
    eng, pairs = _engine()
    r0 = eng.stats.rounds
    with jax.profiler.trace(str(tmp_path)):
        qids = [eng.submit(jnp.asarray(p, jnp.int32)) for p in pairs]
        eng.run_until_drained()
    rounds = {r.round: r for r in _records("span", tracing.ROUND)}
    assert sorted(rounds) == list(range(r0, eng.stats.rounds))
    for rid, rnd in rounds.items():
        assert rnd.parent is None
        kids = [r for r in _records("span") if r.round == rid
                and r.name != tracing.ROUND]
        assert {k.name for k in kids} >= set(PHASES)
        for k in kids:
            assert rnd.t0_ns <= k.t0_ns <= k.t1_ns <= rnd.t1_ns
            want = tracing.RETIRE if k.name == tracing.COLLECT else \
                tracing.ROUND
            assert k.parent == want
        first = {n: min(k.t0_ns for k in kids if k.name == n) for n in PHASES}
        assert sorted(PHASES, key=first.get) == list(PHASES)
    queries = {r.qid: r for r in _records("query")}
    assert set(queries) == set(qids)
    for q in queries.values():
        assert q.status == "DONE"
        assert r0 <= q.admit_round <= q.retire_round < eng.stats.rounds
        assert q.submit_ns <= q.admit_ns <= q.done_ns
        # a query ends with the round that retired it
        assert q.done_ns <= rounds[q.retire_round].t1_ns
    # the profiler saw the same spans, on its own timeline
    from bench.harness import trace as tr

    names = {e[0] for p in tr.load_xplane(str(tmp_path))["planes"]
             for ln in p["lines"] for e in ln["events"]}
    assert {tracing.ROUND, *PHASES, tracing.COLLECT} <= names


def test_dump_writes_json_lines(clean, tmp_path):
    eng, pairs = _engine()
    with jax.profiler.trace(str(tmp_path / "trace")):
        eng.submit(jnp.asarray(pairs[0], jnp.int32))
        eng.run_until_drained()
    n = tracing.dump(tmp_path / "records.jsonl")
    lines = [json.loads(s) for s in
             (tmp_path / "records.jsonl").read_text().splitlines()]
    assert len(lines) == n == len(tracing.records()) > 0
    assert {ln["kind"] for ln in lines} == {"span", "count", "query"}


def test_span_names_are_the_programs_own(clean, tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with pytest.raises(ValueError, match="quegel"):
            tracing.span("pump")


def test_jitted_programs_are_named(clean):
    eng, _ = _engine()
    ed = eng._editions[eng._current_version]
    entries = [ed.round, ed.round_admit, ed.round_resume, eng._extract_all,
               eng._frontier_arcs]
    names = [f.__name__ for f in entries]
    assert "counted" not in names
    assert names == ["round_k", "round_admit", "round_resume", "extract_all",
                     "frontier_arcs"]
    vmask = np.zeros(eng.capacity, bool)
    text = ed.round.lower(eng._slots, vmask, *ed.round_args).as_text(
        debug_info=True)
    assert "jit_round_k" in text and "jit_counted" not in text
    # the propagate calls carry their view and plan
    assert "propagate.default.coo" in text and "propagate.rev.coo" in text


def test_profiler_started_mid_serving_compiles_nothing(clean, tmp_path):
    eng, pairs = _engine()
    compiles = eng.stats.jit_compiles
    with jax.profiler.trace(str(tmp_path)):
        for p in pairs:
            eng.submit(jnp.asarray(p, jnp.int32))
        eng.run_until_drained()
    assert eng.stats.jit_compiles == compiles
    assert _records("count", tracing.FRONTIER_ARCS)


def _run_alone(eng, pair, tmp_path) -> dict:
    """Serve one query alone under the profiler: its rounds, and each
    counter summed over them."""
    tracing.clear()
    with jax.profiler.trace(str(tmp_path)):
        eng.submit(jnp.asarray(pair, jnp.int32))
        eng.run_until_drained()
    out = {name: sum(r.value for r in _records("count", name))
           for name in (tracing.FRONTIER_ARCS, tracing.ARCS_READ,
                        tracing.FRONTIER_PATH, tracing.PROPAGATE_CALLS)}
    out["rounds"] = len(_records("span", tracing.ROUND))
    return out


def _expected_plan(graph, capacity, fronts, out_deg) -> tuple:
    """(frontier passes, arcs read) of one slot's propagate calls over
    ``fronts`` with every other slot idle: a frontier pass reads its trips
    of K arcs; past its limits every arc is read for every slot."""
    k, t, p = ref.frontier_sizes(graph, capacity)
    passes = read = 0
    for f in fronts:
        arcs, pairs = int(out_deg[f].sum()), int((f & (out_deg > 0)).sum())
        if arcs <= t and pairs <= p:
            passes += 1
            read += -(-arcs // k) * k
        else:
            read += capacity * int(graph.src.shape[0])
    return passes, read


def _tiny(config: str) -> dict:
    cfg = spec.load_json(spec.ROOT / "bench" / "configs" / config)
    cfg.update(cfg.pop("tiny"))
    return cfg


def test_frontier_arcs_match_the_kronecker_reference(clean, tmp_path):
    """BiBFS on the tiny Graph500 graph: the counters read each advancing
    slot's frontiers before its round, the ones the round propagates.  So
    they see levels 0 .. k-1 of the k supersteps the reference derives,
    both ways, and each propagate call's path and arcs read follow from
    those frontiers' arcs as the plan decides."""
    cfg = _tiny("graph500-kron.json")
    data = kron_bibfs.build(cfg, np.random.default_rng([7, 0]))
    pool = kron_bibfs.pool(data, cfg, np.random.default_rng([7, 1]))
    eng = kron_bibfs.make_engine(data, cfg)
    _warm(eng, pool[: cfg["capacity"]])
    fwd = kron_bibfs._matrix(data.n, data.src, data.dst)
    bwd = kron_bibfs._matrix(data.n, data.dst, data.src)
    out_deg, in_deg = np.diff(fwd.indptr), np.diff(bwd.indptr)
    graphs = {v: eng._backends[v].graph for v in ("default", "rev")}
    for s, t in pool:
        ds, dt = kron_bibfs._levels(fwd, s), kron_bibfs._levels(bwd, t)
        k = kron_bibfs._supersteps(ds, dt)
        ref_need = sum(out_deg[ds == j].sum() + in_deg[dt == j].sum()
                       for j in range(k))
        plans = [
            _expected_plan(graphs["default"], cfg["capacity"],
                           [ds == j for j in range(k)], out_deg),
            _expected_plan(graphs["rev"], cfg["capacity"],
                           [dt == j for j in range(k)], in_deg)]
        got = _run_alone(eng, (s, t), tmp_path)
        assert got["rounds"] == k
        assert got[tracing.FRONTIER_ARCS] == ref_need
        assert got[tracing.PROPAGATE_CALLS] == 2 * k
        assert got[tracing.FRONTIER_PATH] == sum(p for p, _ in plans)
        assert got[tracing.ARCS_READ] == sum(r for _, r in plans)


def _terrain_frontiers(data, s, t) -> list:
    """The frontiers ``TerrainSSSP`` builds, on the host: Bellman-Ford in
    float32 from ``s`` with the paper's Euclidean early stop; the last
    is the one its final superstep builds and never propagates."""
    n = data.side ** 2
    fin = np.float32(2 ** 30)
    d = np.full(n, fin, np.float32)
    d[s] = 0.0
    front = np.zeros(n, bool)
    front[s] = True
    eu = np.linalg.norm(data.coords - data.coords[s], axis=1)
    out = []
    while True:
        got = np.full(n, fin, np.float32)
        m = front[data.src]
        np.minimum.at(got, data.dst[m], d[data.src[m]] + data.w[m])
        improved = got < d
        d = np.where(improved, got, d)
        de_min = eu[improved].min() if improved.any() else fin
        out.append(improved)
        if d[t] < de_min or not improved.any():
            return out
        front = improved


def test_frontier_arcs_match_the_terrain_reference(clean, tmp_path):
    """Terrain SSSP on the tiny mesh: the bench's reference is Dijkstra,
    whose settled set is not the program's frontiers, so the reference
    here replays the program's Bellman-Ford rounds on the host.  The
    counters see every frontier the rounds propagate: {s}, then each
    built but the last."""
    cfg = _tiny("quegel-terrain.json")
    data = terrain_sssp.build(cfg, np.random.default_rng([7, 0]))
    pool = terrain_sssp.pool(data, cfg, np.random.default_rng([7, 1]))
    eng = terrain_sssp.make_engine(data, cfg)
    _warm(eng, pool[: cfg["capacity"]])
    out_deg = np.bincount(data.src, minlength=data.side ** 2)
    for s, t in pool[:4]:
        built = _terrain_frontiers(data, s, t)
        fronts = [np.arange(data.side ** 2) == s] + built[:-1]
        passes, read = _expected_plan(eng.graph, cfg["capacity"], fronts,
                                      out_deg)
        got = _run_alone(eng, (s, t), tmp_path)
        assert got["rounds"] == len(built)
        assert got[tracing.FRONTIER_ARCS] == sum(out_deg[f].sum()
                                                 for f in fronts)
        assert got[tracing.FRONTIER_PATH] == passes > 0
        assert got[tracing.PROPAGATE_CALLS] == len(built)
        assert got[tracing.ARCS_READ] == read


def test_counters_follow_the_path_each_call_took(clean, tmp_path,
                                                 frontier_passes):
    """``quegel.frontier_path``, ``quegel.propagate_calls`` and
    ``quegel.arcs_read`` against what the rounds did, recorded inside the
    frontier pass as it ran: its calls, and their trips of K arcs; every
    other call read every arc of every slot."""
    totals = frontier_passes
    g = random_graph(1000, 8.0, seed=3, directed=True)
    eng = make_bfs_engine(g, capacity=4)
    rng = np.random.default_rng(5)
    pairs = [tuple(int(v) for v in p) for p in rng.integers(0, 1000, (12, 2))]
    _warm(eng, pairs[:4])
    jax.effects_barrier()
    totals.clear()
    with jax.profiler.trace(str(tmp_path)):
        for p in pairs:
            eng.submit(jnp.asarray(p, jnp.int32))
        eng.run_until_drained()
    jax.effects_barrier()
    got = {name: sum(r.value for r in _records("count", name))
           for name in (tracing.FRONTIER_PATH, tracing.PROPAGATE_CALLS,
                        tracing.ARCS_READ)}
    rounds = len(_records("span", tracing.ROUND))
    k = ref.frontier_sizes(g, eng.capacity)[0]
    dense = eng.capacity * g.num_edges
    calls = got[tracing.PROPAGATE_CALLS]
    assert calls == rounds
    assert got[tracing.FRONTIER_PATH] == len(totals)
    assert 0 < len(totals) < calls  # both paths taken
    assert got[tracing.ARCS_READ] == (sum(-(-t // k) * k for t in totals)
                                      + (calls - len(totals)) * dense)


# Rounds 104-109 of a traced kron-bibfs-sat window on one TPU v5e (seed
# 1400000001, 15 s): every call of both views took the frontier pass.
V5E_KRON_COUNTS = [(name, 2, rnd) for rnd in range(104, 110)
                   for name in ("quegel.propagate_calls",
                                "quegel.frontier_path")]


def test_frontier_path_metric_reads_a_recorded_excerpt(monkeypatch):
    """``bench/metrics/frontier_path.py`` over the window's rounds only:
    the recorded excerpt reads 100%; one more round in the window whose
    two calls went dense brings it to 12 of 14 calls; a window without
    the counters reads nothing."""
    import types

    from bench.harness import program

    reader = spec.load_module(spec.BENCH / "metrics" / "frontier_path.py")
    recs = [tracing.Count(*c) for c in V5E_KRON_COUNTS]

    def run(r0, r1):
        return types.SimpleNamespace(window=types.SimpleNamespace(
            stats0={"rounds": r0}, stats1={"rounds": r1}))

    monkeypatch.setattr(program, "records", lambda: recs)
    assert reader.read(run(104, 110)) == 100.0
    recs += [tracing.Count("quegel.propagate_calls", 2, 110),
             tracing.Count("quegel.frontier_path", 0, 110),
             tracing.Count("quegel.frontier_path", 0, 111)]
    assert reader.read(run(104, 111)) == pytest.approx(100.0 * 12 / 14)
    assert reader.read(run(104, 110)) == 100.0
    assert reader.read(run(0, 100)) is None


@pytest.mark.parametrize("arg_carried", [False, True],
                         ids=["constant", "arg_carried"])
def test_counters_see_each_frontier_once_across_suspend_and_resume(
        clean, tmp_path, arg_carried):
    """A BFS query suspended after every round re-enters through the
    resume admission: the counters, run on the slot table as each round
    sees it, still count every level it propagates exactly once."""
    g = random_graph(300, 3.0, seed=9, directed=True)
    eng = make_bfs_engine(g, capacity=2, arg_carried=arg_carried)
    _warm(eng, [(0, 1), (2, 3)])
    src, dst = np.asarray(g.src), np.asarray(g.dst)
    out_deg = np.bincount(src, minlength=g.n)
    s, t = 0, 299
    level = np.full(g.n, -1)
    level[s], frontier, j = 0, [s], 0
    while frontier and level[t] < 0:
        nxt = np.unique(dst[np.isin(src, frontier)])
        nxt = nxt[level[nxt] < 0]
        j += 1
        level[nxt] = j
        frontier = list(nxt)
    want = sum(out_deg[level == i].sum() for i in range(j))
    with jax.profiler.trace(str(tmp_path)):
        eng.submit(jnp.asarray((s, t), jnp.int32))
        while len(eng.runtime.scheduler) or eng.runtime.live.any():
            eng.runtime.run_round()
            live = [i for i in range(eng.capacity) if eng.runtime.live[i]]
            if live:
                eng.runtime.suspend(live)
    got = sum(r.value for r in _records("count", tracing.FRONTIER_ARCS))
    assert level[t] > 1 and got == want
    assert sum(r.value for r in _records("count", tracing.FRONTIER_PATH)) \
        == sum(r.value for r in _records("count", tracing.PROPAGATE_CALLS))
