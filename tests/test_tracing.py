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
from repro.apps.ppsp import make_bibfs_engine
from repro.core import tracing
from repro.core.graph import random_graph

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


def _run_alone(eng, pair, tmp_path) -> tuple:
    """Serve one query alone under the profiler: its frontier arcs and
    arcs read, summed over its rounds."""
    tracing.clear()
    with jax.profiler.trace(str(tmp_path)):
        eng.submit(jnp.asarray(pair, jnp.int32))
        eng.run_until_drained()
    need = sum(r.value for r in _records("count", tracing.FRONTIER_ARCS))
    read = sum(r.value for r in _records("count", tracing.ARCS_READ))
    rounds = len(_records("span", tracing.ROUND))
    return need, read, rounds


def _tiny(config: str) -> dict:
    cfg = spec.load_json(spec.ROOT / "bench" / "configs" / config)
    cfg.update(cfg.pop("tiny"))
    return cfg


def test_frontier_arcs_match_the_kronecker_reference(clean, tmp_path):
    """BiBFS on the tiny Graph500 graph: the counter reads each live slot's
    frontiers after its round, the ones its next superstep propagates.  So
    it sees levels 1 .. k-1 of the k supersteps the reference derives,
    both ways: the level-0 frontier ({s} forward, {t} backward) is made by
    ``init`` inside the admission round and propagated there, before any
    readback, and a finished query's last frontier is never propagated.
    The only difference from the reference's frontiers is level 0."""
    cfg = _tiny("graph500-kron.json")
    data = kron_bibfs.build(cfg, np.random.default_rng([7, 0]))
    pool = kron_bibfs.pool(data, cfg, np.random.default_rng([7, 1]))
    eng = kron_bibfs.make_engine(data, cfg)
    _warm(eng, pool[: cfg["capacity"]])
    fwd = kron_bibfs._matrix(data.n, data.src, data.dst)
    bwd = kron_bibfs._matrix(data.n, data.dst, data.src)
    out_deg, in_deg = np.diff(fwd.indptr), np.diff(bwd.indptr)
    padded = sum(int(eng._backends[v].graph.src.shape[0])
                 for v in ("default", "rev"))  # arcs of both views
    seen_beyond_level_0 = False
    for s, t in pool:
        ds, dt = kron_bibfs._levels(fwd, s), kron_bibfs._levels(bwd, t)
        k = kron_bibfs._supersteps(ds, dt)
        level0 = out_deg[s] + in_deg[t]
        ref = sum(out_deg[ds == j].sum() + in_deg[dt == j].sum()
                  for j in range(k))
        need, read, rounds = _run_alone(eng, (s, t), tmp_path)
        assert rounds == k
        assert need == ref - level0
        assert read == rounds * cfg["capacity"] * padded
        seen_beyond_level_0 |= need > 0
    assert seen_beyond_level_0


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
    counter sees every frontier but level 0 ({s}) and the last one."""
    cfg = _tiny("quegel-terrain.json")
    data = terrain_sssp.build(cfg, np.random.default_rng([7, 0]))
    pool = terrain_sssp.pool(data, cfg, np.random.default_rng([7, 1]))
    eng = terrain_sssp.make_engine(data, cfg)
    _warm(eng, pool[: cfg["capacity"]])
    out_deg = np.bincount(data.src, minlength=data.side ** 2)
    for s, t in pool[:4]:
        fronts = _terrain_frontiers(data, s, t)
        need, read, rounds = _run_alone(eng, (s, t), tmp_path)
        assert rounds == len(fronts)
        assert need == sum(out_deg[f].sum() for f in fronts[:-1])
        assert need > 0
        assert read == rounds * cfg["capacity"] * eng.graph.edge_capacity
