"""Sparsity-aware propagation + multi-superstep round invariants
(DESIGN.md §3): gating and superstep fusion are pure optimizations — the
qid -> result maps and `EngineStats` accounting must be indistinguishable
from the dense single-step engine, including admission mid-stream."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps.hub2 import build_hub_index, make_hub2_engine
from repro.apps.keyword import MAXK, make_keyword_engine, make_vertex_text
from repro.apps.ppsp import make_bfs_engine, make_bibfs_engine
from repro.core import tracing


def _pairs(graph, n_pairs, seed=0):
    rng = np.random.default_rng(seed)
    return [
        (int(a), int(b))
        for a, b in rng.integers(0, graph.n_real, (n_pairs, 2))
    ]


def _stat_tuple(eng):
    s = eng.stats
    return (s.super_rounds, s.barriers, s.queries_done, s.supersteps_total)


def _res_map(res):
    return {
        qid: {k: np.asarray(v).tolist() for k, v in r.items()}
        for qid, r in res.items()
    }


def _drain(eng, pairs):
    for p in pairs:
        eng.submit(jnp.asarray(p, jnp.int32))
    return _res_map(eng.run_until_drained())


# ------------------------------------------------- multi-superstep rounds
@pytest.mark.parametrize("k", [2, 4, 8])
def test_steps_per_round_results_identical(small_directed, k):
    """steps_per_round=k returns the same qid->result map as k=1 and the
    same exact per-query superstep totals, with ~k x fewer barriers."""
    g = small_directed
    pairs = _pairs(g, 12, seed=5)
    base = make_bfs_engine(g, capacity=4)
    multi = make_bfs_engine(g, capacity=4, steps_per_round=k)
    out_base = _drain(base, pairs)
    out_multi = _drain(multi, pairs)
    assert out_base == out_multi
    assert multi.stats.supersteps_total == base.stats.supersteps_total
    assert multi.stats.queries_done == base.stats.queries_done
    assert multi.stats.barriers < base.stats.barriers


def test_steps_per_round_midstream_admission(small_directed):
    """Queries submitted between multi-step rounds join at round
    boundaries; results still match the single-step engine."""
    g = small_directed
    waves = [_pairs(g, 3, seed=s) for s in (31, 32, 33)]
    out = {}
    for k in (1, 4):
        eng = make_bfs_engine(g, capacity=3, steps_per_round=k)
        qids = []
        for wave in waves:
            qids += [eng.submit(jnp.asarray(p, jnp.int32)) for p in wave]
            eng.run_round()
        res = eng.run_until_drained()
        assert set(res) == set(qids)
        out[k] = _res_map(res)
    assert out[1] == out[4]


def test_steps_per_round_rejects_legacy(small_directed):
    with pytest.raises(ValueError):
        make_bfs_engine(small_directed, capacity=2, legacy=True,
                        steps_per_round=4)


# --------------------------------------------------------- gating parity
@pytest.mark.parametrize("backend", ["blocks_ref", "pallas"])
def test_engine_gated_matches_dense_tile(small_directed, backend):
    """gate=True (active-block skipping) vs gate=False (dense pre-mask)
    through the engine, under steps_per_round>1 with mid-stream admission:
    identical results AND identical EngineStats."""
    g = small_directed
    waves = [_pairs(g, 3, seed=s) for s in (41, 42)]
    out, stats = {}, {}
    for gate in (True, False):
        eng = make_bfs_engine(g, capacity=3, backend=backend, block=16,
                              steps_per_round=4, gate=gate)
        qids = []
        for wave in waves:
            qids += [eng.submit(jnp.asarray(p, jnp.int32)) for p in wave]
            eng.run_round()
        res = eng.run_until_drained()
        assert set(res) == set(qids)
        out[gate] = _res_map(res)
        stats[gate] = _stat_tuple(eng)
    assert out[True] == out[False]
    assert stats[True] == stats[False]


def test_engine_coo_gather_matches_dense(small_directed):
    """The frontier-gated COO gather path through the engine (BiBFS: two
    propagation views) against the plain segment reduction."""
    g = small_directed
    pairs = _pairs(g, 10, seed=51)
    plain = make_bibfs_engine(g, capacity=4)
    gated = make_bibfs_engine(g, capacity=4, gather_edges=64,
                              steps_per_round=2)
    out_p = _drain(plain, pairs)
    out_g = _drain(gated, pairs)
    assert out_p == out_g
    assert gated.stats.supersteps_total == plain.stats.supersteps_total


def test_stale_frontier_of_a_finished_slot_reads_nothing(frontier_passes):
    """A slot that stopped advancing keeps its last frontier; the round
    hands ``propagate`` its frontier masked by the advance flag, so the
    round's one decision counts only the advancing slots' arcs.  Here a
    star query ends at once, its last frontier the star's 99 leaves, while
    a chain query runs on with 2 arcs a round."""
    from repro.core.graph import Graph

    chain = np.arange(30)
    leaves = np.arange(101, 200)
    src = np.concatenate([chain, chain + 1, np.full(99, 100), leaves])
    dst = np.concatenate([chain + 1, chain, leaves, np.full(99, 100)])
    g = Graph.from_edges(src, dst, 200)
    totals = frontier_passes
    eng = make_bfs_engine(g, capacity=2)
    res = _drain(eng, [(0, 30), (100, 101)])
    jax.effects_barrier()
    assert totals[0] == 1 + 99  # both slots' first frontiers: {0}, {100}
    assert totals[1] == 2       # {1} alone: the star's leaves are stale
    assert set(totals[2:]) == {2}
    assert len(totals) == 30
    dense = make_bfs_engine(g, capacity=2, gate=False)
    assert res == _drain(dense, [(0, 30), (100, 101)])


def test_engine_gated_keyword_lanes(small_directed):
    """Multi-lane (MAXK, V) state: keyword search on a tile backend with
    gating == coo reference."""
    g = small_directed
    tokens = make_vertex_text(g.n, 20, 2, seed=6)
    rng = np.random.default_rng(7)
    qs = []
    for _ in range(4):
        q = np.full(MAXK, -1, np.int32)
        q[:2] = rng.integers(0, 8, 2)
        qs.append(jnp.asarray(q))
    out = {}
    for be in ("coo", "blocks_ref"):
        eng = make_keyword_engine(g, tokens, capacity=2, delta_max=3,
                                  backend=be, block=16, steps_per_round=2)
        for q in qs:
            eng.submit(q)
        out[be] = _res_map(eng.run_until_drained())
    assert out["coo"] == out["blocks_ref"]


# ------------------------------------------------------------ hub2 tiles
def test_hub2_index_on_tile_backends(small_undirected):
    """Hub² indexing mixes min_right + max_right on one view; with the
    per-semiring BlockSparse tables it must build the same index on tile
    backends as on coo."""
    g = small_undirected
    idx_coo = build_hub_index(g, k=4, capacity=4)
    for be in ("blocks_ref", "pallas"):
        idx = build_hub_index(g, k=4, capacity=4, backend=be, block=16)
        np.testing.assert_array_equal(
            np.asarray(idx_coo.hub_dist), np.asarray(idx.hub_dist)
        )
        np.testing.assert_array_equal(
            np.asarray(idx_coo.core), np.asarray(idx.core)
        )


def test_hub2_query_on_tile_backend(small_undirected):
    g = small_undirected
    idx = build_hub_index(g, k=4, capacity=4, backend="blocks_ref", block=16)
    e_coo = make_hub2_engine(g, idx, capacity=2)
    e_blk = make_hub2_engine(g, idx, capacity=2, backend="blocks_ref",
                             block=16, steps_per_round=4)
    for s, t in _pairs(g, 5, seed=61):
        q = jnp.asarray([s, t], jnp.int32)
        assert int(e_coo.query(q)["dist"]) == int(e_blk.query(q)["dist"])


# -------------------------------------------------------- frontier stats
def test_track_frontier_records_occupancy(small_directed, tmp_path):
    """Under the profiler every round records the frontier arcs its live
    slots lead out of (core/tracing.py); with the profiler off nothing is
    recorded and no counter is dispatched."""
    g = small_directed
    eng = make_bfs_engine(g, capacity=4)
    _drain(eng, _pairs(g, 4, seed=72))  # warm: the profiler must not compile
    tracing.clear()
    r0 = eng.stats.super_rounds
    with jax.profiler.trace(str(tmp_path)):
        _drain(eng, _pairs(g, 6, seed=71))
    rounds = range(r0, eng.stats.super_rounds)
    fa = [r for r in tracing.records() if r.kind == "count"
          and r.name == tracing.FRONTIER_ARCS]
    assert [r.round for r in fa] == list(rounds)
    assert all(r.value >= 0 for r in fa)
    assert max(r.value for r in fa) > 0
    # off: no record, no extra dispatch or readback on the hot path
    tracing.clear()
    _drain(eng, _pairs(g, 4, seed=73))
    assert tracing.records() == []
