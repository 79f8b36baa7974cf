"""Versioned mutable graphs (DESIGN.md §12).

Three layers of invariants:

Graph layer — ``Graph.apply_delta`` merges a batched edge delta into BOTH
adjacency views incrementally; the result must be indistinguishable from a
graph rebuilt from scratch on the merged edge set (same canonical edge
multiset, same degrees, same CSR invariants, same propagate semantics),
with the documented edge cases: duplicate-add last-wins, upsert of an
existing edge, self-loops, delete-of-absent raises without corrupting,
padded-range endpoints refused, empty delta is a version-bumping no-op.

Index layer — ``maintain_hub_index`` with the hub set pinned must produce
labels byte-identical to a full rebuild on the mutated graph with the SAME
hubs pinned; past the threshold it falls back to a rebuild.

Serving layer — the version-pinning invariant: a slot's answer is computed
entirely on the graph version it was admitted under.  For a scripted
mutation sequence with queries in flight, every result must equal a fresh
engine built at that query's pinned version — across fused/legacy paths
in-process and the SPMD path in a subprocess (which needs 8 forced host
devices).  The result cache must never serve a result computed on a
different version, and journal recovery must replay mutations through the
content-hash chain before resuming in-flight queries.
"""
import math
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps import hub2
from repro.apps.ppsp import make_bfs_engine, make_bibfs_engine
from repro.core.graph import BlockSparse, EdgeDelta, Graph, random_graph
from repro.core.runtime import QueryJournal
from repro.core.semiring import INF, MIN_PLUS
from repro.kernels import ref
from repro.launch.supervise import run_with_recovery
from repro.train.fault import FailureInjector

MODES = [("fused", 1), ("fused", 4), ("legacy", 1)]


# --------------------------------------------------------------- helpers
def _canon(src, dst, w):
    """Edges as a canonically-ordered (dst-major) triple, for comparisons
    that must ignore within-group insertion order."""
    s, d, w = np.asarray(src), np.asarray(dst), np.asarray(w)
    k = np.lexsort((s, d))
    return s[k], d[k], w[k]


def _check_invariants(g):
    """The structural contract both views must keep across any splice."""
    s, d = np.asarray(g.src), np.asarray(g.dst)
    n = np.int64(g.n)
    assert (np.diff(d.astype(np.int64)) >= 0).all(), "COO not dst-sorted"
    cs, cd = np.asarray(g.csr_src), np.asarray(g.csr_dst)
    key = cs.astype(np.int64) * n + cd
    assert (np.diff(key) > 0).all(), "CSR not (src,dst)-lex sorted / has dups"
    np.testing.assert_array_equal(
        np.asarray(g.csr_row),
        np.searchsorted(cs, np.arange(g.n + 1)).astype(np.int32))
    np.testing.assert_array_equal(
        np.asarray(g.in_deg), np.bincount(d, minlength=g.n).astype(np.int32))
    np.testing.assert_array_equal(
        np.asarray(g.out_deg), np.bincount(s, minlength=g.n).astype(np.int32))
    # both views hold the same edge multiset
    a = _canon(g.src, g.dst, g.w)
    b = _canon(g.csr_src, g.csr_dst, g.csr_w)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


def _edge_map(g):
    return {(int(s), int(d)): w for s, d, w in
            zip(np.asarray(g.src), np.asarray(g.dst), np.asarray(g.w))}


def _non_edge(g, rng):
    pairs = set(zip(np.asarray(g.src).tolist(), np.asarray(g.dst).tolist()))
    while True:
        a, b = (int(v) for v in rng.integers(0, g.n_real, 2))
        if a != b and (a, b) not in pairs and (b, a) not in pairs:
            return a, b


def _assert_res_equal(a, b):
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))


@pytest.fixture(scope="module")
def tail_graph():
    """Random core + a path tail 48->...->59: queries on the tail take many
    rounds, so mutations land while they are genuinely in flight (same
    construction as test_recovery.py's matrix_graph)."""
    g = random_graph(48, 3.0, seed=1, directed=True)
    src = np.concatenate([np.asarray(g.src), np.arange(48, 59)])
    dst = np.concatenate([np.asarray(g.dst), np.arange(49, 60)])
    return Graph.from_edges(src.astype(np.int32), dst.astype(np.int32), 60)


# ===================================================== graph-layer deltas
def test_apply_delta_matches_rebuild(small_directed):
    g = small_directed
    rng = np.random.default_rng(7)
    adds = [_non_edge(g, rng) for _ in range(5)]
    es, ed = np.asarray(g.src), np.asarray(g.dst)
    dels = [(int(es[i]), int(ed[i])) for i in (0, 10, 25)]
    wd = np.asarray(g.w).dtype
    w = np.arange(2, 7).astype(wd)

    g1 = g.apply_delta(adds, dels, w=w)
    assert g1.version == 1 and g1.parent_hash == g.content_hash()
    _check_invariants(g1)

    # independent expectation: plain dict merge, then a from-scratch build
    exp = _edge_map(g)
    for p in dels:
        del exp[p]
    for p, ww in zip(adds, w):
        exp[p] = ww
    assert _edge_map(g1) == exp
    ks = np.asarray([p[0] for p in exp], np.int32)
    kd = np.asarray([p[1] for p in exp], np.int32)
    rebuilt = Graph.from_edges(ks, kd, g.n_real,
                               w=np.asarray(list(exp.values()), wd))
    for x, y in zip(_canon(g1.src, g1.dst, g1.w),
                    _canon(rebuilt.src, rebuilt.dst, rebuilt.w)):
        np.testing.assert_array_equal(x, y)
    # semantics: propagate is identical on the spliced and rebuilt graphs
    x = jnp.asarray(rng.integers(0, 50, (2, g.n)), jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(ref.propagate_coo(g1, MIN_PLUS, x)),
        np.asarray(ref.propagate_coo(rebuilt, MIN_PLUS, x)))


def test_duplicate_add_last_wins_and_upsert(small_directed):
    g = small_directed
    wd = np.asarray(g.w).dtype
    # duplicate add of the same new pair: the LAST weight wins, one row
    a, b = _non_edge(g, np.random.default_rng(3))
    g1 = g.apply_delta(adds=[(a, b), (a, b)], w=np.asarray([5, 9], wd))
    assert g1.num_edges == g.num_edges + 1
    assert _edge_map(g1)[(a, b)] == 9
    _check_invariants(g1)
    # upsert of an EXISTING edge: weight replaced, edge count unchanged
    s0, d0 = int(np.asarray(g.src)[4]), int(np.asarray(g.dst)[4])
    g2 = g.apply_delta(adds=[(s0, d0)], w=np.asarray([3], wd))
    assert g2.num_edges == g.num_edges and _edge_map(g2)[(s0, d0)] == 3
    # delete+add of the same pair in ONE batch nets out to the add
    g3 = g.apply_delta(adds=[(s0, d0)], dels=[(s0, d0)],
                       w=np.asarray([7], wd))
    assert g3.num_edges == g.num_edges and _edge_map(g3)[(s0, d0)] == 7
    _check_invariants(g3)


def test_self_loop_add_delete(small_directed):
    g = small_directed
    g1 = g.apply_delta(adds=[(4, 4)])
    assert _edge_map(g1)[(4, 4)] == 1 and g1.num_edges == g.num_edges + 1
    _check_invariants(g1)
    # removing it restores the original arrays exactly (content reverts)
    g2 = g1.apply_delta(dels=[(4, 4)])
    assert g2.content_hash() == g.content_hash() and g2.version == 2


def test_delete_nonexistent_raises_without_corruption(small_directed):
    g = small_directed
    a, b = _non_edge(g, np.random.default_rng(11))
    before = g.content_hash()
    with pytest.raises(ValueError, match="not present"):
        g.make_delta(dels=[(a, b)])
    with pytest.raises(ValueError, match="not present"):
        g.apply_delta(dels=[(a, b)])
    # untouched: same hash, same version, views still coherent
    assert g.content_hash() == before and g.version == 0
    _check_invariants(g)


def test_delta_in_padded_range_refused(small_directed):
    gp = small_directed.padded(8)
    assert gp.n == 64 and gp.n_real == 60
    with pytest.raises(ValueError, match="real vertex range"):
        gp.make_delta(adds=[(60, 63)])
    with pytest.raises(ValueError, match="real vertex range"):
        gp.make_delta(adds=[(5, 61)])
    with pytest.raises(ValueError, match="real vertex range"):
        gp.make_delta(dels=[(62, 63)])
    # a real-range delta on a padded graph is fine
    a, b = _non_edge(gp, np.random.default_rng(0))
    _check_invariants(gp.apply_delta(adds=[(a, b)]))


def test_empty_delta_is_version_bumping_noop(small_directed):
    g = small_directed
    h = g.content_hash()
    assert g.content_hash() is h  # memoized (satellite: hash computed once)
    g1 = g.apply_delta()
    assert g1.version == 1 and g1.parent_hash == h
    assert g1.content_hash() == h
    assert g1.src is g.src and g1.csr_row is g.csr_row  # arrays shared


def test_blocksparse_nslots_required():
    with pytest.raises(TypeError):
        BlockSparse(src_ids=jnp.zeros((1, 1), jnp.int32),
                    tiles=jnp.zeros((1, 1, 4, 4)), block=4)


def test_update_blocks_incremental_with_growth():
    # path 0->1->...->59: sparse block rows, so new edges force max_bpr up
    n = 60
    g = Graph.from_edges(np.arange(n - 1, dtype=np.int32),
                         np.arange(1, n, dtype=np.int32), n)
    bs = g.to_blocks(16, MIN_PLUS.add_id)
    delta = g.make_delta(adds=[(59, 0), (30, 1)], dels=[(0, 1)])
    g1 = g.apply_delta(delta)
    touched = delta.touched_dst_blocks(16)
    np.testing.assert_array_equal(touched, [0])  # dst 0 and 1 share block 0
    bs1 = g1.update_blocks(bs, MIN_PLUS.add_id, touched)
    assert bs1.tiles.shape[1] > bs.tiles.shape[1]  # src-blocks/row grew
    x = jnp.asarray(np.random.default_rng(0).integers(0, 40, (2, n)),
                    jnp.int32)
    want = np.asarray(ref.propagate_coo(g1, MIN_PLUS, x))
    got = np.asarray(ref.propagate_blocks_ref(bs1, MIN_PLUS, x))[:, :n]
    np.testing.assert_array_equal(got, want)
    # touched=None refreshes every row — same answer
    bs_all = g1.update_blocks(bs, MIN_PLUS.add_id)
    got_all = np.asarray(ref.propagate_blocks_ref(bs_all, MIN_PLUS, x))[:, :n]
    np.testing.assert_array_equal(got_all, want)


# ================================================== Hub^2 incremental
def test_hub2_incremental_matches_pinned_rebuild(small_undirected):
    g = small_undirected
    idx = hub2.build_hub_index(g, 8)
    rng = np.random.default_rng(5)
    a, b = _non_edge(g, rng)
    es, ed = np.asarray(g.src), np.asarray(g.dst)
    s0, d0 = int(es[3]), int(ed[3])  # undirected: both directions exist
    delta = g.make_delta(adds=[(a, b), (b, a)],
                         dels=[(s0, d0), (d0, s0)])
    g1 = g.apply_delta(delta)

    inc, info = hub2.maintain_hub_index(g1, idx, delta, threshold=1.0)
    assert info["mode"] == "incremental" and info["affected_hubs"] > 0
    full = hub2.build_hub_index(g1, 8, hubs=np.asarray(idx.hub_ids))
    for f in ("hub_ids", "is_hub", "hub_dist", "core"):
        np.testing.assert_array_equal(np.asarray(getattr(inc, f)),
                                      np.asarray(getattr(full, f)))

    # past the threshold: full rebuild, hubs re-picked from new degrees
    reb, info_r = hub2.maintain_hub_index(g1, idx, delta, threshold=0.0)
    assert info_r["mode"] == "rebuild" and info_r["affected_hubs"] == idx.k

    # empty delta: nothing affected, the SAME index object comes back
    same, info_e = hub2.maintain_hub_index(g1, inc, g1.make_delta())
    assert same is inc and info_e["affected_hubs"] == 0


@pytest.mark.parametrize("arg_carried", [False, True],
                         ids=["constant", "arg_carried"])
def test_hub2_engine_maintains_index_through_apply_delta(small_undirected,
                                                         arg_carried):
    g = small_undirected
    idx = hub2.build_hub_index(g, 8)
    eng = hub2.make_hub2_engine(
        g, idx, capacity=2, arg_carried=arg_carried,
        index_fn=hub2.hub_index_updater(threshold=0.5))
    q = jnp.asarray([1, 50], jnp.int32)
    qid0 = eng.submit(q)
    r0 = eng.run_until_drained()[qid0]

    rng = np.random.default_rng(9)
    a, b = _non_edge(g, rng)
    info = eng.apply_delta(adds=[(a, b), (b, a)])
    assert info["index"]["mode"] == "incremental"
    assert info["index"]["affected_hubs"] > 0  # the relabel path ran
    qid1 = eng.submit(q)
    r1 = eng.run_until_drained()[qid1]

    # truth: fresh engine on the mutated graph with the OLD hubs pinned
    # (incremental maintenance never re-picks the hub set)
    g1 = eng.graph.trimmed()
    idx1 = hub2.build_hub_index(g1, 8, hubs=np.asarray(idx.hub_ids))
    fresh = hub2.make_hub2_engine(g1, idx1, capacity=2)
    fid = fresh.submit(q)
    _assert_res_equal(r1, fresh.run_until_drained()[fid])

    # an indexed engine without a maintainer must refuse to mutate
    bare = hub2.make_hub2_engine(g, idx, capacity=2)
    with pytest.raises(ValueError, match="index maintainer"):
        bare.apply_delta(adds=[(a, b)])


# =============================================== serving-layer invariants
def _fresh_answer(g, q, *, legacy=False, spr=1, factory=make_bfs_engine):
    e = factory(g, capacity=2, legacy=legacy, steps_per_round=spr)
    qid = e.submit(jnp.asarray(q, jnp.int32))
    return e.run_until_drained()[qid]


@pytest.mark.parametrize("mode,spr", MODES,
                         ids=[f"{m}-spr{k}" for m, k in MODES])
def test_versioned_parity_pin(tail_graph, mode, spr):
    """The acceptance pin: scripted mutations with queries in flight; every
    answer must equal a fresh engine built at that query's pinned version."""
    g0 = tail_graph
    legacy = mode == "legacy"
    eng = make_bfs_engine(g0, capacity=3, legacy=legacy, steps_per_round=spr)
    q_tail, q_mid = [48, 59], [48, 57]
    id0 = eng.submit(jnp.asarray(q_tail, jnp.int32))
    id1 = eng.submit(jnp.asarray(q_mid, jnp.int32))
    eng.run_round()
    assert int(np.asarray(eng.runtime.live).sum()) == 2  # mid-flight

    # v1: shortcut 48->58 — would change the in-flight answers if the
    # engine ever let them see it
    info1 = eng.apply_delta(adds=[(48, 58)])
    g1 = eng.graph
    assert info1["version"] == 1 and 0 in info1["editions"]
    id2 = eng.submit(jnp.asarray(q_tail, jnp.int32))  # admits on v1
    eng.run_round()

    # v2: shortcut gone again, plus an unrelated edge
    info2 = eng.apply_delta(adds=[(0, 59)], dels=[(48, 58)])
    g2 = eng.graph
    assert info2["version"] == 2
    id3 = eng.submit(jnp.asarray(q_tail, jnp.int32))  # admits on v2
    res = eng.run_until_drained()

    for qid, q, gg in [(id0, q_tail, g0), (id1, q_mid, g0),
                       (id2, q_tail, g1), (id3, q_tail, g2)]:
        want = _fresh_answer(gg, q, legacy=legacy, spr=spr)
        _assert_res_equal(res[qid], want)
    # the versions genuinely disagree: pinned v0 kept the long path
    assert int(np.asarray(res[id0]["dist"])) != int(np.asarray(res[id2]["dist"]))

    # editions for retired versions are pruned at the next mutation
    info3 = eng.apply_delta()
    assert info3["editions"] == [3]


def test_suspended_query_resumes_on_pinned_version(tail_graph):
    g = tail_graph
    eng = make_bfs_engine(g, capacity=2)
    qid0 = eng.submit(jnp.asarray([48, 59], jnp.int32))
    eng.run_round()
    victim = int(np.flatnonzero(np.asarray(eng.runtime.live))[0])
    eng.runtime.suspend([victim])
    # mutate while the query sits suspended: its payload pins version 0
    eng.apply_delta(adds=[(48, 59)])
    qid1 = eng.submit(jnp.asarray([48, 59], jnp.int32))
    res = eng.run_until_drained()
    assert int(np.asarray(res[qid0]["dist"])) == 11   # old version: the path
    assert int(np.asarray(res[qid1]["dist"])) == 1    # new version: the edge
    _assert_res_equal(res[qid0], _fresh_answer(g, [48, 59]))
    _assert_res_equal(res[qid1], _fresh_answer(eng.graph, [48, 59]))


def test_cache_never_serves_cross_version(tail_graph):
    g = tail_graph
    eng = make_bfs_engine(g, capacity=2, result_cache=8)
    st = eng.runtime.stats
    q = jnp.asarray([48, 59], jnp.int32)
    qid0 = eng.submit(q)
    r0 = eng.run_until_drained()[qid0]
    qid1 = eng.submit(q)  # same version: a legitimate hit
    assert st.cache_hits == 1
    _assert_res_equal(eng.runtime.results[qid1], r0)

    info = eng.apply_delta(adds=[(48, 59)])  # answer-changing mutation
    assert info["cache_invalidated"] >= 1
    assert st.cache_invalidations == info["cache_invalidated"]
    qid2 = eng.submit(q)  # MUST miss: the cached result is for v0
    assert st.cache_hits == 1
    r2 = eng.run_until_drained()[qid2]
    assert int(np.asarray(r2["dist"])) == 1
    _assert_res_equal(r2, _fresh_answer(eng.graph, [48, 59]))

    # revert the content: v2's hash equals v0's, but the v1 entry dies
    info2 = eng.apply_delta(dels=[(48, 59)])
    assert info2["content_hash"] == g.content_hash()
    qid3 = eng.submit(q)
    assert st.cache_hits == 1  # v1's entry was invalidated, not served
    r3 = eng.run_until_drained()[qid3]
    _assert_res_equal(r3, r0)


def test_cache_entry_from_pinned_retirement_survives_revert(tail_graph):
    """A query retiring AFTER a mutation is cached under its pinned (old)
    version's key.  When the content genuinely reverts, that entry is
    byte-identical to the fresh answer — serving it is correct."""
    g = tail_graph
    eng = make_bfs_engine(g, capacity=2, result_cache=8)
    st = eng.runtime.stats
    q = jnp.asarray([48, 59], jnp.int32)
    qid0 = eng.submit(q)
    eng.run_round()  # in flight on v0
    eng.apply_delta(adds=[(49, 48)])  # hash changes; fwd BFS unaffected
    r0 = eng.run_until_drained()[qid0]  # retires under the v0 key
    eng.apply_delta(dels=[(49, 48)])  # content reverts to v0's bytes
    assert eng.graph.content_hash() == g.content_hash()
    qid1 = eng.submit(q)
    assert st.cache_hits == 1  # served from the pinned-retirement entry
    _assert_res_equal(eng.runtime.results[qid1], r0)


def test_apply_delta_argument_errors(tail_graph, small_directed):
    eng = make_bfs_engine(tail_graph, capacity=2)
    d = tail_graph.make_delta(adds=[(0, 59)])
    assert isinstance(d, EdgeDelta)
    with pytest.raises(ValueError, match="not both"):
        eng.apply_delta(d, dels=[(0, 1)])
    beng = make_bibfs_engine(small_directed, capacity=2)
    with pytest.raises(ValueError, match="unknown views"):
        beng.apply_delta(adds=[(0, 1)], aux_deltas={"nope": None})


def test_bibfs_aux_view_follows_delta(small_directed):
    g = small_directed
    eng = make_bibfs_engine(g, capacity=2)
    q = [1, 40]
    qid0 = eng.submit(jnp.asarray(q, jnp.int32))
    eng.run_until_drained()
    rng = np.random.default_rng(13)
    a, b = _non_edge(g, rng)
    es, ed = np.asarray(g.src), np.asarray(g.dst)
    eng.apply_delta(adds=[(a, b)], dels=[(int(es[7]), int(ed[7]))])
    g1 = eng.graph
    # the reverse view tracked the delta: same canonical edges as g1.reverse()
    rev = eng.aux_graphs["rev"][0]
    for x, y in zip(_canon(rev.src, rev.dst, rev.w),
                    _canon(g1.reverse().src, g1.reverse().dst,
                           g1.reverse().w)):
        np.testing.assert_array_equal(x, y)
    qid1 = eng.submit(jnp.asarray(q, jnp.int32))
    res = eng.run_until_drained()
    _assert_res_equal(res[qid1],
                      _fresh_answer(g1, q, factory=make_bibfs_engine))


# ===================================================== journal + recovery
def test_mutation_journal_roundtrip(tmp_path):
    p = str(tmp_path / "j.wal")
    j = QueryJournal(p)
    adds = np.asarray([[0, 1], [2, 3]], np.int32)
    j.mutation(version=1, parent_hash="aa", content_hash="bb",
               adds=adds, add_w=np.asarray([1.5, 2.5], np.float32),
               dels=np.zeros((0, 2), np.int32))
    j.close()
    (rec,) = QueryJournal.replay(p)
    assert rec["type"] == "mutation" and rec["version"] == 1
    assert rec["parent_hash"] == "aa" and rec["content_hash"] == "bb"
    np.testing.assert_array_equal(np.asarray(rec["adds"]).reshape(-1, 2), adds)
    np.testing.assert_array_equal(np.asarray(rec["add_w"]), [1.5, 2.5])
    assert np.asarray(rec["dels"]).size == 0


def test_apply_delta_record_chain_checks(tail_graph):
    eng = make_bfs_engine(tail_graph, capacity=2)
    base = dict(type="mutation", version=1,
                adds=np.zeros((0, 2), np.int32), add_w=np.zeros((0,)),
                dels=np.zeros((0, 2), np.int32))
    with pytest.raises(RuntimeError, match="chain mismatch"):
        eng.apply_delta_record(dict(base, parent_hash="0" * 64,
                                    content_hash="f" * 64))
    # right parent, wrong recorded content: replay must refuse, not serve
    with pytest.raises(RuntimeError, match="diverged"):
        eng.apply_delta_record(dict(base,
                                    parent_hash=eng.graph.content_hash(),
                                    content_hash="f" * 64))


def test_recovery_replays_mutations(tail_graph, tmp_path):
    """Crash-recovery parity WITH a mid-stream mutation: the recovered run
    must replay the journaled delta through the hash chain before resuming
    in-flight queries, and end observationally identical to the
    uninterrupted run."""
    g = tail_graph
    subs = [(np.asarray([48, 59], np.int32), {}),
            (np.asarray([48, 57], np.int32), {}),
            (np.asarray([5, 20], np.int32), {})]

    def boot():
        return make_bfs_engine(g, capacity=3)

    def on_round(eng, rounds):
        # guard on version: a replayed mutation must not be applied twice
        if rounds >= 2 and eng.graph.version == 0:
            eng.apply_delta(adds=[(48, 58)])

    def fingerprint(eng):
        res = {q: {k: np.asarray(v).tolist() for k, v in r.items()}
               for q, r in eng.runtime.results.items()}
        return res, dict(eng.runtime.status), dict(eng.runtime.steps)

    base, _ = run_with_recovery(boot, str(tmp_path / "base.wal"), subs,
                                snapshot_every=2, on_round=on_round)
    want = fingerprint(base)
    assert base.graph.version == 1
    # all three were admitted on v0, so the tail query keeps the long path
    assert want[0][0]["dist"] == 11

    for r in (1, 3, 5):  # before / just after / well after the mutation
        inj = FailureInjector(fail_at_steps={r})
        eng, info = run_with_recovery(boot, str(tmp_path / f"c{r}.wal"),
                                      subs, snapshot_every=2, injector=inj,
                                      on_round=on_round)
        assert fingerprint(eng) == want, r
        assert eng.graph.version == 1
        if r >= 3:
            assert info["mutations_replayed"] == 1


# ------------------------------------------------------- SPMD subprocess
SPMD_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.apps.ppsp import make_bfs_engine
    from repro.core.graph import Graph, random_graph
    from repro.launch.mesh import make_mesh

    assert len(jax.devices()) == 8
    core = random_graph(48, 3.0, seed=1, directed=True)
    src = np.concatenate([np.asarray(core.src), np.arange(48, 59)])
    dst = np.concatenate([np.asarray(core.dst), np.arange(49, 60)])
    g0 = Graph.from_edges(src.astype(np.int32), dst.astype(np.int32),
                          60).padded(8)
    mesh8 = make_mesh((8,), ("w",))

    def fresh(g, q):
        e = make_bfs_engine(g, capacity=2)
        qid = e.submit(jnp.asarray(q, jnp.int32))
        return e.run_until_drained()[qid]

    eng = make_bfs_engine(g0, capacity=3, mesh=mesh8)
    id0 = eng.submit(jnp.asarray([48, 59], jnp.int32))
    id1 = eng.submit(jnp.asarray([48, 57], jnp.int32))
    eng.run_round()
    assert int(np.asarray(eng.runtime.live).sum()) == 2
    eng.apply_delta(adds=[(48, 58)])
    g1 = eng.graph
    id2 = eng.submit(jnp.asarray([48, 59], jnp.int32))
    eng.run_round()
    eng.apply_delta(adds=[(0, 59)], dels=[(48, 58)])
    g2 = eng.graph
    id3 = eng.submit(jnp.asarray([48, 59], jnp.int32))
    res = eng.run_until_drained()

    for qid, q, gg in [(id0, [48, 59], g0), (id1, [48, 57], g0),
                       (id2, [48, 59], g1), (id3, [48, 59], g2)]:
        want = fresh(gg, q)
        assert set(res[qid]) == set(want)
        for k in want:
            np.testing.assert_array_equal(np.asarray(res[qid][k]),
                                          np.asarray(want[k]))
    assert int(np.asarray(res[id0]["dist"])) != int(np.asarray(res[id2]["dist"]))
    print("MUTATION_SPMD_OK")
    """
)


def test_spmd_versioned_parity_pin():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep)
    )
    env["JAX_PLATFORMS"] = "cpu"  # see test_sharded_engine.py
    r = subprocess.run([sys.executable, "-c", SPMD_SCRIPT],
                       capture_output=True, text=True, env=env, timeout=560)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "MUTATION_SPMD_OK" in r.stdout


# ================================== compile-once serving (DESIGN.md §12 add.)
def test_with_capacity_padding_semantics(tail_graph):
    """Capacity padding is invisible to every consumer of the logical graph:
    same content hash, same propagation, same delta semantics — only the
    physical array length (the jit shape key) changes."""
    g = tail_graph
    cap = g.num_edges + 16
    gc = g.with_capacity(max_e=cap)
    assert gc.edge_capacity == cap and gc.num_edges == g.num_edges
    assert gc.content_hash() == g.content_hash()
    assert gc.version == g.version
    # COO padding: src = dst = n, w = 0 at the tail (dst-sort preserved)
    s, d, w = np.asarray(gc.src), np.asarray(gc.dst), np.asarray(gc.w)
    assert (s[g.num_edges:] == g.n).all() and (d[g.num_edges:] == g.n).all()
    assert (w[g.num_edges:] == 0).all()
    assert (np.diff(d.astype(np.int64)) >= 0).all()
    # trimmed() round-trips to the exact graph
    gt = gc.trimmed()
    _check_invariants(gt)
    for f in ("src", "dst", "w", "csr_src", "csr_dst", "csr_w", "csr_row"):
        np.testing.assert_array_equal(np.asarray(getattr(gt, f)),
                                      np.asarray(getattr(g, f)))
    # padding rows are inert under propagation (segment n is sliced off)
    x = np.where(np.arange(g.n) == 48, 0.0, INF).astype(np.float32)
    np.testing.assert_array_equal(
        np.asarray(ref.propagate_coo(gc, MIN_PLUS, jnp.asarray(x))),
        np.asarray(ref.propagate_coo(g, MIN_PLUS, jnp.asarray(x))))
    # in-capacity delta: shapes held, values-only change, content parity
    g1c = gc.apply_delta(adds=[(0, 59)])
    g1 = g.apply_delta(adds=[(0, 59)])
    assert g1c.edge_capacity == cap and g1c.num_edges == g.num_edges + 1
    assert g1c.content_hash() == g1.content_hash()
    _check_invariants(g1c.trimmed())
    # overflow: capacity grows (shape change = the one honest recompile)
    big = [(int(i % 48), int((i * 7 + 3) % 48)) for i in range(1, 48)]
    big = [(a, b) for a, b in big if a != b]
    g2c = g1c.apply_delta(adds=big)
    assert g2c.edge_capacity > cap
    assert g2c.content_hash() == g1.apply_delta(adds=big).content_hash()
    # carrier() strips lineage statics so jit treedefs match across versions
    import jax
    assert (jax.tree.structure(g1c.carrier())
            == jax.tree.structure(gc.carrier()))


def test_arg_carried_zero_recompiles(tail_graph):
    """The acceptance pin for arg-carried mode: ten in-capacity mutations,
    ZERO new compiles, every answer equal to a fresh engine at that
    version."""
    g = tail_graph
    rng = np.random.default_rng(3)
    eng = make_bfs_engine(g, capacity=3, arg_carried=True,
                          edge_capacity=g.num_edges + 20)
    ceng = make_bfs_engine(g, capacity=3)  # constant-closure shadow
    qid = eng.submit(jnp.asarray([48, 59], jnp.int32))
    cqid = ceng.submit(jnp.asarray([48, 59], jnp.int32))
    _assert_res_equal(eng.run_until_drained()[qid],
                      ceng.run_until_drained()[cqid])
    base = dict(eng.compile_counts)
    assert sum(base.values()) == eng.stats.jit_compiles > 0
    for i in range(10):
        a, b = (int(v) for v in rng.integers(0, 48, 2))
        if a == b:
            b = (a + 1) % 48
        eng.apply_delta(adds=[(a, b)])
        ceng.apply_delta(adds=[(a, b)])
        qid = eng.submit(jnp.asarray([48, 59], jnp.int32))
        cqid = ceng.submit(jnp.asarray([48, 59], jnp.int32))
        _assert_res_equal(eng.run_until_drained()[qid],
                          ceng.run_until_drained()[cqid])
    assert dict(eng.compile_counts) == base, "arg-carried mode recompiled"
    assert eng.stats.jit_compiles == sum(base.values())

    # capacity overflow falls back to ONE honest recompile, still correct:
    # 40 edges guaranteed absent (core edges live in [0,48)^2, the tail
    # path's sources are >= 48), well past the 20-edge headroom
    big = [(i, 49 + (i % 10)) for i in range(40)]
    eng.apply_delta(adds=big)
    ceng.apply_delta(adds=big)
    qid = eng.submit(jnp.asarray([48, 59], jnp.int32))
    cqid = ceng.submit(jnp.asarray([48, 59], jnp.int32))
    _assert_res_equal(eng.run_until_drained()[qid],
                      ceng.run_until_drained()[cqid])
    grown = {v: c for v, c in eng.compile_counts.items() if v not in base}
    assert grown, "overflow must recompile"


def test_arg_carried_carrier_shares_the_view_arrays(tail_graph):
    """An arg-carried engine holds each view capacity-padded from the
    start, so the round's carrier is those arrays, not a second copy of
    the graph on the device; still so after an in-capacity delta."""
    g = tail_graph
    eng = make_bibfs_engine(g, capacity=2, arg_carried=True)

    def check_shared():
        ed = eng._editions[eng._current_version]
        (carrier,) = ed.round_args
        views = {"default": ed.graph, **ed.aux}
        for name, args in carrier["views"].items():
            for leaf in ("src", "dst", "w", "csr_src", "csr_dst", "csr_w"):
                assert getattr(args["graph"], leaf) is getattr(views[name], leaf)
        assert carrier["graph"].src is ed.graph.src

    check_shared()
    assert eng.graph.content_hash() == g.content_hash()
    assert eng.graph.num_edges == g.num_edges < eng.graph.edge_capacity
    eng.apply_delta(adds=[(0, 1), (2, 3)], dels=None)
    check_shared()


def test_arg_carried_mode_resolution(tail_graph):
    g = tail_graph
    # auto: resolved by the edge-count threshold
    assert make_bfs_engine(g, capacity=2,
                           arg_carried_threshold=1)._arg_carried
    assert not make_bfs_engine(g, capacity=2,
                               arg_carried_threshold=10**9)._arg_carried
    # explicit True forces it regardless of size; legacy cannot carry
    assert make_bfs_engine(g, capacity=2, arg_carried=True)._arg_carried
    with pytest.raises(ValueError, match="carriable"):
        make_bfs_engine(g, capacity=2, arg_carried=True, legacy=True)
    with pytest.raises(ValueError, match="fused round"):
        make_bfs_engine(g, capacity=2, warmup=True, legacy=True)


def test_background_warmup_compiles_off_hot_path(tail_graph):
    """warmup=True: apply_delta returns without compiling; the old edition
    keeps serving its in-flight query; once the warm thread finishes, the
    new version's first dispatch adds ZERO compiles."""
    g = tail_graph
    eng = make_bfs_engine(g, capacity=3, warmup=True)
    qin = eng.submit(jnp.asarray([48, 59], jnp.int32))
    eng.run_round()
    compiles_before = eng.stats.jit_compiles
    eng.apply_delta(adds=[(48, 58)])
    assert eng.stats.warmups == 1
    # apply_delta itself never compiles — the warm thread does
    assert eng.run_until_drained()[qin]["dist"] == 11  # pinned v0, old path
    assert eng.wait_warmup(timeout=300), "warm thread did not finish"
    warmed = eng.stats.jit_compiles
    assert warmed > compiles_before  # the thread really compiled v1
    qid = eng.submit(jnp.asarray([48, 59], jnp.int32))
    res = eng.run_until_drained()
    assert eng.stats.jit_compiles == warmed, "post-warm dispatch recompiled"
    assert int(np.asarray(res[qid]["dist"])) == 2  # v1 shortcut
    _assert_res_equal(res[qid], _fresh_answer(eng.graph, [48, 59]))


def test_suspend_across_two_mutations_refcount(tail_graph):
    """Satellite pin: payloads suspended across >= 2 consecutive mutations
    keep their admission edition installed (refcounted), resume on it, and
    the edition is pruned only after the last reference drops."""
    g = tail_graph
    eng = make_bfs_engine(g, capacity=2)
    qid0 = eng.submit(jnp.asarray([48, 59], jnp.int32))
    qid1 = eng.submit(jnp.asarray([48, 57], jnp.int32))
    eng.run_round()
    victims = np.flatnonzero(np.asarray(eng.runtime.live)).tolist()
    assert len(victims) == 2
    eng.runtime.suspend(victims)
    assert eng._resume_refs == {0: 2}  # two payloads pin v0

    info1 = eng.apply_delta(adds=[(48, 59)])        # v1
    info2 = eng.apply_delta(adds=[(48, 58)])        # v2
    # v0 survives both prunes on refcount alone; v1 had no readers
    assert info1["editions"] == [0, 1]
    assert info2["editions"] == [0, 2]

    qid2 = eng.submit(jnp.asarray([48, 59], jnp.int32))  # admits on v2
    res = eng.run_until_drained()
    assert eng._resume_refs == {}  # both resumes released their pin
    assert int(np.asarray(res[qid0]["dist"])) == 11  # v0: the long path
    assert int(np.asarray(res[qid1]["dist"])) == 9
    assert int(np.asarray(res[qid2]["dist"])) == 1   # v2: direct edge
    _assert_res_equal(res[qid0], _fresh_answer(g, [48, 59]))
    _assert_res_equal(res[qid2], _fresh_answer(eng.graph, [48, 59]))
    # last reference gone: the next mutation finally prunes v0
    info3 = eng.apply_delta()
    assert info3["editions"] == [3]


def test_result_cache_bucketed_invalidation(tail_graph):
    from repro.core.runtime import ResultCache, _MISS

    c = ResultCache(8)
    c.put("aa:1", 1)
    c.put("aa:2", 2)
    c.put("bb:3", 3)
    assert c.invalidate_except("bb") == 2
    assert len(c) == 1 and c.get("bb:3") == 3 and c.get("aa:1") is _MISS
    # LRU eviction keeps the buckets consistent
    c2 = ResultCache(2)
    c2.put("v1:a", 1)
    c2.put("v1:b", 2)
    c2.put("v2:c", 3)  # evicts v1:a
    assert len(c2) == 2
    assert c2.invalidate_except("v2") == 1  # only v1:b left to drop
    assert c2.get("v2:c") == 3
    # the predicate sweep still works and maintains buckets
    c2.put("v2:d", 4)
    assert c2.invalidate(lambda k: k.endswith("d")) == 1
    assert c2.invalidate_except("zz") == 1
    assert len(c2) == 0

    # engine path: the mutation invalidation is timed into the new counter
    eng = make_bfs_engine(tail_graph, capacity=2, result_cache=8)
    qid = eng.submit(jnp.asarray([48, 59], jnp.int32))
    eng.run_until_drained()
    assert eng.stats.cache_invalidation_ms == 0.0
    info = eng.apply_delta(adds=[(48, 59)])
    assert info["cache_invalidated"] == 1
    assert eng.stats.cache_invalidation_ms > 0.0


def test_sharded_splice_matches_full_repartition(tail_graph):
    """Host-level satellite pin (no mesh needed): for both partitions, the
    shard-local splice holds exactly the edges a full re-partition would
    put in each row, and a row outgrowing Emax falls back to the full
    path."""
    from repro.core.distributed import ShardedGraph

    g = tail_graph.padded(64)
    es, ed_ = np.asarray(g.src), np.asarray(g.dst)
    dels = [(int(es[4]), int(ed_[4]))]
    adds = [(3, 17), (40, 2), (59, 1)]

    def rows(sg, r):
        v = np.asarray(sg.valid[r])
        return (np.asarray(sg.srcp[r])[v], np.asarray(sg.dstp[r])[v],
                np.asarray(sg.wp[r])[v])

    for part in ("dst", "src"):
        sg = ShardedGraph(g, 8, partition=part)
        emax0 = int(sg.srcp.shape[1])
        delta = g.make_delta(adds=adds, dels=dels)
        g1 = g.apply_delta(delta)
        spliced = sg.apply_delta(g1, delta)
        assert int(spliced.srcp.shape[1]) == emax0  # shapes held
        full = ShardedGraph(g1, 8, partition=part)
        for r in range(8):
            for a, b in zip(rows(spliced, r), rows(full, r)):
                np.testing.assert_array_equal(a, b, err_msg=f"{part} row {r}")
        # untouched rows must be the SAME buffers, not recomputed copies
        d = delta if part == "dst" else delta.reversed()
        touched = set(int(t) for t in d.touched_dst_blocks(sg.block))
        untouched = [r for r in range(8) if r not in touched]
        assert untouched, "delta unexpectedly touched every row"
        for r in untouched:
            np.testing.assert_array_equal(np.asarray(spliced.srcp[r]),
                                          np.asarray(sg.srcp[r]))
        # overflow: enough edges into one block to outgrow Emax + headroom
        blk0 = [(s, 0) if part == "dst" else (0, s)
                for s in range(1, emax0 + 6)]
        dd = g.make_delta(adds=blk0)
        gBig = g.apply_delta(dd)
        fb = sg.apply_delta(gBig, dd)
        fullBig = ShardedGraph(gBig, 8, partition=part)
        assert int(fb.srcp.shape[1]) == int(fullBig.srcp.shape[1])
        for r in range(8):
            for a, b in zip(rows(fb, r), rows(fullBig, r)):
                np.testing.assert_array_equal(a, b)


SPMD_AC_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.apps.ppsp import make_bfs_engine
    from repro.core.distributed import ShardedGraph
    from repro.core.graph import Graph, random_graph
    from repro.launch.mesh import make_mesh

    assert len(jax.devices()) == 8
    core = random_graph(48, 3.0, seed=1, directed=True)
    src = np.concatenate([np.asarray(core.src), np.arange(48, 59)])
    dst = np.concatenate([np.asarray(core.dst), np.arange(49, 60)])
    g0 = Graph.from_edges(src.astype(np.int32), dst.astype(np.int32),
                          60).padded(8)
    mesh8 = make_mesh((8,), ("w",))

    def fresh(g, q):
        e = make_bfs_engine(g, capacity=2)
        qid = e.submit(jnp.asarray(q, jnp.int32))
        return e.run_until_drained()[qid]

    eng = make_bfs_engine(g0, capacity=3, mesh=mesh8, arg_carried=True)
    id0 = eng.submit(jnp.asarray([48, 59], jnp.int32))
    eng.run_round()
    assert int(np.asarray(eng.runtime.live).sum()) == 1
    base = dict(eng.compile_counts)

    # two in-capacity mutations with the query in flight; the backend's
    # partitions are spliced shard-locally (refresh receives the delta)
    eng.apply_delta(adds=[(48, 58)])
    g1 = eng.graph
    id1 = eng.submit(jnp.asarray([48, 59], jnp.int32))
    eng.run_round()
    eng.apply_delta(adds=[(0, 59)], dels=[(48, 58)])
    g2 = eng.graph
    id2 = eng.submit(jnp.asarray([48, 59], jnp.int32))
    res = eng.run_until_drained()

    # sharded splice == full re-partition, row for row, on the final graph
    be = eng._editions[eng._current_version].backends["default"]
    full = ShardedGraph(g2, 8, partition=be.sg.partition)
    for r in range(8):
        for a, b in [(be.sg.srcp, full.srcp), (be.sg.dstp, full.dstp),
                     (be.sg.wp, full.wp)]:
            va = np.asarray(be.sg.valid[r]); vb = np.asarray(full.valid[r])
            np.testing.assert_array_equal(np.asarray(a[r])[va],
                                          np.asarray(b[r])[vb])

    # zero recompiles across both mutations (shared arg-carried entries)
    newv = {v: c for v, c in eng.compile_counts.items() if v not in base}
    assert not newv, f"SPMD arg-carried recompiled: {newv}"

    # version-pinning parity vs fresh single-device engines
    for qid, gg in [(id0, g0), (id1, g1), (id2, g2)]:
        want = fresh(gg, [48, 59])
        assert set(res[qid]) == set(want)
        for k in want:
            np.testing.assert_array_equal(np.asarray(res[qid][k]),
                                          np.asarray(want[k]))
    assert int(np.asarray(res[id0]["dist"])) == 11
    assert int(np.asarray(res[id1]["dist"])) == 2
    print("MUTATION_SPMD_AC_OK")
    """
)


def test_spmd_arg_carried_shard_local_delta():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep)
    )
    env["JAX_PLATFORMS"] = "cpu"  # see test_sharded_engine.py
    r = subprocess.run([sys.executable, "-c", SPMD_AC_SCRIPT],
                       capture_output=True, text=True, env=env, timeout=560)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "MUTATION_SPMD_AC_OK" in r.stdout
