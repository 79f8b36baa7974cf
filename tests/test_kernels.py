"""Frontier-propagation kernel validation: Pallas (interpret) and the
block-sparse jnp oracle must match the COO segment-reduction reference,
swept over shapes, dtypes and semirings."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graph import Graph, random_graph
from repro.core.semiring import (
    BY_NAME,
    INF,
    MAX_PLUS,
    MAX_RIGHT,
    MIN_PLUS,
    MIN_RIGHT,
    SUM_TIMES,
)
from repro.kernels import frontier, ops, ref


def naive_propagate(graph: Graph, sr, x: np.ndarray) -> np.ndarray:
    """Python loop oracle (single query row)."""
    src = np.asarray(graph.src)
    dst = np.asarray(graph.dst)
    w = np.asarray(graph.w)
    out = np.full(graph.n, sr.add_id, dtype=x.dtype)
    for s, d, ww in zip(src, dst, w):
        if sr.name == "min_plus":
            msg = x[s] + ww if x[s] < INF and ww < INF else INF
        elif sr.name == "max_plus":
            msg = x[s] + ww if x[s] > -INF and ww > -INF else -INF
        elif sr.name in ("min_right", "max_right"):
            msg = x[s]
        elif sr.name == "sum_times":
            msg = x[s] * ww
        else:
            raise ValueError(sr.name)
        if sr.name in ("min_plus", "min_right"):
            out[d] = min(out[d], msg)
        elif sr.name in ("max_plus", "max_right"):
            out[d] = max(out[d], msg)
        else:
            out[d] = out[d] + msg
    return out


def _rand_x(rng, sr, n, q):
    if sr.name in ("min_plus", "min_right"):
        x = rng.integers(0, 20, (q, n)).astype(np.int32)
        x[rng.random((q, n)) < 0.5] = INF
    elif sr.name in ("max_plus", "max_right"):
        x = rng.integers(0, 20, (q, n)).astype(np.int32)
        x[rng.random((q, n)) < 0.5] = -(2**30)
    else:
        x = rng.standard_normal((q, n)).astype(np.float32)
    return x


SEMIRINGS = [MIN_PLUS, MIN_RIGHT, MAX_PLUS, MAX_RIGHT, SUM_TIMES]


@pytest.mark.parametrize("sr", SEMIRINGS, ids=lambda s: s.name)
def test_coo_matches_naive(sr):
    rng = np.random.default_rng(7)
    g = random_graph(40, 2.5, seed=5)
    if sr.name == "sum_times":
        g = Graph.from_edges(np.asarray(g.src), np.asarray(g.dst), g.n_real,
                             w=rng.standard_normal(g.num_edges), weight_dtype=np.float32)
    x = _rand_x(rng, sr, g.n, 3)
    got = np.asarray(ref.propagate_coo(g, sr, jnp.asarray(x)))
    for qi in range(3):
        want = naive_propagate(g, sr, x[qi])
        if x.dtype == np.float32:
            np.testing.assert_allclose(got[qi], want, rtol=1e-5, atol=1e-5)
        else:
            np.testing.assert_array_equal(got[qi], want)


@pytest.mark.parametrize("sr", SEMIRINGS, ids=lambda s: s.name)
@pytest.mark.parametrize("n,block", [(40, 8), (65, 16), (128, 16)])
@pytest.mark.parametrize("q", [1, 5])
def test_blocks_ref_and_pallas_match_coo(sr, n, block, q):
    rng = np.random.default_rng(n * 17 + q)
    g = random_graph(n, 3.0, seed=n + q)
    if sr.name == "sum_times":
        g = Graph.from_edges(np.asarray(g.src), np.asarray(g.dst), g.n_real,
                             w=rng.standard_normal(g.num_edges), weight_dtype=np.float32)
    x = jnp.asarray(_rand_x(rng, sr, g.n, q))
    want = np.asarray(ref.propagate_coo(g, sr, x))
    bs = g.to_blocks(block, sr.add_id, dtype=np.asarray(g.w).dtype)
    got_ref = np.asarray(ref.propagate_blocks_ref(bs, sr, x))
    got_pl = np.asarray(frontier.propagate_blocks(bs, sr, x, interpret=True))
    if np.asarray(x).dtype == np.float32:
        np.testing.assert_allclose(got_ref, want, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(got_pl, want, rtol=1e-4, atol=1e-4)
    else:
        np.testing.assert_array_equal(got_ref, want)
        np.testing.assert_array_equal(got_pl, want)


def test_frontier_mask_equivalence(small_directed):
    """Masking a source == setting its value to add_id."""
    g = small_directed
    rng = np.random.default_rng(0)
    x = jnp.asarray(_rand_x(rng, MIN_RIGHT, g.n, 2))
    mask = jnp.asarray(rng.random((2, g.n)) < 0.5)
    got = ops.propagate(g, MIN_RIGHT, x, mask)
    want = ops.propagate(g, MIN_RIGHT, jnp.where(mask, x, INF))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _masked_case(sr, n=70, q=4, seed=3, frontier_p=0.15):
    """Graph + x + sparse frontier for one semiring (float w for sum)."""
    rng = np.random.default_rng(seed)
    g = random_graph(n, 3.0, seed=seed)
    if sr.name == "sum_times":
        g = Graph.from_edges(np.asarray(g.src), np.asarray(g.dst), g.n_real,
                             w=rng.standard_normal(g.num_edges),
                             weight_dtype=np.float32)
    x = jnp.asarray(_rand_x(rng, sr, g.n, q))
    mask = jnp.asarray(rng.random((q, g.n)) < frontier_p)
    return g, x, mask


@pytest.mark.parametrize("sr", SEMIRINGS, ids=lambda s: s.name)
@pytest.mark.parametrize("backend", ["blocks_ref", "pallas"])
@pytest.mark.parametrize("gate", [True, False], ids=["gated", "dense"])
def test_frontier_mask_parity_tile_backends(sr, backend, gate):
    """frontier_mask through the tile backends — gated (active-block
    skipping + in-tile masking) and dense (pre-mask baseline) must both
    equal the masked COO reference, on every semiring."""
    g, x, mask = _masked_case(sr)
    want = np.asarray(ref.propagate_coo(g, sr, x, mask))
    bs = g.to_blocks(16, sr.add_id, dtype=np.asarray(g.w).dtype)
    got = np.asarray(
        ops.propagate(g, sr, x, mask, blocks=bs, backend=backend, gate=gate)
    )
    if np.asarray(x).dtype == np.float32:
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    else:
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("sr", SEMIRINGS, ids=lambda s: s.name)
@pytest.mark.parametrize("chunk", [7, 64, 4096])
def test_coo_gather_parity(sr, chunk):
    """The coo plan with ``gather_edges`` as its arcs a trip is exact for
    any chunk size: chunks whose trips cannot hold the frontier (the dense
    fallback), chunks smaller than the frontier's arcs (a multi-trip
    while_loop) and chunks larger than E."""
    g, x, mask = _masked_case(sr, seed=5)
    want = np.asarray(ref.propagate_coo(g, sr, x, mask))
    got = np.asarray(ops.propagate(g, sr, x, mask, gather_edges=chunk))
    if np.asarray(x).dtype == np.float32:
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    else:
        np.testing.assert_array_equal(got, want)


def test_coo_gather_empty_and_full_frontier():
    g, x, _ = _masked_case(MIN_RIGHT, seed=9)
    for mask in (jnp.zeros(x.shape, bool), jnp.ones(x.shape, bool)):
        want = np.asarray(ref.propagate_coo(g, MIN_RIGHT, x, mask))
        got = np.asarray(ops.propagate(g, MIN_RIGHT, x, mask, gather_edges=32))
        np.testing.assert_array_equal(got, want)


def test_block_activity_gates_padding_and_dead_blocks():
    """The activity bitmap marks padded slots dead, and only blocks
    holding frontier vertices (in any lane) active."""
    g = random_graph(64, 3.0, seed=11)
    bs = g.to_blocks(16, MIN_RIGHT.add_id)
    nb, m = bs.num_dst_blocks, bs.max_bpr
    valid = np.asarray(ops.block_activity(bs, None))
    assert valid.shape == (nb, m)
    assert (valid.sum(1) == np.asarray(bs.nslots)).all()
    # frontier confined to vertex-block 2 -> only tiles sourced there live
    mask = np.zeros((1, g.n), bool)
    mask[0, 2 * 16 : 3 * 16] = True
    act = np.asarray(ops.block_activity(bs, jnp.asarray(mask)))
    src_ids = np.asarray(bs.src_ids)
    assert (act <= valid).all()
    assert (act == (valid & (src_ids == 2))).all()


def test_pallas_float_min_plus():
    """Weighted (float) min-plus through the Pallas path."""
    rng = np.random.default_rng(4)
    g0 = random_graph(50, 3.0, seed=9)
    w = rng.random(g0.num_edges).astype(np.float32) + 0.1
    g = Graph.from_edges(np.asarray(g0.src), np.asarray(g0.dst), g0.n_real,
                         w=w, weight_dtype=np.float32)
    x = np.full((2, g.n), float(INF), np.float32)
    x[0, 3] = 0.0
    x[1, 7] = 0.0
    bs = g.to_blocks(16, float(INF), dtype=np.float32)
    want = np.asarray(ref.propagate_coo(g, MIN_PLUS, jnp.asarray(x)))
    got = np.asarray(frontier.propagate_blocks(bs, MIN_PLUS, jnp.asarray(x), interpret=True))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("traced", [False, True], ids=["constant", "traced"])
@pytest.mark.parametrize("sr", SEMIRINGS[:4], ids=lambda s: s.name)
def test_coo_combine_matches_numpy(sr, traced):
    """The coo combine equals a per-destination numpy reduction: a hub of
    in-degree 257, vertices with no in-edges, multi-edges and capacity
    padding; with the graph held as a trace constant and as a traced
    argument (the argument-carried round)."""
    rng = np.random.default_rng(11)
    n, hub = 300, 299
    src = np.concatenate([rng.integers(0, n, 900), np.arange(257)])
    dst = np.concatenate([rng.integers(0, n // 2, 900), np.full(257, hub)])
    w = np.concatenate([rng.integers(1, 9, 900), np.ones(257)]).astype(np.int32)
    g = Graph.from_edges(src, dst, n, w=w).with_capacity(max_e=len(src) + 77)
    x = _rand_x(rng, sr, n, 3)
    f = rng.random((3, n)) < 0.7
    x[:, 0], f[:, 0] = (-50 if sr.add_id > 0 else 50), True
    prop = lambda g, x, f: ref.propagate_coo(g, sr, x, f)
    got = np.asarray((jax.jit(prop) if traced else prop)(g, jnp.asarray(x), jnp.asarray(f)))
    s, d, ww = (np.asarray(a).astype(np.int64) for a in g._edges_np())
    xs = np.where(f, x, sr.add_id).astype(np.int64)[:, s]
    msgs = {"min_plus": xs + ww, "max_plus": xs + ww,
            "min_right": xs, "max_right": xs}[sr.name]
    msgs = np.where(np.abs(xs) >= abs(sr.add_id), sr.add_id, msgs)
    want = np.full((3, n), sr.add_id, np.int64)
    (np.minimum if sr.add_id > 0 else np.maximum).at(want, (slice(None), d), msgs)
    np.testing.assert_array_equal(got, want)


def _to_blocks_loop(g: Graph, block: int, add_id, dtype):
    """Per-edge loop reference for ``Graph.to_blocks``."""
    src, dst, w = (np.asarray(a) for a in (g.src, g.dst, g.w))
    nb = -(-g.n // block)
    rows = [[] for _ in range(nb)]
    for p in np.unique((dst // block).astype(np.int64) * nb + src // block):
        rows[int(p // nb)].append(int(p % nb))
    max_bpr = max(1, max(len(r) for r in rows))
    src_ids = np.zeros((nb, max_bpr), np.int32)
    tiles = np.full((nb, max_bpr, block, block), add_id, dtype=dtype)
    for i, r in enumerate(rows):
        src_ids[i, : len(r)] = r
    for s, d, ww in zip(src, dst, w):
        i, k = d // block, rows[d // block].index(s // block)
        cell = (i, k, s % block, d % block)
        if add_id == 0:
            tiles[cell] += ww
        elif add_id > 0:
            tiles[cell] = min(tiles[cell], ww)
        else:
            tiles[cell] = max(tiles[cell], ww)
    return src_ids, tiles, np.array([len(r) for r in rows], np.int32)


@pytest.mark.parametrize("sr", SEMIRINGS, ids=lambda s: s.name)
@pytest.mark.parametrize("n,block", [(1, 8), (70, 16), (300, 128)])
def test_to_blocks_matches_loop_reference(sr, n, block):
    """The vectorised tile build equals the per-edge loop, duplicates and
    empty rows included."""
    rng = np.random.default_rng(n + block)
    e = 4 * n
    src, dst = rng.integers(0, n, e), rng.integers(0, n, e)
    w = rng.integers(1, 9, e).astype(np.float32 if sr.name == "sum_times" else np.int32)
    g = Graph.from_edges(src, dst, n, w=w, weight_dtype=w.dtype)
    bs = g.to_blocks(block, sr.add_id)
    src_ids, tiles, nslots = _to_blocks_loop(g, block, sr.add_id, w.dtype)
    np.testing.assert_array_equal(np.asarray(bs.src_ids), src_ids)
    np.testing.assert_array_equal(np.asarray(bs.tiles), tiles)
    np.testing.assert_array_equal(np.asarray(bs.nslots), nslots)


def test_trace_state_clean_inside_jit():
    """The guard that keeps in-trace tile tables out of the cache must see
    a ``jax.jit`` trace as unclean."""
    seen = []
    jax.jit(lambda x: (seen.append(ops._trace_state_clean()), x)[1])(1)
    jax.eval_shape(lambda x: (seen.append(ops._trace_state_clean()), x)[1], 1)
    assert seen == [False, False]
    assert ops._trace_state_clean() is True


def test_tile_table_built_in_a_trace_is_not_cached(small_directed):
    """A table a jit trace builds holds that trace's values: it must not
    outlive the trace in the backend's cache, while one built eagerly is
    kept."""
    g = small_directed
    be = ops.make_backend("blocks_ref", g, block=16)
    x = jnp.asarray(_rand_x(np.random.default_rng(1), MIN_RIGHT, g.n, 2))
    traced = np.asarray(jax.jit(lambda x: be.propagate(MIN_RIGHT, x))(x))
    assert be.tables == {}
    eager = np.asarray(be.propagate(MIN_RIGHT, x))
    assert set(be.tables) == {"min_right"}
    np.testing.assert_array_equal(traced, eager)


def test_interpret_is_refused_on_a_tpu(monkeypatch):
    """On a TPU the kernel is always compiled: asking for the interpreter
    there is an error, not a silent slowdown."""
    g = random_graph(40, 2.0, seed=1)
    bs = g.to_blocks(8, MIN_RIGHT.add_id)
    x = jnp.zeros((1, g.n), jnp.int32)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="interpret=True on a TPU"):
        frontier.propagate_blocks(bs, MIN_RIGHT, x, interpret=True)


# ------------------------------------------------ frontier-driven coo pass
SELECTIVE = [MIN_PLUS, MIN_RIGHT, MAX_PLUS, MAX_RIGHT]
C = 8


def _weighted(g, dtype, seed=0):
    rng = np.random.default_rng(seed)
    w = rng.integers(1, 9, g.num_edges)
    return Graph.from_edges(np.asarray(g.src)[: g.num_edges],
                            np.asarray(g.dst)[: g.num_edges], g.n_real, w=w,
                            weight_dtype=dtype)


def _x(sr, shape, dtype, seed=1):
    """Values with unreached (add-identity) entries mixed in."""
    rng = np.random.default_rng(seed)
    x = rng.integers(0, 50, shape).astype(dtype)
    return jnp.asarray(np.where(rng.random(shape) < 0.2, sr.add_id, x)
                       .astype(dtype))


def _frontier_of_arcs(g, rows, target, seed=2):
    """A (rows, V) frontier whose out-arcs over all rows come to at most
    ``target``, vertices drawn at random into rows in turn."""
    rng = np.random.default_rng(seed)
    deg = np.diff(np.asarray(g.csr_row))
    f = np.zeros((rows, g.n), bool)
    total = 0
    for i, v in enumerate(rng.permutation(np.repeat(np.arange(g.n), rows))):
        r = i % rows
        if deg[v] and not f[r, v] and total + deg[v] <= target:
            f[r, v] = True
            total += deg[v]
    return jnp.asarray(f), total


def _vmapped(g, sr, chunk=None):
    return jax.jit(jax.vmap(
        lambda x, f: ref.propagate_coo_frontier(g, sr, x, f, chunk)))


@pytest.mark.parametrize("dtype", [np.int32, np.float32],
                         ids=["int32", "float32"])
@pytest.mark.parametrize("sr", SELECTIVE, ids=lambda s: s.name)
def test_frontier_pass_is_bit_identical_to_the_dense_pass(sr, dtype):
    """The frontier pass, called once for one row, against the dense pass:
    the same messages reach the same destinations, and a min or max has
    nothing to round."""
    g = _weighted(random_graph(200, 4.0, seed=4), dtype)
    x = _x(sr, (g.n,), dtype)
    f, _ = _frontier_of_arcs(g, 1, 300)
    took, _ = ref.frontier_plan(g, sr, x.shape, f[0])
    assert bool(took)
    got = ref.propagate_coo_frontier(g, sr, x, f[0])
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.propagate_coo(g, sr, x, f[0])))


@pytest.mark.parametrize("sr", SELECTIVE, ids=lambda s: s.name)
@pytest.mark.parametrize("regime", ["empty", "one_trip", "trips", "dense"])
def test_frontier_pass_under_vmap_in_every_regime(sr, regime):
    """Under ``vmap`` over C = 8 slots the one decision spans every slot:
    an empty frontier (no trip), one that fits one trip, one that needs
    several, and one past the dense threshold; each equal to the dense
    pass bit for bit."""
    g = _weighted(random_graph(256, 8.0, seed=6), np.int32)
    k, t, p = ref.frontier_sizes(g, C)
    target = {"empty": 0, "one_trip": k, "trips": 3 * k, "dense": 8 * t}[regime]
    f, total = _frontier_of_arcs(g, C, target)
    x = _x(sr, (C, g.n), np.int32)
    took, read = ref.frontier_plan(g, sr, x.shape, f)
    trips = -(-total // k)
    if regime == "dense":
        assert not bool(took) and int(read) == C * g.num_edges
    else:
        assert bool(took) and int(read) == trips * k
        assert {"empty": trips == 0, "one_trip": trips == 1,
                "trips": trips > 1}[regime]
    got = _vmapped(g, sr)(x, f)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.propagate_coo(g, sr, x, f)))


@pytest.mark.parametrize("sr", SELECTIVE, ids=lambda s: s.name)
def test_frontier_pass_on_padded_and_mutated_graphs(sr):
    """Capacity padding (``src = dst = n`` rows past the real arcs) is
    never read, and an in-capacity ``apply_delta`` keeps ``csr_row``
    current: both equal the dense pass on the trimmed graph."""
    base = _weighted(random_graph(120, 4.0, seed=8), np.int32)
    padded = base.with_capacity(max_e=base.num_edges + 96)
    s, d = np.asarray(base.src)[:5], np.asarray(base.dst)[:5]
    mutated = padded.apply_delta(adds=[(3, 17), (40, 41), (7, 90)],
                                 dels=np.stack([s, d], 1), w=[2, 3, 4])
    assert mutated.edge_capacity == padded.edge_capacity
    x = _x(sr, (C, base.n), np.int32)
    for g in (padded, mutated):
        f, _ = _frontier_of_arcs(g, C, 200)
        assert bool(ref.frontier_plan(g, sr, x.shape, f)[0])
        got = _vmapped(g, sr)(x, f)
        want = ref.propagate_coo(g.trimmed(), sr, x, f)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("shared", [True, False], ids=["shared", "per_lane"])
@pytest.mark.parametrize("sr", SELECTIVE, ids=lambda s: s.name)
def test_frontier_pass_with_lanes(sr, shared):
    """A multi-lane ``x`` of (L, V) per slot: the lanes are rows of the
    pass, with one frontier for every lane or one per lane."""
    g = _weighted(random_graph(150, 4.0, seed=10), np.int32)
    lanes = 3
    x = _x(sr, (C, lanes, g.n), np.int32)
    f, _ = _frontier_of_arcs(g, C * (1 if shared else lanes), 150)
    f = f.reshape((C, 1 if shared else lanes, g.n))
    assert bool(ref.frontier_plan(g, sr, x.shape, f)[0])
    got = _vmapped(g, sr)(x, f)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.propagate_coo(g, sr, x, f)))


def test_frontier_pass_lowers_to_one_branch_and_one_loop():
    """Under ``jit(vmap(...))`` the decision is one real conditional over
    every slot, and the pass one unbatched ``while_loop``: not a select
    over both branches, nor a loop run to the largest slot's trips."""
    g = _weighted(random_graph(64, 3.0, seed=12), np.int32)
    x = _x(MIN_RIGHT, (C, g.n), np.int32)
    f = jnp.zeros((C, g.n), bool)
    text = _vmapped(g, MIN_RIGHT).lower(x, f).as_text()
    assert text.count("stablehlo.case") == 1
    assert text.count("stablehlo.while") == 1
    # the loop carries the flat (C * V + 1) output: no slot axis on it
    assert f"tensor<{C * g.n + 1}xi32>" in text


@pytest.mark.parametrize("sr", [SUM_TIMES, MIN_RIGHT], ids=lambda s: s.name)
def test_dense_pass_without_frontier_or_selective_semiring(sr):
    """``sum_times`` and a call with no frontier keep the dense pass."""
    g = _weighted(random_graph(64, 3.0, seed=14), np.float32)
    x = jnp.asarray(np.random.default_rng(0).random((C, g.n)), jnp.float32)
    f = None if sr is MIN_RIGHT else jnp.ones((C, g.n), bool)
    took, read = ref.frontier_plan(g, sr, x.shape, f)
    assert not bool(took) and int(read) == C * g.num_edges
    text = _vmapped(g, sr).lower(x, f).as_text() if f is not None else \
        jax.jit(lambda x: ref.propagate_coo_frontier(g, sr, x)).lower(x) \
        .as_text()
    assert "stablehlo.case" not in text and "stablehlo.while" not in text
    got = (jax.vmap(lambda x, f: ref.propagate_coo_frontier(g, sr, x, f))(x, f)
           if f is not None else ref.propagate_coo_frontier(g, sr, x))
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.propagate_coo(g, sr, x, f)))
