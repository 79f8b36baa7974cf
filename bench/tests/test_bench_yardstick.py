"""The benchmark's own generators, reference, byte count and arrivals."""
import numpy as np
import pytest

from bench.harness import arrivals, spec
from bench.harness.roofline import least_bytes, peaks

BENCH = spec.benchmark()
KRON = spec.load_module(spec.BENCH / "configs" / "kron_bibfs.py")
TERRAIN = spec.load_module(spec.BENCH / "configs" / "terrain_sssp.py")


def undirected(n, edges):
    e = np.asarray(edges, np.int32)
    src = np.concatenate([e[:, 0], e[:, 1]])
    dst = np.concatenate([e[:, 1], e[:, 0]])
    deg = np.bincount(src, minlength=n)
    return KRON.Data(n, src, dst, np.flatnonzero(deg).astype(np.int32))


@pytest.mark.parametrize("seed", [0, 2**31 + 5])
def test_kronecker_is_graph500_and_deterministic(seed):
    scale, ef = 10, 16
    init = (0.57, 0.19, 0.19, 0.05)
    a = KRON.kronecker_edges(scale, ef, init, np.random.default_rng(seed))
    b = KRON.kronecker_edges(scale, ef, init, np.random.default_rng(seed))
    c = KRON.kronecker_edges(scale, ef, init, np.random.default_rng(seed + 1))
    assert a.shape == (2, ef << scale)  # Graph500's M before dedup
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.min() >= 0 and a.max() < 1 << scale
    cfg = dict(scale=scale, edgefactor=ef, initiator=init)
    data = KRON.build(cfg, np.random.default_rng(seed))
    assert len(data.src) <= KRON.edge_capacity(cfg)
    key = data.src.astype(np.int64) * data.n + data.dst
    assert len(np.unique(key)) == len(key) and (data.src != data.dst).all()
    assert set(zip(data.src, data.dst)) == set(zip(data.dst, data.src))
    # the skew Graph500's initiator makes: a hub far above the mean degree
    deg = np.bincount(data.src, minlength=data.n)
    assert deg.max() > 20 * deg.mean()


def test_terrain_copy_matches_program_generator():
    from repro.core.graph import Graph, grid_terrain

    g, coords = grid_terrain(20, 20, seed=0)
    src, dst, w, c = TERRAIN.grid_terrain(20, 20, np.random.default_rng(0))
    mine = Graph.from_edges(src, dst, 400, w=w, weight_dtype=np.float32)
    for x, y in ((g.src, mine.src), (g.dst, mine.dst), (g.w, mine.w),
                 (coords, c)):
        assert np.array_equal(np.asarray(x), np.asarray(y))


def test_least_bytes_hand_count():
    deg = np.asarray([2, 2, 2, 3, 2, 1])
    # arcs cost 4 + 4 + 4 bytes, each frontier vertex a 4-byte read + write
    assert least_bytes([[0], [1, 2]], deg) == (2 * 12 + 8) + (4 * 12 + 16)
    assert least_bytes([[0]], deg, weight_bytes=4) == 2 * 16 + 8


def test_kron_reference_on_a_six_vertex_graph():
    # 0-1, 0-2, 1-3, 2-3, 3-4, 4-5: d(0, 5) = 4, BiBFS meets at 3 after
    # 2 supersteps: forward frontiers {0}, {1, 2}; backward {5}, {4}
    data = undirected(6, [(0, 1), (0, 2), (1, 3), (2, 3), (3, 4), (4, 5)])
    (r,) = KRON.reference(data, {}, [(0, 5)])
    assert r["answer"] == 4
    assert r["least_bytes"] == (32 + 64) + (20 + 32)
    (u,) = KRON.reference(undirected(6, [(0, 1), (2, 3)]), {}, [(0, 3)])
    assert u["answer"] is None


def test_kron_control_takes_a_non_shortest_meeting():
    # d(5, 4) = 3 via 5-3-2-4; vertex 0 is 2 hops from both ends
    data = undirected(6, [(5, 3), (3, 2), (2, 4), (3, 0), (0, 2)])
    ref = KRON.reference(data, {}, [(5, 4)])
    assert ref[0]["answer"] == 3
    assert KRON.control(data, {}, [(5, 4)], ref) == [4]
    limits = {"wrong_answers": 0}
    assert KRON.compare([3], ref, limits) == ({"wrong_answers": (0, 0)}, 0)
    assert KRON.compare([4], ref, limits)[0]["wrong_answers"][0] == 1
    assert KRON.compare([None], ref, limits)[1] == 0  # the harness counts it


def test_terrain_pool_offers_the_same_lengths_on_every_seed():
    data = TERRAIN.build(dict(side=32), np.random.default_rng(0))
    cfg = dict(length_strata=16)

    def lengths(seed):
        out = []
        for s, t in TERRAIN.pool(data, cfg, np.random.default_rng(seed)):
            s, t = int(s), int(t)
            assert 0 <= s < 32 * 32 and 0 <= t < 32 * 32
            out.append((abs(s // 32 - t // 32), abs(s % 32 - t % 32)))
        return sorted(tuple(sorted(o)) for o in out), out

    a, pa = lengths(1)
    b, pb = lengths(2**31 + 1)
    assert a == b and pa != pb
    cheb = sorted(max(o) for o in a)
    assert cheb == sorted(TERRAIN.chebyshev_quantiles(32, 16))


def test_kron_pool_offers_the_configured_distances():
    cfg = dict(scale=9, edgefactor=16, initiator=(0.57, 0.19, 0.19, 0.05),
               hop_distances=[1, 2, 2, 3],
               ball_targets=[[40, 40], [1, 900], [900, 1], [10**6, 0]])
    data = KRON.build(cfg, np.random.default_rng(4))
    pool = KRON.pool(data, cfg, np.random.default_rng(5))
    ref = KRON.reference(data, cfg, pool)
    assert [r["answer"] for r in ref] == cfg["hop_distances"]
    assert all(p[0] in data.endpoints and p[1] in data.endpoints
               for p in pool)


def test_poisson_arrivals_same_gaps_other_order():
    n = 2 * arrivals.CYCLE
    spec_ = dict(process="poisson", rate=2.0)
    a = arrivals.arrivals(spec_, n, np.random.default_rng(1))
    b = arrivals.arrivals(spec_, n, np.random.default_rng(2**31 + 9))
    assert not np.array_equal(a, b)
    assert np.allclose(np.sort(np.diff(a, prepend=0)),
                       np.sort(np.diff(b, prepend=0)))
    assert abs(a[-1] - n / 2.0) < 1.0  # n arrivals at 2/s
    with pytest.raises(TypeError):  # no knobs beside the rate
        arrivals.arrivals(dict(spec_, cycle=8), n, np.random.default_rng(1))


def test_unknown_device_has_no_peaks():
    assert peaks("TPU v5 lite")["hbm_bw"] == 819e9
    with pytest.raises(ValueError):
        peaks("cpu")
