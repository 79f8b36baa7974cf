"""The Kronecker query pool: drawn at fixed hop distances and balls (the
arcs around an endpoint), so that every seed's pool asks for the same
supersteps and nearly the same frontier arcs as uniform pairs."""
import numpy as np
import pytest

from bench.harness import spec

BENCH = spec.benchmark()
KRON = spec.load_module(spec.BENCH / "configs" / "kron_bibfs.py")
CFG = spec.load_json(spec.ROOT / "bench" / "configs" / "graph500-kron.json")
# the configuration's lists are ball_targets of the graph of this seed
DERIVED_ON = 7


def kron_cfg(**sizes) -> dict:
    return {k: v for k, v in CFG.items() if k != "tiny"} | sizes


def walked_arcs(data, pairs) -> tuple:
    """Per query, the out-degrees of both ends' frontiers summed over the
    supersteps BiBFS runs (what the program's ``quegel.frontier_arcs``
    counts), and the supersteps."""
    a = KRON._matrix(data.n, data.src, data.dst)
    deg = np.diff(a.indptr)
    arcs, steps = [], []
    for s, t in pairs:
        ds, dt = KRON._levels(a, int(s)), KRON._levels(a, int(t))
        k = KRON._supersteps(ds, dt)
        arcs.append(sum(int(deg[ds == j].sum() + deg[dt == j].sum())
                        for j in range(k)))
        steps.append(k)
    return np.asarray(arcs), np.asarray(steps)


def hop_only_pool(data, cfg, rng) -> list:
    """The earlier draw, on hop distance alone: s uniform over the
    endpoints with an endpoint at distance d, t uniform over those."""
    fwd = KRON._matrix(data.n, data.src, data.dst)
    out = []
    for d in cfg["hop_distances"]:
        while True:
            s = int(data.endpoints[rng.integers(len(data.endpoints))])
            cand = data.endpoints[
                KRON._levels(fwd, s)[data.endpoints] == d]
            if len(cand):
                break
        out.append((s, int(rng.choice(cand))))
    return out


@pytest.mark.parametrize("seed", [0, 2**31 + 5])
def test_pool_is_deterministic_for_a_seed(seed):
    cfg = kron_cfg(**CFG["tiny"])
    data = KRON.build(cfg, np.random.default_rng([seed, 0]))
    a = KRON.pool(data, cfg, np.random.default_rng([seed, 1]))
    b = KRON.pool(data, cfg, np.random.default_rng([seed, 1]))
    assert len(a) == len(cfg["hop_distances"])
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert all(p.dtype == np.int32 for p in a)


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_ball_is_the_walked_arcs_of_two_supersteps(seed):
    cfg = kron_cfg(scale=10)
    data = KRON.build(cfg, np.random.default_rng(seed))
    a = KRON._matrix(data.n, data.src, data.dst)
    ball, deg = KRON.ball(a), np.diff(a.indptr)
    rng = np.random.default_rng(seed + 1)
    pairs = data.endpoints[rng.integers(len(data.endpoints), size=(64, 2))]
    arcs, steps = walked_arcs(data, pairs)
    s, t = pairs.T
    one, two = steps == 1, steps == 2
    assert one.any() and two.any()
    assert np.array_equal(arcs[one], deg[s[one]] + deg[t[one]])
    assert np.array_equal(arcs[two], ball[s[two]] + ball[t[two]])


def uniform_arcs(data, hop_distances, rng) -> float:
    """Mean frontier arcs of uniform endpoint pairs (seeded sources, every
    endpoint a target) at the distances of ``hop_distances``, in its mix."""
    a = KRON._matrix(data.n, data.src, data.dst)
    deg, ball, eps = np.diff(a.indptr), KRON.ball(a), data.endpoints
    arcs, dist = [], []
    for s in eps[rng.integers(len(eps), size=KRON.SOURCES)]:
        d = KRON._levels(a, s)[eps]
        arcs.append(np.where(d <= 2, deg[s] + deg[eps], ball[s] + ball[eps]))
        dist.append(d)
    arcs, dist = np.concatenate(arcs), np.concatenate(dist)
    hd = np.asarray(hop_distances)
    return sum(arcs[dist == d].mean() * (hd == d).mean() for d in set(hd))


def test_tiny_quantiles_are_derived_from_uniform_pairs():
    cfg = kron_cfg(**CFG["tiny"])
    data = KRON.build(cfg, np.random.default_rng([DERIVED_ON, 0]))
    got = KRON.ball_targets(data, cfg["hop_distances"],
                            np.random.default_rng([DERIVED_ON, 1]))
    assert got == CFG["tiny"]["ball_targets"]
    assert len(CFG["ball_targets"]) == len(CFG["hop_distances"])


def test_scale_18_targets_are_derived_from_uniform_pairs():
    cfg = kron_cfg()
    data = KRON.build(cfg, np.random.default_rng([DERIVED_ON, 0]))
    got = KRON.ball_targets(data, cfg["hop_distances"],
                            np.random.default_rng([DERIVED_ON, 1]))
    assert got == CFG["ball_targets"]


@pytest.mark.parametrize("scale", [12, 14])
def test_pool_arcs_agree_across_seeds(scale):
    """The scale-18 hop mix at a CPU scale, its ball targets derived there:
    the pools of six seeds read arcs within 3% of each other and of uniform
    pairs at the same distances, where the hop-only draw of the same seeds
    spreads by 30% or more."""
    cfg = kron_cfg(scale=scale)
    ref = KRON.build(cfg, np.random.default_rng([DERIVED_ON, 0]))
    cfg["ball_targets"] = KRON.ball_targets(
        ref, cfg["hop_distances"], np.random.default_rng([DERIVED_ON, 1]))
    stratified, hop_only, uniform = [], [], []
    for seed in (1, 2, 3, 4, 5, 2**31 + 6):
        data = KRON.build(cfg, np.random.default_rng([seed, 0]))
        for out, draw in ((stratified, KRON.pool), (hop_only, hop_only_pool)):
            arcs, steps = walked_arcs(
                data, draw(data, cfg, np.random.default_rng([seed, 1])))
            assert steps.mean() == pytest.approx(1 + 29 / 32)
            out.append(arcs.mean())
        uniform.append(uniform_arcs(data, cfg["hop_distances"],
                                    np.random.default_rng([seed, 2])))
    assert max(stratified) / min(stratified) < 1.03, stratified
    assert np.mean(stratified) == pytest.approx(np.mean(uniform), rel=0.03)
    assert max(hop_only) / min(hop_only) > 1.3, hop_only
