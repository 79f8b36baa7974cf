"""Graph500 Kronecker graph served by BiBFS point-to-point hop queries.

The plain side of the ``graph500-kron`` configuration: the Graph500
generator (specification v3, section 3), the query pool (endpoints of
degree >= 1, as Graph500 draws its search keys, at fixed hop distances
and balls), the reference (``scipy.sparse.csgraph`` BFS hop
counts, nothing of the program), the least bytes each query needs, and
the control.  ``make_engine`` is the one
call into the system under test.
"""
from __future__ import annotations

import dataclasses

import numpy as np
from scipy.sparse import csgraph, csr_matrix

from bench.harness.roofline import least_bytes

# The program reports an unreached target as this distance.
UNREACHED = 2**30
# Uniform sources whose pairs with every endpoint ``ball_targets`` reads.
SOURCES = 256


@dataclasses.dataclass
class Data:
    n: int
    src: np.ndarray
    dst: np.ndarray
    endpoints: np.ndarray  # vertices of degree >= 1


def kronecker_edges(scale: int, edgefactor: int, initiator, rng) -> np.ndarray:
    """Graph500's Kronecker generator: ``edgefactor * 2**scale`` edges as a
    (2, M) array of (start, end) labels, vertex labels and edge order
    randomly permuted.  ``initiator`` is (A, B, C, D)."""
    a, b, c, _ = initiator
    n, m = 1 << scale, edgefactor << scale
    ab, c_norm, a_norm = a + b, c / (1.0 - (a + b)), a / (a + b)
    ij = np.zeros((2, m), np.int64)
    for ib in range(scale):
        ii = rng.random(m) > ab
        jj = rng.random(m) > np.where(ii, c_norm, a_norm)
        ij[0] += ii.astype(np.int64) << ib
        ij[1] += jj.astype(np.int64) << ib
    ij = rng.permutation(n)[ij]
    return ij[:, rng.permutation(m)]


def edge_capacity(cfg: dict) -> int:
    """Arcs of the undirected graph before self-loops and duplicates go:
    a bound every seed's graph fits, so every seed compiles one shape."""
    return 2 * cfg["edgefactor"] << cfg["scale"]


def build(cfg: dict, rng) -> Data:
    n = 1 << cfg["scale"]
    i, j = kronecker_edges(cfg["scale"], cfg["edgefactor"],
                           cfg["initiator"], rng)
    src, dst = np.concatenate([i, j]), np.concatenate([j, i])
    keep = src != dst
    key = np.unique(dst[keep] * n + src[keep])
    src, dst = (key % n).astype(np.int32), (key // n).astype(np.int32)
    deg = np.bincount(src, minlength=n)
    return Data(n, src, dst, np.flatnonzero(deg).astype(np.int32))


def make_engine(data: Data, cfg: dict):
    from repro.apps.ppsp import make_bibfs_engine
    from repro.core.graph import Graph

    g = Graph.from_edges(data.src, data.dst, data.n)
    return make_bibfs_engine(g, capacity=cfg["capacity"],
                             backend=cfg["backend"],
                             edge_capacity=edge_capacity(cfg))


def pool(data: Data, cfg: dict, rng) -> list:
    """One (s, t) per entry of ``hop_distances`` d, at that entry's
    ``ball_targets`` (bs, bt): s is the endpoint whose ball lies nearest bs
    among those with an endpoint at distance d, t the endpoint at distance
    d from s whose ball lies nearest bt (ties broken by the seed).  The
    targets are the mean balls of strata of uniform endpoint pairs
    (``ball_targets``), so every seed offers the same supersteps and,
    since a query's frontier arcs follow the balls of its ends
    (``ball``), nearly the same arcs as uniform pairs at those distances."""
    fwd = _matrix(data.n, data.src, data.dst)
    eps, b, tie = data.endpoints, ball(fwd), rng.random(data.n)
    out = []
    for d, (bs, bt) in zip(cfg["hop_distances"], cfg["ball_targets"]):
        for s in eps[np.lexsort((tie[eps], np.abs(b[eps] - bs)))]:
            cand = eps[_levels(fwd, s)[eps] == d]
            if len(cand):
                break
        else:
            raise ValueError(f"no endpoint pair at distance {d}")
        t = cand[np.lexsort((tie[cand], np.abs(b[cand] - bt)))[0]]
        out.append(np.asarray([s, t], np.int32))
    return out


def ball(a: csr_matrix) -> np.ndarray:
    """Each vertex's degree plus its neighbours' degrees (``A @ deg +
    deg``): a BiBFS query of two supersteps reads ball(s) + ball(t) arcs,
    one of a single superstep deg(s) + deg(t)."""
    deg = np.diff(a.indptr)
    return a @ deg + deg


def ball_targets(data: Data, hop_distances: list, rng) -> list:
    """``ball_targets`` for ``hop_distances``, from the uniform endpoint
    pairs of ``SOURCES`` seeded sources and every endpoint.  For a distance
    d that m entries name: the endpoints in order of ball, each weighted by
    the sources it lies at distance d from (by symmetry s's balls at d are
    distributed as t's), cut into m strata of equal weight; each stratum's
    mean ball, from the heaviest stratum down, less what the strata above
    it overshot, taken to the nearest ball the graph has.  Entry i pairs
    the i-th lightest target for s with the i-th heaviest for t, so that
    every t can lie at distance d from a light s or a heavy one, and the
    pool's balls at each distance sum to those of as many uniform pairs."""
    fwd = _matrix(data.n, data.src, data.dst)
    eps = data.endpoints
    balls = ball(fwd)[eps]
    order = np.argsort(balls, kind="stable")
    balls = balls[order]
    weight = {d: np.zeros(len(eps)) for d in set(hop_distances)}
    for s in eps[rng.integers(len(eps), size=SOURCES)]:
        levels = _levels(fwd, s)[eps][order]
        for d, w in weight.items():
            w += levels == d
    picks = {}
    for d, w in weight.items():
        m = hop_distances.count(d)
        cum = np.cumsum(w)
        cut = np.searchsorted(cum, cum[-1] * np.arange(1, m) / m)
        means = [np.average(x, weights=y)
                 for x, y in zip(np.split(balls, cut), np.split(w, cut))]
        got, over = [], 0.0
        for mean in means[::-1]:
            want = mean - over
            i = np.searchsorted(balls, want).clip(1, len(balls) - 1)
            near = balls[i] if balls[i] - want < want - balls[i - 1] \
                else balls[i - 1]
            got.insert(0, int(near))
            over = near - want
        picks[d] = [[got[i], got[m - 1 - i]] for i in range(m)]
    return [picks[d].pop(0) for d in hop_distances]


def served(result) -> int:
    return int(result["dist"])


# ------------------------------------------------------------- reference
def _matrix(n: int, src, dst) -> csr_matrix:
    return csr_matrix((np.ones(len(src), np.int8), (src, dst)), shape=(n, n))


def _levels(a: csr_matrix, s: int) -> np.ndarray:
    """Hop distance from ``s`` to every vertex (-1 where unreached)."""
    order, pred = csgraph.breadth_first_order(
        a, s, directed=True, return_predecessors=True)
    d = np.full(a.shape[0], -1, np.int64)
    d[s] = 0
    rest, up = order[1:], pred[order[1:]]
    while len(rest):
        ok = d[up] >= 0
        d[rest[ok]] = d[up[ok]] + 1
        rest, up = rest[~ok], up[~ok]
    return d


def _supersteps(ds: np.ndarray, dt: np.ndarray) -> int:
    """Supersteps BiBFS runs: until some vertex is reached from both ends
    within j levels, or one end has no vertex at level j."""
    both = (ds >= 0) & (dt >= 0)
    meet = int(np.maximum(ds, dt)[both].min()) if both.any() else 1 << 62
    return min(max(meet, 1), int(ds.max()) + 1, int(dt.max()) + 1)


def reference(data: Data, cfg: dict, queries) -> list:
    """Per query ``{"answer", "least_bytes", "control"}``: the hop distance
    (None when t is unreached), the bytes its supersteps cannot avoid, and
    the answer of the control (below)."""
    fwd = _matrix(data.n, data.src, data.dst)
    bwd = _matrix(data.n, data.dst, data.src)
    out_deg, in_deg = np.diff(fwd.indptr), np.diff(bwd.indptr)
    out = []
    for s, t in np.asarray(queries, np.int64):
        ds, dt = _levels(fwd, s), _levels(bwd, t)
        k = _supersteps(ds, dt)
        frontiers_f = [np.flatnonzero(ds == j) for j in range(k)]
        frontiers_b = [np.flatnonzero(dt == j) for j in range(k)]
        met = np.flatnonzero((ds >= 0) & (dt >= 0) & (ds <= k) & (dt <= k))
        out.append(dict(
            answer=int(ds[t]) if ds[t] >= 0 else None,
            least_bytes=least_bytes(frontiers_f, out_deg)
            + least_bytes(frontiers_b, in_deg),
            control=int(ds[met[0]] + dt[met[0]]) if len(met) else None,
        ))
    return out


def control(data: Data, cfg: dict, queries, ref: list) -> list:
    """The control breaks the configuration's guarantee of a shortest hop
    count: at the last superstep it takes the first vertex reached from
    both ends (the lowest id) instead of the best one, the shortcut of
    reading one bi-reached vertex for a min over all of them."""
    return [r["control"] for r in ref]


def compare(answers: list, ref: list, limits: dict) -> dict:
    """``({name: (value, limit)}, answers that fail)``: the answers that
    differ from the reference hop count (an unreached target is
    ``UNREACHED`` in the program; an unanswered query is left to the
    harness's own count)."""
    wrong = 0
    for got, r in zip(answers, ref):
        want = UNREACHED if r["answer"] is None else r["answer"]
        wrong += got is not None and min(got, UNREACHED) != want
    return {"wrong_answers": (wrong, limits["wrong_answers"])}, wrong
