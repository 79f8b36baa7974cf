"""The Quegel paper's terrain shortest paths (section 5.3), TerrainSSSP.

The plain side of the ``quegel-terrain`` configuration: a copy of the
program's terrain generator (so that a change to the program cannot change
the data), the query pool, the reference (float64 Dijkstra of
``scipy.sparse.csgraph`` on the same float32 weights), the least bytes each
query needs, and the control.  ``make_engine`` is the one call into the
system under test.
"""
from __future__ import annotations

import dataclasses

import numpy as np
from scipy.sparse import csgraph, csr_matrix

from bench.harness.roofline import least_bytes

SOURCES_PER_BLOCK = 32  # Dijkstra rows held at once


@dataclasses.dataclass
class Data:
    side: int
    src: np.ndarray
    dst: np.ndarray
    w: np.ndarray       # float32 metres
    coords: np.ndarray  # (n, 3) float32


def grid_terrain(rows: int, cols: int, rng, eps_subdiv: int = 1):
    """Copy of ``repro.core.graph.grid_terrain`` (numpy only): an elevation
    mesh at 10 m spacing, 8-connected with per-cell shortcut (diagonal)
    arcs both ways, 3-D Euclidean float32 weights.  Returns
    ``(src, dst, w, coords)``; ``rng`` plays the generator's ``seed``."""
    r = rows * eps_subdiv - (eps_subdiv - 1)
    c = cols * eps_subdiv - (eps_subdiv - 1)
    yy0, xx0 = np.meshgrid(np.arange(rows), np.arange(cols), indexing="ij")
    elev = (
        12.0 * np.sin(yy0 / 6.0) * np.cos(xx0 / 7.0)
        + 6.0 * np.sin((yy0 + xx0) / 11.0)
        + rng.random((rows, cols)) * 1.5
    ).astype(np.float32)
    yi = np.linspace(0, rows - 1, r)
    xi = np.linspace(0, cols - 1, c)
    y0 = np.clip(yi.astype(int), 0, rows - 2)
    x0 = np.clip(xi.astype(int), 0, cols - 2)
    fy = (yi - y0)[:, None]
    fx = (xi - x0)[None, :]
    z = (
        elev[y0][:, x0] * (1 - fy) * (1 - fx)
        + elev[y0 + 1][:, x0] * fy * (1 - fx)
        + elev[y0][:, x0 + 1] * (1 - fy) * fx
        + elev[y0 + 1][:, x0 + 1] * fy * fx
    ).astype(np.float32)
    spacing = 10.0 / eps_subdiv
    ys, xs = np.meshgrid(np.arange(r), np.arange(c), indexing="ij")
    coords = np.stack(
        [xs.ravel() * spacing, ys.ravel() * spacing, z.ravel()], axis=1
    ).astype(np.float32)
    src_l, dst_l = [], []
    for dy, dx in ((0, 1), (1, 0), (1, 1), (1, -1)):
        y = np.arange(max(0, -dy), r - max(0, dy))
        x = np.arange(max(0, -dx), c - max(0, dx))
        yy, xx = np.meshgrid(y, x, indexing="ij")
        a = (yy * c + xx).ravel()
        b = ((yy + dy) * c + xx + dx).ravel()
        src_l += [a, b]
        dst_l += [b, a]
    src = np.concatenate(src_l).astype(np.int32)
    dst = np.concatenate(dst_l).astype(np.int32)
    w = np.linalg.norm(coords[src] - coords[dst], axis=1).astype(np.float32)
    return src, dst, w, coords


def build(cfg: dict, rng) -> Data:
    side = cfg["side"]
    src, dst, w, coords = grid_terrain(side, side, rng)
    return Data(side, src, dst, w, coords)


def make_engine(data: Data, cfg: dict, weight_dtype=np.float32):
    from repro.apps.terrain import make_terrain_engine
    from repro.core.graph import Graph

    g = Graph.from_edges(data.src, data.dst, data.side ** 2, w=data.w,
                         weight_dtype=weight_dtype)
    return make_terrain_engine(g, data.coords, capacity=cfg["capacity"],
                               backend=cfg["backend"], arg_carried=True)


def chebyshev_quantiles(side: int, k: int) -> np.ndarray:
    """The Chebyshev distance max(|dx|, |dy|) of a uniform pair of grid
    points, at quantiles ``(i + 0.5) / k``."""
    ell = np.arange(side)
    p1 = ((2 * ell + 1) * side - ell * (ell + 1)) / side**2  # P(|dx| <= l)
    return np.searchsorted(p1**2, (np.arange(k) + 0.5) / k)


def offsets(side: int, k: int) -> np.ndarray:
    """``k`` fixed (dx, dy): Chebyshev lengths of uniform pairs at quantiles
    ``(i + 0.5) / k``, each at a fixed point of its ring (golden-ratio
    steps round the 8 * length ring points)."""
    out = []
    for i, length in enumerate(chebyshev_quantiles(side, k)):
        length = int(length)
        if length == 0:
            out.append((0, 0))
            continue
        edge, j = divmod(int((i * 0.6180339887498949) % 1.0 * 8 * length),
                         2 * length)
        out.append([(-length + j, -length), (length, -length + j),
                    (length - j, length), (-length, length - j)][edge])
    return np.asarray(out, np.int64)


def pool(data: Data, cfg: dict, rng) -> list:
    """One (s, t) per fixed offset (``offsets``), turned by a seeded
    symmetry of the square (which keeps its length) and placed uniformly
    where it fits the grid: every seed offers the same set of lengths."""
    side, out = data.side, []
    for dx, dy in offsets(side, cfg["length_strata"]):
        if rng.integers(2):
            dx, dy = dy, dx
        dx, dy = dx * rng.choice((-1, 1)), dy * rng.choice((-1, 1))
        x = int(rng.integers(max(0, -dx), side - max(0, dx)))
        y = int(rng.integers(max(0, -dy), side - max(0, dy)))
        out.append(np.asarray([y * side + x, (y + dy) * side + x + dx],
                              np.int32))
    return out


def served(result) -> float:
    return float(result["dist"])


# ------------------------------------------------------------- reference
def reference(data: Data, cfg: dict, queries) -> list:
    """Per query ``{"answer", "least_bytes"}``: the float64 Dijkstra
    distance, and the bytes that relaxing every vertex nearer than t once
    (as Dijkstra does before it settles t) cannot avoid."""
    n = data.side ** 2
    a = csr_matrix((data.w.astype(np.float64), (data.src, data.dst)),
                   shape=(n, n))
    out_deg = np.diff(a.indptr)
    queries = np.asarray(queries, np.int64).reshape(-1, 2)
    out = []
    for b in range(0, len(queries), SOURCES_PER_BLOCK):
        block = queries[b:b + SOURCES_PER_BLOCK]
        dist = csgraph.dijkstra(a, directed=True, indices=block[:, 0])
        for row, t in zip(dist, block[:, 1]):
            out.append(dict(
                answer=float(row[t]),
                least_bytes=least_bytes([np.flatnonzero(row < row[t])],
                                        out_deg, weight_bytes=4),
            ))
    return out


def control(data: Data, cfg: dict, queries, ref: list) -> list:
    """The control is the program itself with its arc weights held in
    bfloat16, the step below the float32 the configuration states (and
    the one that would halve the weights' bytes), on the same queries."""
    import jax.numpy as jnp

    eng = make_engine(data, cfg, weight_dtype=jnp.bfloat16)
    qids = [eng.submit(q) for q in queries]
    eng.run_until_drained()
    return [served(eng.poll(q)[1]) for q in qids]


def compare(answers: list, ref: list, limits: dict) -> dict:
    """``({name: (value, limit)}, answers that fail)``: the largest error of
    a served distance relative to the float64 reference (over 1 m where the
    two ends meet); an unanswered query is left to the harness's own
    count."""
    errs = [abs(got - r["answer"]) / max(r["answer"], 1.0)
            for got, r in zip(answers, ref) if got is not None]
    limit = limits["max_rel_err"]
    return ({"max_rel_err": (max(errs, default=0.0), limit)},
            sum(e > limit for e in errs))
