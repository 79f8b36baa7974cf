"""Terrain shortest-path queries — paper §5.3.

The terrain substrate (core.graph.grid_terrain) builds the paper's
transformed network: a DEM elevation mesh subdivided with per-cell shortcut
(diagonal) edges and 3D-Euclidean edge weights, replacing TIN+Chen&Han.

The query program is weighted SSSP (min-plus relaxation) with the paper's
early-termination rule: track d_E^min = min Euclidean distance from s over
the current wavefront (the aggregator); once d_N(s,t) < d_E^min no future
relaxation can improve d_N(s,t) (Euclidean lower-bounds network distance),
so t force-terminates.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.engine import QuegelEngine, StepCtx, VertexProgram
from repro.core.graph import Graph
from repro.core.semiring import INF, MIN_PLUS

FINF = float(INF)


class TerrainSSSP(VertexProgram):
    """index = coords (V, 3) float32 vertex positions."""

    def init(self, graph: Graph, query, index=None):
        s = query[0]
        d = jnp.full((graph.n,), FINF, jnp.float32).at[s].set(0.0)
        return dict(d=d, frontier=jnp.zeros((graph.n,), bool).at[s].set(True))

    def superstep(self, state, ctx: StepCtx):
        coords = ctx.index
        s, t = ctx.query[0], ctx.query[1]
        d = state["d"]
        got = ctx.propagate(MIN_PLUS, d, state["frontier"])
        improved = got < d
        d = jnp.where(improved, got, d)
        # aggregator: min Euclidean distance from s over the new wavefront
        eu = jnp.linalg.norm(coords - coords[s][None, :], axis=-1)
        de_min = jnp.where(improved, eu, FINF).min()
        early = d[t] < de_min  # t calls force_terminate()
        done = early | ~improved.any()
        return dict(d=d, frontier=improved), done

    def frontier_of(self, state):
        return {"default": state["frontier"]}

    def extract(self, state, query):
        t = query[1]
        visited = (state["d"] < FINF).sum()
        return dict(dist=state["d"][t], visited=visited)


def make_terrain_engine(graph: Graph, coords, capacity: int = 8, **kw):
    return QuegelEngine(
        graph,
        TerrainSSSP(),
        capacity,
        index=jnp.asarray(coords),
        example_query=jnp.zeros((2,), jnp.int32),
        **kw,
    )
