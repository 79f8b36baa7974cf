"""PPSP (point-to-point shortest path) queries — paper §5.1.1.

BFS and bidirectional BFS vertex programs on unweighted graphs.  Distances
are hop counts; the result is d(s, t) (INF when unreachable).

Superstep numbering: the paper's superstep 1 only broadcasts from `s`; our
dense formulation fuses broadcast+receive, so our superstep i corresponds to
the paper's superstep i+1 (wavefront at distance i after round i).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.engine import StepCtx, VertexProgram
from repro.core.graph import Graph
from repro.core.semiring import INF, MIN_RIGHT


def _onehot(n, idx, dtype=bool):
    return jnp.zeros((n,), dtype).at[idx].set(True)


class BFSProgram(VertexProgram):
    """Forward BFS from s until t is reached (paper's simplest PPSP)."""

    def init(self, graph: Graph, query, index=None):
        s, t = query[0], query[1]
        dist = jnp.full((graph.n,), INF, jnp.int32).at[s].set(0)
        return dict(dist=dist, frontier=_onehot(graph.n, s))

    def superstep(self, state, ctx: StepCtx):
        dist, frontier = state["dist"], state["frontier"]
        t = ctx.query[1]
        got = ctx.propagate(MIN_RIGHT, dist, frontier)
        newly = (got < INF) & (dist >= INF)
        dist = jnp.where(newly, ctx.step, dist)
        reached_t = dist[t] < INF  # force_terminate()
        done = reached_t | ~newly.any()
        return dict(dist=dist, frontier=newly), done

    def extract(self, state, query):
        t = query[1]
        visited = (state["dist"] < INF).sum()
        return dict(dist=state["dist"][t], visited=visited)

    def frontier_of(self, state):
        return {"default": state["frontier"]}


class BiBFSProgram(VertexProgram):
    """Bidirectional BFS (paper §5.1.1): forward from s on G, backward from
    t on G^R; stop when some vertex is bi-reached (or a frontier empties —
    the paper's aggregator-based early stop for small CCs)."""

    def init(self, graph: Graph, query, index=None):
        s, t = query[0], query[1]
        ds = jnp.full((graph.n,), INF, jnp.int32).at[s].set(0)
        dt = jnp.full((graph.n,), INF, jnp.int32).at[t].set(0)
        return dict(
            ds=ds,
            dt=dt,
            ff=_onehot(graph.n, s),
            fb=_onehot(graph.n, t),
            best=jnp.asarray(INF, jnp.int32),
        )

    def superstep(self, state, ctx: StepCtx):
        ds, dt = state["ds"], state["dt"]
        got_f = ctx.propagate(MIN_RIGHT, ds, state["ff"])
        got_b = ctx.propagate(MIN_RIGHT, dt, state["fb"], which="rev")
        new_f = (got_f < INF) & (ds >= INF)
        new_b = (got_b < INF) & (dt >= INF)
        ds = jnp.where(new_f, ctx.step, ds)
        dt = jnp.where(new_b, ctx.step, dt)
        both = jnp.where((ds < INF) & (dt < INF), ds + dt, INF)
        best = jnp.minimum(state["best"], both.min())
        bi_reached = best < INF
        dead = ~new_f.any() | ~new_b.any()  # a direction went silent
        done = bi_reached | dead
        return dict(ds=ds, dt=dt, ff=new_f, fb=new_b, best=best), done

    def extract(self, state, query):
        visited = ((state["ds"] < INF) | (state["dt"] < INF)).sum()
        return dict(dist=jnp.minimum(state["best"], INF), visited=visited)

    def frontier_of(self, state):
        return {"default": state["ff"], "rev": state["fb"]}


def make_bibfs_engine(graph: Graph, capacity: int = 8, **kw):
    """Convenience constructor wiring the reverse-graph view.  Tile
    backends build their per-semiring block tables inside the engine's
    PropagateBackends (DESIGN.md §2) — no table plumbing here."""
    from repro.core.engine import QuegelEngine

    return QuegelEngine(
        graph,
        BiBFSProgram(),
        capacity,
        aux_graphs={"rev": graph.reverse()},
        example_query=jnp.zeros((2,), jnp.int32),
        **kw,
    )


def make_bfs_engine(graph: Graph, capacity: int = 8, **kw):
    from repro.core.engine import QuegelEngine

    return QuegelEngine(
        graph,
        BFSProgram(),
        capacity,
        example_query=jnp.zeros((2,), jnp.int32),
        **kw,
    )
