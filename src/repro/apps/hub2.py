"""Hub²-Labeling for PPSP queries — paper §5.1.2.

The index: pick the k highest-degree vertices as hubs H.  Every vertex
keeps hub-distance labels L(v) = {<h, d(v,h)>} restricted to *core-hubs*
(hubs h with no other hub on any shortest v-h path); hubs keep labels to
all hubs.

Exactly as in the paper, **indexing is itself a Quegel job**: the query set
is {<h> | h in H}, each query a flagged BFS computing d(h, .) and the
pre_H(.) flag ("some shortest path from h passes another hub").  The engine
batches these k BFS queries C at a time under superstep-sharing.

Querying: d_ub = min_{h_s, h_t} d(s,h_s) + d(h_s,h_t) + d(h_t,t) from the
labels (the paper computes this in 2 supersteps via the aggregator; we fold
the same reduction into admission), then a BiBFS over the non-hub induced
subgraph with the early cutoff at superstep 1 + floor(d_ub / 2).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import QuegelEngine, StepCtx, VertexProgram
from repro.core.graph import Graph
from repro.core.semiring import INF, MAX_RIGHT, MIN_RIGHT


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class HubIndex:
    """The V-data index loaded by every worker before querying."""

    hub_ids: jnp.ndarray  # (k,) int32 vertex ids of hubs
    is_hub: jnp.ndarray  # (V,) bool
    hub_dist: jnp.ndarray  # (k, V) int32 d(h, v), INF if unreachable
    core: jnp.ndarray  # (k, V) bool — h is a core-hub of v (labels kept)

    @property
    def k(self) -> int:
        return int(self.hub_ids.shape[0])

    def hub_hub(self) -> jnp.ndarray:
        """(k, k) pairwise hub distance matrix d(h_i, h_j)."""
        return self.hub_dist[:, self.hub_ids]


def pick_hubs(graph: Graph, k: int, mode: str = "degree") -> np.ndarray:
    """Top-k degree vertices (paper: in/out/sum for directed; they found the
    choices similar and report in-degree)."""
    if mode == "in":
        deg = np.asarray(graph.in_deg)
    elif mode == "out":
        deg = np.asarray(graph.out_deg)
    else:
        deg = np.asarray(graph.in_deg) + np.asarray(graph.out_deg)
    deg = deg[: graph.n_real]
    return np.argsort(-deg, kind="stable")[:k].astype(np.int32)


class HubLabelBFS(VertexProgram):
    """The indexing query <h>: BFS recording d(h, v) and pre_H(v).

    A vertex's outgoing flag is TRUE when a shortest path from h to it
    passes a hub other than h (itself counting if it is a hub) — receivers
    of a TRUE flag have h excluded from their core-hub set.
    """

    def __init__(self, is_hub: jnp.ndarray):
        self.is_hub = is_hub

    def init(self, graph: Graph, query, index=None):
        h = query[0]
        dist = jnp.full((graph.n,), INF, jnp.int32).at[h].set(0)
        return dict(
            dist=dist,
            pre=jnp.zeros((graph.n,), bool),
            frontier=jnp.zeros((graph.n,), bool).at[h].set(True),
        )

    def superstep(self, state, ctx: StepCtx):
        dist, pre, frontier = state["dist"], state["pre"], state["frontier"]
        h = ctx.query[0]
        # flag lane: sender emits 1 iff it is a hub (other than h) or its
        # own pre flag is set
        sender_flag = ((self.is_hub & (jnp.arange(dist.shape[0]) != h)) | pre).astype(jnp.int32)
        got_d = ctx.propagate(MIN_RIGHT, dist, frontier)
        got_f = ctx.propagate(MAX_RIGHT, sender_flag, frontier)
        newly = (got_d < INF) & (dist >= INF)
        dist = jnp.where(newly, ctx.step, dist)
        pre = pre | (newly & (got_f > 0))
        done = ~newly.any()
        return dict(dist=dist, pre=pre, frontier=newly), done

    def extract(self, state, query):
        return dict(dist=state["dist"], pre=state["pre"])

    def frontier_of(self, state):
        return {"default": state["frontier"]}


def build_hub_index(graph: Graph, k: int, capacity: int = 8, backend: str = "coo",
                    hubs=None, **kw) -> HubIndex:
    """Run the |H| BFS queries through the engine and assemble the labels.

    HubLabelBFS mixes min_right (distance) and max_right (pre-flag) on the
    SAME view, and one tile table encodes exactly one add-identity
    (DESIGN.md §2) — the engine's tile backends build one table per
    semiring on demand, so no table plumbing is needed here.

    ``hubs`` pins an explicit hub set (default: ``pick_hubs(graph, k)``) —
    the incremental-maintenance parity tests rebuild against the mutated
    graph with the OLD hub set pinned, since ``maintain_hub_index`` keeps
    hubs fixed on the incremental path.
    """
    index, _ = _build_hub_index_counted(graph, k, capacity, backend,
                                        hubs=hubs, **kw)
    return index


def _build_hub_index_counted(graph: Graph, k: int, capacity: int = 8,
                             backend: str = "coo", hubs=None, **kw):
    """(HubIndex, engine rounds spent building) — the round count is what
    the store's zero-rebuild guarantee is asserted against."""
    hubs = pick_hubs(graph, k) if hubs is None \
        else np.asarray(hubs, np.int32)
    is_hub = jnp.zeros((graph.n,), bool).at[jnp.asarray(hubs)].set(True)
    eng = QuegelEngine(
        graph,
        HubLabelBFS(is_hub),
        capacity,
        backend=backend,
        example_query=jnp.zeros((1,), jnp.int32),
        **kw,
    )
    qids = [eng.submit(jnp.asarray([h], jnp.int32)) for h in hubs]
    res = eng.run_until_drained()
    hub_dist = np.stack([np.asarray(res[q]["dist"]) for q in qids])  # (k, V)
    pre = np.stack([np.asarray(res[q]["pre"]) for q in qids])  # (k, V)
    reach = hub_dist < INF
    is_hub_np = np.asarray(is_hub)
    # core-hub of v: reachable & no other hub on any shortest path; hubs
    # always keep all (reachable) hub labels.
    core = reach & (~pre | is_hub_np[None, :])
    return HubIndex(
        hub_ids=jnp.asarray(hubs),
        is_hub=is_hub,
        hub_dist=jnp.asarray(hub_dist),
        core=jnp.asarray(core),
    ), eng.stats.rounds


def load_or_build_hub_index(store, graph: Graph, k: int, capacity: int = 8,
                            backend: str = "coo", name: str = "index",
                            **kw) -> tuple[HubIndex, dict]:
    """Boot the Hub² index from a durable store (DESIGN.md §10), building
    and persisting it only on first use.  Returns ``(index, info)`` with
    ``info = {built, index_rounds, graph_hash}`` — ``index_rounds`` is 0 on
    a store hit (no index-construction super-rounds ran), which is the
    whole point: restore is a load, not a rebuild.  The entry is bound to
    ``graph.content_hash()``: a store written against a different graph
    (or with a stale index) is rebuilt, never silently served."""
    ghash = graph.content_hash()
    if store.exists(name) and store.meta(name).get("graph_hash") == ghash:
        return store.get(name), {
            "built": False, "index_rounds": 0, "graph_hash": ghash,
        }
    index, rounds = _build_hub_index_counted(graph, k, capacity, backend,
                                             **kw)
    store.put(name, index, meta={"graph_hash": ghash, "k": int(k)})
    return index, {
        "built": True, "index_rounds": int(rounds), "graph_hash": ghash,
    }


# ------------------------------------------------ incremental maintenance
def _relabel_hubs(graph: Graph, is_hub, hub_ids, rows):
    """Label BFS for ``rows`` hub queries on ``graph`` — semantics
    identical to :class:`HubLabelBFS` (1-based Pregel supersteps, a
    frontier-gated min_right distance lane plus a max_right sender-flag
    lane), but in plain numpy on the host: maintenance re-runs only the
    affected rows, and an engine construction + compile per delta — or
    even per-superstep jnp dispatch — would swamp the incremental win it
    exists to deliver.  A vertex is newly reached iff it has a frontier
    in-neighbor (every frontier sender carries a finite distance), and its
    pre flag is set iff some such sender is another hub or flagged itself.
    Returns ``(dist, pre)`` as ``(m, V)`` numpy arrays."""
    src, dst, _ = graph._edges_np()
    n = graph.n
    is_hub_np = np.asarray(is_hub)
    hubs = np.asarray(hub_ids, np.int32)[np.asarray(rows)]
    dist = np.full((len(hubs), n), INF, np.int32)
    pre = np.zeros((len(hubs), n), bool)
    for q, h in enumerate(int(h) for h in hubs):
        other_hub = is_hub_np.copy()
        other_hub[h] = False
        dq, pq = dist[q], pre[q]
        dq[h] = 0
        frontier = np.zeros(n, bool)
        frontier[h] = True
        step = 0
        while frontier.any():
            step += 1  # Pregel supersteps are 1-based, as in HubLabelBFS
            e = frontier[src]
            reach = np.zeros(n, bool)
            reach[dst[e]] = True
            # flag lane, evaluated on the step-start pre (as the engine
            # does: sender_flag is read before this superstep's updates)
            flagged = np.zeros(n, bool)
            flagged[dst[e & (other_hub | pq)[src]]] = True
            newly = reach & (dq >= INF)
            dq[newly] = step
            pq |= newly & flagged
            frontier = newly
    return dist, pre


def affected_hubs(index: HubIndex, delta) -> np.ndarray:
    """Hub rows whose labels (dist or pre flags) can change under ``delta``.

    With ``d_h = hub_dist[h]`` on the PRE-mutation graph:

    * insert (u, v) affects h  iff  d_h[u] + 1 <= d_h[v] — strict ``<``
      shortens some distance; equality adds a shortest-path-DAG edge,
      which can only flip pre flags (a tie path through another hub).
    * delete (u, v) affects h  iff  d_h[u] + 1 == d_h[v] — only edges ON
      the shortest-path DAG of h carry its BFS; removing a non-DAG edge
      changes neither distances nor flags.

    INF arithmetic is safe: ``INF + 1 <= d`` is false for any label
    (labels are bounded by INF), evaluated in int64.
    """
    hd = np.asarray(index.hub_dist).astype(np.int64)  # (k, V)
    aff = np.zeros(hd.shape[0], bool)
    if len(delta.add_src):
        u, v = np.asarray(delta.add_src), np.asarray(delta.add_dst)
        aff |= (hd[:, u] + 1 <= hd[:, v]).any(axis=1)
    if len(delta.del_src):
        u, v = np.asarray(delta.del_src), np.asarray(delta.del_dst)
        aff |= (hd[:, u] + 1 == hd[:, v]).any(axis=1)
    return np.nonzero(aff)[0]


def maintain_hub_index(graph: Graph, index: HubIndex, delta, *,
                       threshold: float = 0.01, capacity: int = 8,
                       backend: str = "coo", **kw):
    """Maintain a Hub² index across one ``Graph.apply_delta`` (DESIGN.md
    §12).  Returns ``(new_index, info)``.

    Small deltas (``delta.size <= threshold * |E|``) take the incremental
    path: the hub set stays FIXED, only the rows ``affected_hubs`` names
    are re-labeled (an eager batched BFS, no engine build), and the
    ``core`` mask is recomputed for exactly those rows.  Past the
    threshold the whole index is rebuilt via :func:`build_hub_index`,
    re-picking hubs from the mutated degree distribution.

    Fixed-hub incremental maintenance is SOUND — ``Hub2PPSP`` answers
    correctly under any hub set — but hub quality can drift as mutations
    reshape degrees; the rebuild threshold is also the quality backstop.

    ``info``: mode ('incremental'|'rebuild'), k, frac (delta.size/|E|),
    affected_hubs (k on rebuild), threshold.
    """
    k = index.k
    frac = delta.size / max(1, graph.num_edges)
    base = dict(k=k, frac=float(frac), threshold=float(threshold))
    if frac > threshold:
        rebuilt, _ = _build_hub_index_counted(graph, k, capacity, backend,
                                              **kw)
        return rebuilt, dict(mode="rebuild", affected_hubs=k, **base)
    rows = affected_hubs(index, delta)
    if not len(rows):
        return index, dict(mode="incremental", affected_hubs=0, **base)
    dist_rows, pre_rows = _relabel_hubs(graph, index.is_hub, index.hub_ids,
                                        rows)
    hub_dist = np.asarray(index.hub_dist).copy()
    core = np.asarray(index.core).copy()
    is_hub_np = np.asarray(index.is_hub)
    hub_dist[rows] = dist_rows
    # pre is not stored in HubIndex (only needed transiently): recompute
    # core for exactly the re-labeled rows from their fresh dist/pre.
    core[rows] = (dist_rows < INF) & (~pre_rows | is_hub_np[None, :])
    new_index = HubIndex(
        hub_ids=index.hub_ids,
        is_hub=index.is_hub,
        hub_dist=jnp.asarray(hub_dist),
        core=jnp.asarray(core),
    )
    return new_index, dict(mode="incremental", affected_hubs=int(len(rows)),
                           **base)


def hub_index_updater(threshold: float = 0.01, capacity: int = 8,
                      backend: str = "coo", **kw):
    """Factory for ``QuegelEngine(index_fn=...)``: adapts
    :func:`maintain_hub_index` to the engine's index-maintainer protocol
    ``fn(new_graph, old_index, delta) -> (new_index, info)``."""

    def fn(new_graph, old_index, delta):
        return maintain_hub_index(new_graph, old_index, delta,
                                  threshold=threshold, capacity=capacity,
                                  backend=backend, **kw)

    return fn


class Hub2PPSP(VertexProgram):
    """PPSP query using the Hub² index (paper's querying algorithm):
    BiBFS over the non-hub induced subgraph, upper-bounded by d_ub."""

    def init(self, graph: Graph, query, index: HubIndex = None):
        s, t = query[0], query[1]
        lab_s = jnp.where(index.core[:, s], index.hub_dist[:, s], INF)  # (k,)
        lab_t = jnp.where(index.core[:, t], index.hub_dist[:, t], INF)
        hh = index.hub_hub()  # (k, k)
        # d_ub = min_{hs,ht} d(s,hs) + d(hs,ht) + d(ht,t).  Saturating sum in
        # float32 (int64 unavailable without x64; small sums < 2^24 exact).
        tot = (
            jnp.minimum(lab_s, INF)[:, None].astype(jnp.float32)
            + jnp.minimum(hh, INF).astype(jnp.float32)
            + jnp.minimum(lab_t, INF)[None, :].astype(jnp.float32)
        )
        tmin = tot.min()
        d_ub = jnp.where(tmin < INF, tmin, INF).astype(jnp.int32)
        n = graph.n
        ds = jnp.full((n,), INF, jnp.int32).at[s].set(0)
        dt = jnp.full((n,), INF, jnp.int32).at[t].set(0)
        return dict(
            ds=ds,
            dt=dt,
            ff=jnp.zeros((n,), bool).at[s].set(True),
            fb=jnp.zeros((n,), bool).at[t].set(True),
            d_ub=d_ub,
            bibest=jnp.asarray(INF, jnp.int32),
        )

    def superstep(self, state, ctx: StepCtx):
        idx: HubIndex = ctx.index
        ds, dt = state["ds"], state["dt"]
        got_f = ctx.propagate(MIN_RIGHT, ds, state["ff"])
        got_b = ctx.propagate(MIN_RIGHT, dt, state["fb"], which="rev")
        new_f = (got_f < INF) & (ds >= INF)
        new_b = (got_b < INF) & (dt >= INF)
        ds = jnp.where(new_f, ctx.step, ds)
        dt = jnp.where(new_b, ctx.step, dt)
        # hubs vote to halt immediately: BiBFS explores G[V - H]
        ff = new_f & ~idx.is_hub
        fb = new_b & ~idx.is_hub
        both = jnp.where((ds < INF) & (dt < INF) & ~idx.is_hub, ds + dt, INF)
        bibest = jnp.minimum(state["bibest"], both.min())
        # early cutoff (paper): a non-hub vertex bi-reached at superstep
        # >= 1 + floor(d_ub/2) cannot beat d_ub
        cutoff = ctx.step >= 1 + state["d_ub"] // 2
        dead = ~ff.any() | ~fb.any()
        done = (bibest < INF) | cutoff | dead
        return dict(
            ds=ds, dt=dt, ff=ff, fb=fb, d_ub=state["d_ub"], bibest=bibest
        ), done

    def extract(self, state, query):
        visited = ((state["ds"] < INF) | (state["dt"] < INF)).sum()
        return dict(
            dist=jnp.minimum(state["d_ub"], state["bibest"]), visited=visited
        )

    def frontier_of(self, state):
        return {"default": state["ff"], "rev": state["fb"]}


def make_hub2_engine(graph: Graph, index: HubIndex, capacity: int = 8, **kw):
    return QuegelEngine(
        graph,
        Hub2PPSP(),
        capacity,
        index=index,
        aux_graphs={"rev": graph.reverse()},
        example_query=jnp.zeros((2,), jnp.int32),
        **kw,
    )
