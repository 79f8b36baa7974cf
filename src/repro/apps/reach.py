"""P2P reachability queries — paper §5.4.

Pipeline (mirroring the paper's cascade of pre-processing jobs):
  1. SCC condensation: min-label forward/backward coloring (the Pregel
     algorithm of [36]) — queries on G reduce to queries on the DAG G'.
  2. DFS spanning forest pre/post orders (host-side, as the paper computes
     them outside Pregel via [42]).
  3. Three cascaded label jobs on the DAG:
       level  l(v) = longest #hops from any root           (max-plus)
       yes(v) = [pre(v), max_{u in Out(v)} pre(u)]         (max-right, rev)
       no(v)  = [min_{u in Out(v)} post(u), post(v)]       (min-right, rev)
  4. Query program: BiBFS with label pruning —
       yes(t) ⊆ yes(v)  on the forward frontier  => reachable, terminate;
       l(v) >= l(t) or no(t) ⊄ no(v)             => v votes to halt;
       symmetric rules on the backward frontier.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import QuegelEngine, StepCtx, VertexProgram
from repro.core.graph import Graph
from repro.core.semiring import INF, MAX_PLUS, MAX_RIGHT, MIN_RIGHT
from repro.kernels import ops

NEG = np.int32(-(2**30))


# --------------------------------------------------------------------- SCC
def scc_condense(graph: Graph):
    """SCC condensation (host, iterative Kosaraju) -> (scc_of, dag Graph).

    The paper treats SCC as an independent pre-computed job ([36]); the
    device-side FW-BW coloring variant below (`scc_condense_device`)
    demonstrates the Pregel formulation but converges slowly on chain-like
    graphs, so the host algorithm is the default pre-processing path.
    """
    n = graph.n_real
    src = np.asarray(graph.src)
    dst = np.asarray(graph.dst)
    mask = (src < n) & (dst < n)
    src, dst = src[mask], dst[mask]

    def csr(s, d):
        o = np.argsort(s, kind="stable")
        s2, d2 = s[o], d[o]
        starts = np.searchsorted(s2, np.arange(n + 1))
        return starts, d2

    fs, fd = csr(src, dst)
    bs, bd = csr(dst, src)
    # pass 1: iterative DFS finish order
    visited = np.zeros(n, bool)
    finish = []
    for root in range(n):
        if visited[root]:
            continue
        stack = [(root, 0)]
        visited[root] = True
        while stack:
            v, i = stack.pop()
            nbrs = fd[fs[v] : fs[v + 1]]
            while i < len(nbrs) and visited[nbrs[i]]:
                i += 1
            if i < len(nbrs):
                stack.append((v, i + 1))
                u = nbrs[i]
                visited[u] = True
                stack.append((int(u), 0))
            else:
                finish.append(v)
    # pass 2: reverse DFS in decreasing finish order
    comp = np.full(n, -1, np.int32)
    c = 0
    for v in reversed(finish):
        if comp[v] >= 0:
            continue
        stack = [v]
        comp[v] = c
        while stack:
            u = stack.pop()
            for w in bd[bs[u] : bs[u + 1]]:
                if comp[w] < 0:
                    comp[w] = c
                    stack.append(int(w))
        c += 1
    s2 = comp[src]
    d2 = comp[dst]
    keep = s2 != d2
    s2, d2 = s2[keep], d2[keep]
    key = s2.astype(np.int64) * c + d2
    _, kidx = np.unique(key, return_index=True)
    dag = Graph.from_edges(s2[kidx], d2[kidx], c)
    return comp, dag


def scc_condense_device(graph: Graph, max_outer: int = 64):
    """Min-label FW-BW coloring on device (paper-faithful Pregel variant).

    Each outer round: within the unassigned subgraph, propagate the min
    vertex id forward and backward to fixpoint; vertices where the two
    labels agree form SCCs keyed by that label.
    """
    n = graph.n
    rev = graph.reverse()
    ids = jnp.arange(n, dtype=jnp.int32)
    assigned = jnp.zeros((n,), bool).at[graph.n_real :].set(True)
    scc = jnp.full((n,), -1, jnp.int32)

    @jax.jit
    def fixpoint_min(x, live):
        def body(carry):
            x, changed, _ = carry
            got = ops.propagate(graph, MIN_RIGHT, jnp.where(live, x, INF))
            nx = jnp.where(live & (got < x), got, x)
            return nx, (nx != x).any(), 0

        def fwd_cond(c):
            return c[1]

        x, _, _ = jax.lax.while_loop(fwd_cond, body, (x, jnp.asarray(True), 0))
        return x

    @jax.jit
    def fixpoint_min_rev(x, live):
        def body(carry):
            x, changed, _ = carry
            got = ops.propagate(rev, MIN_RIGHT, jnp.where(live, x, INF))
            nx = jnp.where(live & (got < x), got, x)
            return nx, (nx != x).any(), 0

        x, _, _ = jax.lax.while_loop(lambda c: c[1], body, (x, jnp.asarray(True), 0))
        return x

    for _ in range(max_outer):
        live = ~assigned
        if not bool(live.any()):
            break
        init = jnp.where(live, ids, INF)
        f = fixpoint_min(init, live)
        b = fixpoint_min_rev(init, live)
        hit = live & (f == b)
        scc = jnp.where(hit, f, scc)
        assigned = assigned | hit
    # condense to DAG (host)
    scc_np = np.asarray(scc)[: graph.n_real]
    uniq, inv = np.unique(scc_np, return_inverse=True)
    src = inv[np.asarray(graph.src)]
    dst = inv[np.asarray(graph.dst)]
    keep = src != dst
    src, dst = src[keep], dst[keep]
    key = src.astype(np.int64) * len(uniq) + dst
    _, kidx = np.unique(key, return_index=True)
    dag = Graph.from_edges(src[kidx], dst[kidx], len(uniq))
    return inv.astype(np.int32), dag


# ------------------------------------------------------------- DFS orders
def dfs_orders(dag: Graph) -> tuple[np.ndarray, np.ndarray]:
    """Iterative DFS forest pre/post orders (host; paper cites [42])."""
    n = dag.n_real
    src = np.asarray(dag.src)
    dst = np.asarray(dag.dst)
    order = np.argsort(src, kind="stable")
    src_s, dst_s = src[order], dst[order]
    starts = np.searchsorted(src_s, np.arange(n + 1))
    pre = np.full(n, -1, np.int32)
    post = np.full(n, -1, np.int32)
    cpre = cpost = 0
    for root in range(n):
        if pre[root] >= 0:
            continue
        stack = [(root, iter(dst_s[starts[root] : starts[root + 1]]))]
        pre[root] = cpre
        cpre += 1
        while stack:
            v, it = stack[-1]
            advanced = False
            for u in it:
                if pre[u] < 0:
                    pre[u] = cpre
                    cpre += 1
                    stack.append((int(u), iter(dst_s[starts[u] : starts[u + 1]])))
                    advanced = True
                    break
            if not advanced:
                post[v] = cpost
                cpost += 1
                stack.pop()
    return pre, post


# ------------------------------------------------------------ label jobs
def _fixpoint(graph: Graph, sr, x):
    @jax.jit
    def run(x):
        def body(c):
            x, _ = c
            got = ops.propagate(graph, sr, x)
            nx = sr.add(x, got)
            return nx, (nx != x).any()

        x, _ = jax.lax.while_loop(lambda c: c[1], body, (x, jnp.asarray(True)))
        return x

    return run(x)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ReachIndex:
    level: jnp.ndarray  # (V,)
    pre: jnp.ndarray  # (V,)
    yes_hi: jnp.ndarray  # (V,) max pre over Out(v)
    post: jnp.ndarray  # (V,)
    no_lo: jnp.ndarray  # (V,) min post over Out(v)


def build_reach_index(dag: Graph) -> ReachIndex:
    n = dag.n
    pre_np, post_np = dfs_orders(dag)
    pre = jnp.asarray(np.pad(pre_np, (0, n - len(pre_np)), constant_values=0))
    post = jnp.asarray(np.pad(post_np, (0, n - len(post_np)), constant_values=0))
    rev = dag.reverse()
    # level: longest-hops-from-root, max-plus fixpoint over forward edges
    roots = dag.in_deg == 0
    lvl0 = jnp.where(roots, 0, 0).astype(jnp.int32)
    level = _fixpoint(dag, MAX_PLUS, lvl0)
    # yes-label hi: max pre over reachable set — max-right on reverse edges
    yes_hi = _fixpoint(rev, MAX_RIGHT, pre.astype(jnp.int32))
    # no-label lo: min post over reachable set
    no_lo = _fixpoint(rev, MIN_RIGHT, post.astype(jnp.int32))
    return ReachIndex(level=level, pre=pre, yes_hi=yes_hi, post=post, no_lo=no_lo)


# ---------------------------------------------------------------- queries
class ReachQuery(VertexProgram):
    """(s, t) on the DAG; result reach ∈ {0, 1}."""

    def init(self, graph: Graph, query, index: ReachIndex = None):
        s, t = query[0], query[1]
        n = graph.n
        ds = jnp.full((n,), INF, jnp.int32).at[s].set(0)
        dt = jnp.full((n,), INF, jnp.int32).at[t].set(0)
        # immediate hits from labels: yes(t) ⊆ yes(s) => s reaches t
        yes_sub = (index.pre[s] <= index.pre[t]) & (index.yes_hi[t] <= index.yes_hi[s])
        hit = (s == t) | yes_sub
        return dict(
            ds=ds,
            dt=dt,
            ff=jnp.zeros((n,), bool).at[s].set(True),
            fb=jnp.zeros((n,), bool).at[t].set(True),
            reach=hit,
        )

    def superstep(self, state, ctx: StepCtx):
        idx: ReachIndex = ctx.index
        s, t = ctx.query[0], ctx.query[1]
        ds, dt = state["ds"], state["dt"]
        got_f = ctx.propagate(MIN_RIGHT, ds, state["ff"])
        got_b = ctx.propagate(MIN_RIGHT, dt, state["fb"], which="rev")
        new_f = (got_f < INF) & (ds >= INF)
        new_b = (got_b < INF) & (dt >= INF)
        ds = jnp.where(new_f, ctx.step, ds)
        dt = jnp.where(new_b, ctx.step, dt)
        # yes-label shortcut: any forward-reached v with yes(t) ⊆ yes(v)
        yes_f = new_f & (idx.pre <= idx.pre[t]) & (idx.yes_hi >= idx.yes_hi[t])
        yes_b = new_b & (idx.pre[s] <= idx.pre) & (idx.yes_hi[s] >= idx.yes_hi)
        bi = ((ds < INF) & (dt < INF)).any()
        reach = state["reach"] | yes_f.any() | yes_b.any() | bi
        # pruning (vote to halt): level + no-label containment
        keep_f = (idx.level < idx.level[t]) & (idx.no_lo <= idx.no_lo[t]) & (
            idx.post >= idx.post[t]
        )
        keep_b = (idx.level > idx.level[s]) & (idx.no_lo[s] <= idx.no_lo) & (
            idx.post[s] >= idx.post
        )
        ff = new_f & keep_f
        fb = new_b & keep_b
        done = reach | (~ff.any() & ~fb.any())
        return dict(ds=ds, dt=dt, ff=ff, fb=fb, reach=reach), done

    def frontier_of(self, state):
        return {"default": state["ff"], "rev": state["fb"]}

    def extract(self, state, query):
        visited = ((state["ds"] < INF) | (state["dt"] < INF)).sum()
        return dict(reach=state["reach"], visited=visited)


def make_reach_engine(dag: Graph, index: ReachIndex, capacity: int = 8, **kw):
    return QuegelEngine(
        dag,
        ReachQuery(),
        capacity,
        index=index,
        aux_graphs={"rev": dag.reverse()},
        example_query=jnp.zeros((2,), jnp.int32),
        **kw,
    )
