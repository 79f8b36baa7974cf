"""Graph (RDF) keyword search — paper §5.5.

Query Q = {k_1..k_m} over a vertex-labeled graph; answers are rooted trees
(r, {<v_i, hop(r, v_i)>}) where v_i is the closest vertex to r matching
k_i, with hop <= delta_max.

Per-keyword hop distances flow along *reverse* edges (v learns about
matches reachable through its out-edges).  To return the witness vertex
ids, not just hops, each lane carries the encoding ``hop * N + vid`` whose
min is (min hop, then min id) — a pure min-plus semiring with edge weight N
on the reversed graph (the message `<v_i, hop+1>` of the paper).

RDF adaptation (paper Fig. 8): literals and predicates are modeled as
ordinary vertices carrying their text, so the four RDF message cases
collapse to the vertex-text case; see DESIGN.md §8.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.engine import QuegelEngine, StepCtx, VertexProgram
from repro.core.graph import Graph
from repro.core.semiring import INF, MIN_PLUS

MAXK = 4  # max keywords per query (paper evaluates 2 and 3)


def make_vertex_text(n: int, vocab: int, tokens_per_vertex: int, seed: int = 0,
                     zipf: float = 1.3) -> np.ndarray:
    """Synthetic vertex text: (V, T) int32 token ids, Zipf-distributed
    (frequent words exist, like the paper's K_30 selection)."""
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    p = ranks**-zipf
    p /= p.sum()
    return rng.choice(vocab, size=(n, tokens_per_vertex), p=p).astype(np.int32)


class InvertedIndex:
    """The paper's per-worker inverted index (load2Idx): token -> matching
    vertices.  Device-side we keep the raw token table and resolve matches
    with a vectorized compare (the dense-TPU analogue of a posting list)."""

    def __init__(self, tokens: np.ndarray):
        self.tokens = jnp.asarray(tokens)  # (V, T)

    def match(self, keyword) -> jnp.ndarray:
        """(V,) bool — init_activate's vertex set for one keyword."""
        return (self.tokens == keyword).any(axis=1)


class GraphKeywordSearch(VertexProgram):
    """state: enc (MAXK, V) int32 = hop * N + witness_id (INF when unknown).

    A lane for an unused keyword slot (query padded with -1) stays fully
    INF and is ignored by the root predicate.
    """

    def __init__(self, rev_graph_n: int, delta_max: int = 3):
        self.delta_max = delta_max
        self.n_enc = rev_graph_n

    def init(self, graph: Graph, query, index: InvertedIndex = None):
        n = graph.n
        vids = jnp.arange(n, dtype=jnp.int32)
        def lane(k):
            m = index.match(k) & (k >= 0)
            return jnp.where(m, vids, INF)  # hop 0, witness = self
        enc = jax.vmap(lane)(query)  # (MAXK, V)
        return dict(enc=enc, frontier=enc < INF)

    def superstep(self, state, ctx: StepCtx):
        enc = state["enc"]
        # reverse-edge propagation with weight N: hop+1, witness preserved
        got = ctx.propagate(MIN_PLUS, enc, state["frontier"], which="rev")
        improved = got < enc
        enc = jnp.where(improved, got, enc)
        done = (ctx.step >= self.delta_max) | ~improved.any()
        return dict(enc=enc, frontier=improved), done

    def frontier_of(self, state):
        return {"rev": state["frontier"]}

    def extract(self, state, query):
        enc = state["enc"]  # (MAXK, V)
        used = (query >= 0)[:, None]
        known = (enc < INF) | ~used
        is_root = known.all(axis=0) & (enc < INF).any(axis=0)
        hops = jnp.where(used, enc // self.n_enc, 0)
        total = jnp.where(is_root, hops.sum(axis=0), INF)
        order = jnp.argsort(total)[:16]
        return dict(
            num_roots=is_root.sum(),
            top_roots=order.astype(jnp.int32),
            top_scores=total[order],
            touched=(enc < INF).any(axis=0).sum(),
        )


import jax  # noqa: E402  (used in init's vmap)


def make_keyword_engine(
    graph: Graph, tokens: np.ndarray, capacity: int = 8, delta_max: int = 3, **kw
):
    """Reverse graph carries weight N so min-plus transports hop*N+vid."""
    rev = graph.reverse()
    rev_w = Graph(
        n=rev.n,
        n_real=rev.n_real,
        src=rev.src,
        dst=rev.dst,
        w=jnp.full_like(rev.w, rev.n),
        in_deg=rev.in_deg,
        out_deg=rev.out_deg,
    )
    idx = InvertedIndex(tokens)
    # propagation only ever flows along the weighted reverse view (min-plus)
    return QuegelEngine(
        graph,
        GraphKeywordSearch(rev.n, delta_max),
        capacity,
        index=idx,
        aux_graphs={"rev": rev_w},
        example_query=jnp.full((MAXK,), -1, jnp.int32),
        **kw,
    )
