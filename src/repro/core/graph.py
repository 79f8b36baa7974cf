"""Graph containers and generators.

Two adjacency views coexist:

* **COO-sorted-by-destination** — drives the pure-jnp reference propagation
  (``jax.ops.segment_min`` & friends).  Exact, used for correctness oracles
  and small graphs.
* **Block-sparse dense tiles** — the TPU-native format consumed by the
  Pallas frontier kernel.  Vertices are padded to a multiple of ``block``
  and the adjacency is stored as a list of dense ``(block, block)`` weight
  tiles per destination block.  A Pregel superstep then becomes a
  block-sparse *semiring matmul*: regular, MXU/VPU-shaped, no scatter.

This is the central hardware adaptation (DESIGN.md §2): Quegel's per-vertex
message queues become dense tile algebra.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.semiring import INF, Semiring


def _pad_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class BlockSparse:
    """Block-sparse adjacency for one propagation direction.

    ``src_ids[i, k]`` is the source vertex-block feeding destination block
    ``i`` in slot ``k`` (padded slots point at an identity tile).
    ``tiles[i, k]`` is the dense ``(B, B)`` edge-weight tile; absent edges
    hold the semiring's add-identity so they contribute nothing.
    """

    src_ids: jnp.ndarray  # (nb, max_bpr) int32
    tiles: jnp.ndarray  # (nb, max_bpr, B, B) weight dtype
    block: int = dataclasses.field(metadata=dict(static=True))
    # (nb,) int32 — number of REAL source blocks per destination-block row;
    # slots >= nslots[i] are padding (identity tiles) and may be skipped by
    # the gated kernels.  Always present: every constructor fills it.
    nslots: jnp.ndarray

    @property
    def num_dst_blocks(self) -> int:
        return self.src_ids.shape[0]

    @property
    def max_bpr(self) -> int:
        return self.src_ids.shape[1]


@dataclasses.dataclass(frozen=True)
class EdgeDelta:
    """A validated, batched edge mutation (host-side numpy, never traced).

    Semantics: deletions apply first, then insertions; an inserted
    ``(src, dst)`` that already exists *replaces* its weight (upsert).
    Built via :meth:`Graph.make_delta`, which validates endpoints against
    ``n_real`` and checks every deletion names an existing edge.
    """

    add_src: np.ndarray  # (a,) int32
    add_dst: np.ndarray  # (a,) int32
    add_w: np.ndarray  # (a,) weight dtype
    del_src: np.ndarray  # (d,) int32
    del_dst: np.ndarray  # (d,) int32

    @property
    def size(self) -> int:
        return int(len(self.add_src) + len(self.del_src))

    @property
    def is_empty(self) -> bool:
        return self.size == 0

    def reversed(self) -> "EdgeDelta":
        """The same mutation on the edge-reversed graph (aux 'rev' views)."""
        return EdgeDelta(self.add_dst, self.add_src, self.add_w,
                         self.del_dst, self.del_src)

    def touched_dst_blocks(self, block: int) -> np.ndarray:
        """Destination-block rows whose tiles can change under this delta."""
        if self.is_empty:
            return np.zeros(0, dtype=np.int64)
        d = np.concatenate([self.add_dst, self.del_dst])
        return np.unique(d.astype(np.int64) // block)


def _as_pairs(pairs, what: str):
    """Normalize (k,2) array / (src, dst) tuple / None to two int32 arrays."""
    if pairs is None:
        z = np.zeros(0, dtype=np.int32)
        return z, z.copy()
    if isinstance(pairs, tuple) and len(pairs) == 2:
        s = np.atleast_1d(np.asarray(pairs[0], dtype=np.int32))
        d = np.atleast_1d(np.asarray(pairs[1], dtype=np.int32))
        if s.shape != d.shape:
            raise ValueError(f"{what}: src/dst length mismatch {s.shape} vs {d.shape}")
        return s, d
    a = np.asarray(pairs, dtype=np.int32)
    if a.ndim == 1 and a.shape[0] == 2:
        a = a[None, :]
    if a.ndim != 2 or a.shape[1] != 2:
        raise ValueError(f"{what}: expected (k, 2) pairs or (src, dst) arrays")
    return a[:, 0].copy(), a[:, 1].copy()


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Graph:
    """An immutable directed graph, padded to ``n`` vertices.

    Propagation always flows src -> dst along ``edges``; for backward
    traversal use :meth:`reverse`.  Vertices in ``[n_real, n)`` are padding
    and never carry edges.
    """

    n: int = dataclasses.field(metadata=dict(static=True))
    n_real: int = dataclasses.field(metadata=dict(static=True))
    src: jnp.ndarray  # (E,) int32, sorted by dst
    dst: jnp.ndarray  # (E,) int32, sorted
    w: jnp.ndarray  # (E,) int32 or float32 edge weights
    in_deg: jnp.ndarray  # (n,) int32
    out_deg: jnp.ndarray  # (n,) int32
    # CSR (sorted-by-source) view of the same edges, driving the
    # frontier-gated COO path: ``csr_row[v]:csr_row[v+1]`` indexes the
    # out-edges of v in csr_src/csr_dst/csr_w.  None on graphs built by
    # hand before gating existed (gated propagation then refuses).
    csr_row: Optional[jnp.ndarray] = None  # (n+1,) int32
    csr_src: Optional[jnp.ndarray] = None  # (E,) int32, sorted
    csr_dst: Optional[jnp.ndarray] = None  # (E,) int32
    csr_w: Optional[jnp.ndarray] = None  # (E,)
    # Mutation lineage (DESIGN.md §12): ``apply_delta`` bumps ``version`` and
    # records the parent's content hash, forming a per-version hash chain the
    # journal replays against.  Both are static (JSON-able) so they survive
    # the durable store's manifest round-trip.
    version: int = dataclasses.field(default=0, metadata=dict(static=True))
    parent_hash: Optional[str] = dataclasses.field(
        default=None, metadata=dict(static=True)
    )
    # Edge-capacity padding (DESIGN.md §12 addendum): when ``nnz`` is set the
    # COO/CSR arrays are padded out to a fixed capacity with inert rows
    # (src = dst = n, w = 0 — the segment scatter drops index n, so padding
    # contributes nothing on any semiring) and ``nnz`` holds the logical edge
    # count.  An in-capacity ``apply_delta`` then changes array *values*
    # only, never shapes, which is what lets the argument-carried round
    # reuse one compiled executable across graph versions.
    nnz: Optional[jnp.ndarray] = None  # () int32 — logical edge count

    @property
    def num_edges(self) -> int:
        if self.nnz is not None:
            return int(self.nnz)
        return int(self.src.shape[0])

    @property
    def edge_capacity(self) -> int:
        """Physical edge-array length (== num_edges unless capacity-padded)."""
        return int(self.src.shape[0])

    def _edges_np(self):
        """The logical COO edges as numpy (capacity padding trimmed off)."""
        ne = self.num_edges
        return (np.asarray(self.src)[:ne], np.asarray(self.dst)[:ne],
                np.asarray(self.w)[:ne])

    def content_hash(self) -> str:
        """Stable sha256 over the logical graph (sizes + COO edges +
        weights).  The durable store (core/store.py) binds indexes and tile
        tables to the graph they were built against via this hash, so a
        restored index can never be served over a different graph.

        Memoized: the arrays are immutable, so the digest is computed once
        per Graph object.  The invalidation point is explicit — mutation
        never edits arrays in place, :meth:`apply_delta` returns a *new*
        Graph (with a fresh, empty memo)."""
        memo = getattr(self, "_chash", None)
        if memo is not None:
            return memo
        import hashlib

        ne = self.num_edges  # hash the logical prefix: capacity padding is
        h = hashlib.sha256(f"{self.n}/{self.n_real}".encode())  # not content
        for arr in (self.src, self.dst, self.w):
            a = np.asarray(arr)[:ne]
            h.update(str(a.dtype).encode())
            h.update(a.tobytes())
        digest = h.hexdigest()
        object.__setattr__(self, "_chash", digest)
        return digest

    # ----------------------------------------------------- capacity padding
    def with_capacity(self, max_e: Optional[int] = None, *,
                      max_v: Optional[int] = None) -> "Graph":
        """Pad the edge arrays to a fixed capacity (and optionally repad the
        vertex axis to ``max_v``), returning a shape-stable Graph.

        Padding rows are inert on every propagation path: COO padding holds
        ``src = dst = n, w = 0`` (appended at the tail, preserving the
        dst-sorted invariant; the segment scatter drops destination index
        ``n``), CSR padding holds ``csr_src = csr_dst = n, csr_w = 0``
        (preserving the (src, dst)-lex sort; the gated gather's clamped read
        may mark a padding edge active but its message lands in the dummy
        segment ``n`` and is sliced off).  ``content_hash`` and lineage are
        unchanged — capacity is a *representation* choice, not content.

        ``max_v`` rebuilds the graph with vertex padding (a different padded
        graph, like :meth:`padded` — use before building indexes/tables).
        Capacity overflow on :meth:`apply_delta` grows the arrays (new
        shapes → the arg-carried round recompiles, by design).
        """
        g = self
        if max_v is not None:
            if max_v < g.n_real:
                raise ValueError(f"max_v {max_v} < n_real {g.n_real}")
            if max_v > g.n:
                s, d, w = g._edges_np()
                g2 = Graph.from_edges(s, d, g.n_real, w=w, pad_to=max_v,
                                      weight_dtype=w.dtype)
                g = dataclasses.replace(
                    g2, version=g.version, parent_hash=g.parent_hash
                )
        ne = g.num_edges
        cap = max(int(max_e) if max_e is not None else 0, ne)
        if g.nnz is not None and g.edge_capacity == cap:
            return g
        base = g.trimmed()
        if base.csr_row is None:
            raise ValueError(
                "with_capacity needs the CSR view; build via Graph.from_edges"
            )
        n, pad = base.n, cap - ne

        def padc(a, fill):
            a = np.asarray(a)
            return jnp.asarray(
                np.concatenate([a, np.full(pad, fill, dtype=a.dtype)])
            )

        out = dataclasses.replace(
            base,
            src=padc(base.src, n), dst=padc(base.dst, n), w=padc(base.w, 0),
            csr_src=padc(base.csr_src, n), csr_dst=padc(base.csr_dst, n),
            csr_w=padc(base.csr_w, 0),
            nnz=jnp.asarray(ne, dtype=jnp.int32),
        )
        memo = getattr(base, "_chash", None)
        if memo is not None:
            object.__setattr__(out, "_chash", memo)
        return out

    def trimmed(self) -> "Graph":
        """The exact (capacity-free) graph: the logical prefix of every edge
        array.  Identity when not capacity-padded."""
        if self.nnz is None:
            return self
        ne = int(self.nnz)
        sl = lambda a: None if a is None else a[:ne]
        out = dataclasses.replace(
            self, src=self.src[:ne], dst=self.dst[:ne], w=self.w[:ne],
            csr_src=sl(self.csr_src), csr_dst=sl(self.csr_dst),
            csr_w=sl(self.csr_w), nnz=None,
        )
        memo = getattr(self, "_chash", None)
        if memo is not None:
            object.__setattr__(out, "_chash", memo)
        return out

    def carrier(self) -> "Graph":
        """A lineage-stripped copy for use as a *traced jit argument*.

        ``version``/``parent_hash`` are static fields — part of the jit
        cache key — so the argument-carried round pins them to ``(0, None)``;
        host-side bookkeeping keeps the exact graph with real lineage.
        """
        if self.version == 0 and self.parent_hash is None:
            return self
        return dataclasses.replace(self, version=0, parent_hash=None)

    # ---------------------------------------------------------------- build
    @staticmethod
    def from_edges(
        src,
        dst,
        n: int,
        w=None,
        pad_to: int = 1,
        weight_dtype=np.int32,
    ) -> "Graph":
        src = np.asarray(src, dtype=np.int32)
        dst = np.asarray(dst, dtype=np.int32)
        if w is None:
            w = np.ones_like(src, dtype=weight_dtype)
        else:
            w = np.asarray(w, dtype=weight_dtype)
        order = np.argsort(dst, kind="stable")
        src, dst, w = src[order], dst[order], w[order]
        n_pad = _pad_to(max(n, 1), pad_to)
        in_deg = np.bincount(dst, minlength=n_pad).astype(np.int32)
        out_deg = np.bincount(src, minlength=n_pad).astype(np.int32)
        csr = np.argsort(src, kind="stable")
        csr_src = src[csr]
        csr_row = np.searchsorted(csr_src, np.arange(n_pad + 1)).astype(np.int32)
        return Graph(
            n=n_pad,
            n_real=n,
            src=jnp.asarray(src),
            dst=jnp.asarray(dst),
            w=jnp.asarray(w),
            in_deg=jnp.asarray(in_deg),
            out_deg=jnp.asarray(out_deg),
            csr_row=jnp.asarray(csr_row),
            csr_src=jnp.asarray(csr_src),
            csr_dst=jnp.asarray(dst[csr]),
            csr_w=jnp.asarray(w[csr]),
        )

    def padded(self, multiple: int) -> "Graph":
        """Repad so ``n`` is a multiple of ``multiple`` (a mesh shard axis).

        Padding vertices carry no edges; returns self when already aligned.
        Note this rebuilds the COO/CSR views — repad BEFORE building any
        index or block-sparse tables against the graph.
        """
        if self.n % multiple == 0:
            return self
        s, d, w = self._edges_np()
        return Graph.from_edges(
            s,
            d,
            self.n_real,
            w=w,
            pad_to=_pad_to(self.n, multiple),
            weight_dtype=w.dtype,
        )

    def reverse(self) -> "Graph":
        s, d, w = self._edges_np()
        return Graph.from_edges(
            d,
            s,
            self.n_real,
            w=w,
            pad_to=self.n,
            weight_dtype=w.dtype,
        )

    def undirected(self) -> "Graph":
        s, d, w = self._edges_np()
        return Graph.from_edges(
            np.concatenate([s, d]),
            np.concatenate([d, s]),
            self.n_real,
            w=np.concatenate([w, w]),
            pad_to=self.n // max(self.n_real, 1) and self.n or 1,
            weight_dtype=w.dtype,
        )

    # ------------------------------------------------------- block-sparse
    def to_blocks(self, block: int, add_id, dtype=None) -> BlockSparse:
        """Materialize the block-sparse dense-tile adjacency.

        ``add_id`` fills absent-edge entries (INF for min semirings, 0 for
        OR/sum).  Multi-edges keep the *best* weight under min semantics
        (callers with sum semantics must pre-combine duplicates).
        """
        src, dst, w = self._edges_np()
        dtype = dtype or w.dtype
        nb = _pad_to(self.n, block) // block
        sb = src.astype(np.int64) // block
        db = dst.astype(np.int64) // block
        # (dst block, src block) pairs in sorted order: each dst-block row
        # lists its source blocks ascending, one slot per source block
        uniq, pair_of_edge = np.unique(db * nb + sb, return_inverse=True)
        row = uniq // nb
        nslots = np.bincount(row, minlength=nb)
        max_bpr = max(1, int(nslots.max(initial=0)))
        slot = np.arange(len(uniq)) - (np.cumsum(nslots) - nslots)[row]
        # padded slots point at block 0 with identity tiles
        src_ids = np.zeros((nb, max_bpr), dtype=np.int32)
        src_ids[row, slot] = uniq % nb
        tiles = np.full((nb, max_bpr, block, block), add_id, dtype=dtype)
        cell = ((db * max_bpr + slot[pair_of_edge]) * block
                + src % block) * block + dst % block
        flat, wt = tiles.reshape(-1), w.astype(dtype)
        if np.issubdtype(tiles.dtype, np.unsignedinteger):
            np.bitwise_or.at(flat, cell, wt)
        elif add_id == 0:
            np.add.at(flat, cell, wt)
        elif add_id > 0:  # min semirings: keep best (smallest) weight
            np.minimum.at(flat, cell, wt)
        else:  # max semirings: presence must exceed the -INF fill
            np.maximum.at(flat, cell, wt)
        return BlockSparse(
            src_ids=jnp.asarray(src_ids),
            tiles=jnp.asarray(tiles),
            block=block,
            nslots=jnp.asarray(nslots, dtype=jnp.int32),
        )

    # ---------------------------------------------------------- mutation
    def make_delta(self, adds=None, dels=None, *, w=None) -> EdgeDelta:
        """Validate and normalize a batched edge mutation against this graph.

        ``adds``/``dels`` are ``(k, 2)`` ``(src, dst)`` pair arrays (or
        ``(src_array, dst_array)`` tuples); ``w`` gives per-added-edge
        weights (default 1, cast to the graph's weight dtype).  Raises
        ``ValueError`` — leaving the graph untouched — when an endpoint
        falls outside the real vertex range ``[0, n_real)`` (padding
        vertices never carry edges) or a deletion names an absent edge.
        Within one batch the last add of a given pair wins; a pair both
        deleted and added nets out to the add (upsert).
        """
        a_s, a_d = _as_pairs(adds, "adds")
        d_s, d_d = _as_pairs(dels, "dels")
        wdtype = np.asarray(self.w).dtype
        if w is None:
            a_w = np.ones(len(a_s), dtype=wdtype)
        else:
            a_w = np.broadcast_to(np.asarray(w, dtype=wdtype), (len(a_s),)).copy()
        for name, arr in (("adds", a_s), ("adds", a_d), ("dels", d_s), ("dels", d_d)):
            if len(arr) and (int(arr.min()) < 0 or int(arr.max()) >= self.n_real):
                raise ValueError(
                    f"{name}: endpoint outside the real vertex range "
                    f"[0, {self.n_real}) — padded vertices [{self.n_real}, "
                    f"{self.n}) must stay edge-free"
                )
        n = np.int64(self.n)
        if len(a_s):
            key = a_d.astype(np.int64) * n + a_s
            # keep the LAST occurrence of each added pair
            _, ridx = np.unique(key[::-1], return_index=True)
            idx = np.sort(len(key) - 1 - ridx)
            a_s, a_d, a_w = a_s[idx], a_d[idx], a_w[idx]
        if len(d_s):
            key = d_d.astype(np.int64) * n + d_s
            _, idx = np.unique(key, return_index=True)
            idx = np.sort(idx)
            d_s, d_d = d_s[idx], d_d[idx]
            g_s, g_d, _ = self._edges_np()
            base = g_d.astype(np.int64) * n + g_s
            missing = ~np.isin(d_d.astype(np.int64) * n + d_s, base)
            if missing.any():
                bad = [(int(s), int(d)) for s, d in
                       zip(d_s[missing][:5], d_d[missing][:5])]
                raise ValueError(f"dels: edges not present in graph: {bad}")
        return EdgeDelta(a_s, a_d, a_w, d_s, d_d)

    def apply_delta(self, adds=None, dels=None, *, w=None) -> "Graph":
        """Return a new Graph with the delta applied and ``version`` bumped.

        Both adjacency views are merged *incrementally*: matching rows are
        masked out and new rows spliced into the existing dst-sorted COO and
        src-sorted CSR arrays (``np.isin`` + ``searchsorted`` + ``insert``),
        degrees patched by delta ``bincount`` — no O(E log E) re-sort, no
        full rebuild.  ``csr_row`` is recomputed by binary search (cheap).
        An empty delta is a version-bumping no-op sharing every array.
        Duplicate (src, dst) rows in a multigraph are all replaced by one
        row on upsert.
        """
        delta = adds if isinstance(adds, EdgeDelta) else self.make_delta(adds, dels, w=w)
        parent = self.content_hash()
        if delta.is_empty:
            g = dataclasses.replace(
                self, version=self.version + 1, parent_hash=parent
            )
            object.__setattr__(g, "_chash", parent)  # content unchanged
            return g
        if self.nnz is not None:
            # Capacity-padded: splice the logical prefix, then re-pad.  The
            # same capacity is kept while the result fits (values-only
            # change — the arg-carried round's compiled executable is
            # reused); overflow grows with headroom, changing shapes and
            # forcing the one recompile that genuinely cannot be avoided.
            cap = self.edge_capacity
            out = self.trimmed().apply_delta(delta)
            if out.num_edges > cap:
                cap = grow_capacity(out.num_edges)
            return out.with_capacity(max_e=cap)
        if self.csr_row is None:
            raise ValueError(
                "apply_delta needs the CSR view; build the graph via "
                "Graph.from_edges"
            )
        n = np.int64(self.n)
        src, dst, w_ = np.asarray(self.src), np.asarray(self.dst), np.asarray(self.w)
        a_s, a_d, a_w = delta.add_src, delta.add_dst, delta.add_w
        # rows to drop: explicit deletions plus upserted (re-added) pairs
        rm_s = np.concatenate([delta.del_src, a_s])
        rm_d = np.concatenate([delta.del_dst, a_d])
        keep = ~np.isin(dst.astype(np.int64) * n + src, rm_d.astype(np.int64) * n + rm_s)
        rsrc, rdst = src[~keep], dst[~keep]  # removed rows → degree patch
        ksrc, kdst, kw = src[keep], dst[keep], w_[keep]
        order = np.argsort(a_d, kind="stable")
        i_s, i_d, i_w = a_s[order], a_d[order], a_w[order]
        pos = np.searchsorted(kdst, i_d, side="right")
        new_src = np.insert(ksrc, pos, i_s)
        new_dst = np.insert(kdst, pos, i_d)
        new_w = np.insert(kw, pos, i_w)
        in_deg = (np.asarray(self.in_deg)
                  - np.bincount(rdst, minlength=self.n)
                  + np.bincount(a_d, minlength=self.n)).astype(np.int32)
        out_deg = (np.asarray(self.out_deg)
                   - np.bincount(rsrc, minlength=self.n)
                   + np.bincount(a_s, minlength=self.n)).astype(np.int32)
        csrc = np.asarray(self.csr_src)
        cdst = np.asarray(self.csr_dst)
        cw = np.asarray(self.csr_w)
        ckeep = ~np.isin(csrc.astype(np.int64) * n + cdst,
                         rm_s.astype(np.int64) * n + rm_d)
        kcsrc, kcdst, kcw = csrc[ckeep], cdst[ckeep], cw[ckeep]
        # the CSR view is (src, dst)-lex sorted (stable argsort of the
        # dst-sorted COO), so splice by the composite key
        akey = a_s.astype(np.int64) * n + a_d
        corder = np.argsort(akey, kind="stable")
        j_s, j_d, j_w = a_s[corder], a_d[corder], a_w[corder]
        cpos = np.searchsorted(kcsrc.astype(np.int64) * n + kcdst,
                               akey[corder], side="right")
        new_csrc = np.insert(kcsrc, cpos, j_s)
        new_cdst = np.insert(kcdst, cpos, j_d)
        new_cw = np.insert(kcw, cpos, j_w)
        csr_row = np.searchsorted(new_csrc, np.arange(self.n + 1)).astype(np.int32)
        return Graph(
            n=self.n,
            n_real=self.n_real,
            src=jnp.asarray(new_src),
            dst=jnp.asarray(new_dst),
            w=jnp.asarray(new_w),
            in_deg=jnp.asarray(in_deg),
            out_deg=jnp.asarray(out_deg),
            csr_row=jnp.asarray(csr_row),
            csr_src=jnp.asarray(new_csrc),
            csr_dst=jnp.asarray(new_cdst),
            csr_w=jnp.asarray(new_cw),
            version=self.version + 1,
            parent_hash=parent,
        )

    def update_blocks(
        self, bs: BlockSparse, add_id, touched=None, dtype=None
    ) -> BlockSparse:
        """Incrementally refresh a block-sparse table after :meth:`apply_delta`.

        Only the dst-block rows in ``touched`` (from
        ``EdgeDelta.touched_dst_blocks``) are rebuilt from this graph's COO
        view — the whole point of keeping the COO dst-sorted: each row is an
        O(log E) ``searchsorted`` slice.  The slot axis grows (never
        shrinks) when a touched row gains source blocks; untouched rows are
        byte-preserved.  ``bs`` must come from an ancestor of this graph
        whose edges differ only inside ``touched`` rows.
        """
        block = bs.block
        nb = _pad_to(self.n, block) // block
        if nb != bs.num_dst_blocks:
            raise ValueError("update_blocks: vertex count changed; use to_blocks")
        if touched is None:
            touched = np.arange(nb, dtype=np.int64)
        touched = np.unique(np.asarray(touched, dtype=np.int64))
        touched = touched[(touched >= 0) & (touched < nb)]
        if len(touched) == 0:
            return bs
        src, dst, w = np.asarray(self.src), np.asarray(self.dst), np.asarray(self.w)
        src_ids = np.array(bs.src_ids)
        tiles = np.array(bs.tiles)
        nslots = np.array(bs.nslots)
        rows = {}
        for i in touched:
            lo = int(np.searchsorted(dst, i * block, side="left"))
            hi = int(np.searchsorted(dst, (i + 1) * block, side="left"))
            rows[int(i)] = (lo, hi, np.unique(src[lo:hi] // block))
        need = max((len(sb) for _, _, sb in rows.values()), default=1)
        if need > bs.max_bpr:
            pad = need - bs.max_bpr
            src_ids = np.pad(src_ids, ((0, 0), (0, pad)))
            tiles = np.pad(tiles, ((0, 0), (0, pad), (0, 0), (0, 0)),
                           constant_values=add_id)
        unsigned = np.issubdtype(tiles.dtype, np.unsignedinteger)
        for i, (lo, hi, sb) in rows.items():
            src_ids[i] = 0
            src_ids[i, : len(sb)] = sb
            tiles[i] = add_id
            nslots[i] = len(sb)
            slot_of = {int(b): k for k, b in enumerate(sb)}
            for e in range(lo, hi):
                k = slot_of[int(src[e]) // block]
                r, c = int(src[e] % block), int(dst[e] % block)
                if unsigned:
                    tiles[i, k, r, c] |= w[e]
                elif add_id == 0:
                    tiles[i, k, r, c] += w[e]
                elif add_id > 0:
                    tiles[i, k, r, c] = min(tiles[i, k, r, c], w[e])
                else:
                    tiles[i, k, r, c] = max(tiles[i, k, r, c], w[e])
        if dtype is not None and tiles.dtype != dtype:
            tiles = tiles.astype(dtype)
        return BlockSparse(
            src_ids=jnp.asarray(src_ids),
            tiles=jnp.asarray(tiles),
            block=block,
            nslots=jnp.asarray(nslots),
        )


def grow_capacity(ne: int) -> int:
    """Default edge-capacity headroom: ~25% + slack, rounded to 64."""
    return _pad_to(int(ne * 1.25) + 32, 64)


def pad_block_slots(bs: BlockSparse, slot_cap: int, add_id) -> BlockSparse:
    """Pad a BlockSparse table's slot axis out to ``slot_cap`` source-block
    slots per destination row, keeping tile shapes stable across mutations
    for the argument-carried round.

    Padding slots point at source block 0 with add-identity tiles and
    ``nslots`` is unchanged, so gated kernels skip them outright and the
    ungated tile math treats them as no-ops (identity tiles contribute
    ``add_id``, which every semiring's combine ignores).
    """
    if bs.max_bpr > slot_cap:
        raise ValueError(
            f"slot_cap {slot_cap} < table max_bpr {bs.max_bpr}"
        )
    if bs.max_bpr == slot_cap:
        return bs
    pad = slot_cap - bs.max_bpr
    src_ids = np.pad(np.asarray(bs.src_ids), ((0, 0), (0, pad)))
    tiles = np.pad(np.asarray(bs.tiles), ((0, 0), (0, pad), (0, 0), (0, 0)),
                   constant_values=add_id)
    return BlockSparse(
        src_ids=jnp.asarray(src_ids),
        tiles=jnp.asarray(tiles),
        block=bs.block,
        nslots=bs.nslots,
    )


# ------------------------------------------------------------- generators
def barabasi_albert(n: int, m: int, seed: int = 0, directed: bool = False) -> Graph:
    """Preferential-attachment graph: the skewed-degree ('hub') setting the
    paper's Hub^2 index targets."""
    rng = np.random.default_rng(seed)
    targets = list(range(m))
    repeated: list[int] = list(range(m))
    src_l, dst_l = [], []
    for v in range(m, n):
        picks = rng.choice(repeated, size=m, replace=True) if repeated else rng.integers(0, v, m)
        picks = np.unique(picks)
        for t in picks:
            src_l.append(v)
            dst_l.append(int(t))
            repeated.extend([v, int(t)])
    src = np.array(src_l, dtype=np.int32)
    dst = np.array(dst_l, dtype=np.int32)
    if not directed:
        src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
    # dedupe
    # deduplicated in (dst, src) order, the order from_edges keeps
    key = np.unique(dst.astype(np.int64) * n + src)
    return Graph.from_edges(key % n, key // n, n)


def random_graph(n: int, avg_deg: float, seed: int = 0, directed: bool = True) -> Graph:
    rng = np.random.default_rng(seed)
    e = int(n * avg_deg)
    src = rng.integers(0, n, e).astype(np.int32)
    dst = rng.integers(0, n, e).astype(np.int32)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    if not directed:
        src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
    # deduplicated in (dst, src) order, the order from_edges keeps
    key = np.unique(dst.astype(np.int64) * n + src)
    return Graph.from_edges(key % n, key // n, n)


def multi_component_graph(n_components: int, comp_size: int, avg_deg: float, seed: int = 0) -> Graph:
    """Many small CCs — the BTC-like regime where most (s,t) are unreachable."""
    rng = np.random.default_rng(seed)
    src_l, dst_l = [], []
    for c in range(n_components):
        base = c * comp_size
        e = int(comp_size * avg_deg)
        s = rng.integers(0, comp_size, e) + base
        d = rng.integers(0, comp_size, e) + base
        keep = s != d
        src_l.append(s[keep])
        dst_l.append(d[keep])
    src = np.concatenate(src_l).astype(np.int32)
    dst = np.concatenate(dst_l).astype(np.int32)
    n = n_components * comp_size
    src2, dst2 = np.concatenate([src, dst]), np.concatenate([dst, src])
    key = src2.astype(np.int64) * n + dst2
    _, idx = np.unique(key, return_index=True)
    return Graph.from_edges(src2[idx], dst2[idx], n)


def random_dag(n: int, avg_deg: float, seed: int = 0) -> Graph:
    """DAG via random topological order — the reachability-query substrate."""
    rng = np.random.default_rng(seed)
    e = int(n * avg_deg)
    a = rng.integers(0, n, e).astype(np.int32)
    b = rng.integers(0, n, e).astype(np.int32)
    src, dst = np.minimum(a, b), np.maximum(a, b)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    # deduplicated in (dst, src) order, the order from_edges keeps
    key = np.unique(dst.astype(np.int64) * n + src)
    return Graph.from_edges(key % n, key // n, n)


def random_tree(n: int, max_fanout: int = 8, seed: int = 0,
                deep: bool = False) -> tuple[Graph, np.ndarray]:
    """Rooted tree (child->parent edges) modeling an XML document.

    Default is shallow (parent drawn uniformly from earlier vertices →
    O(log n) depth, like real XML); ``deep=True`` uses a locality window
    giving O(n) depth for stress-testing level-aligned algorithms.
    Returns the graph with edges child->parent (upward propagation — the
    direction SLCA/ELCA bitmaps flow) plus the parent array (parent[0] = -1).
    """
    rng = np.random.default_rng(seed)
    parent = np.full(n, -1, dtype=np.int32)
    for v in range(1, n):
        lo = max(0, v - max_fanout * 4) if deep else 0
        parent[v] = rng.integers(lo, v)
    src = np.arange(1, n, dtype=np.int32)
    dst = parent[1:]
    g = Graph.from_edges(src, dst, n)
    return g, parent


def grid_terrain(
    rows: int,
    cols: int,
    eps_subdiv: int = 1,
    seed: int = 0,
) -> tuple[Graph, np.ndarray]:
    """The paper's §5.3 terrain network: an elevation mesh with per-cell
    shortcut edges (diagonals), Euclidean-3D edge weights.

    Returns (graph, coords) where coords is (n, 3) float32 positions.
    ``eps_subdiv`` > 1 splits each cell edge, adding the shortcut vertices of
    Fig. 4(b); eps_subdiv=1 keeps the plain 8-connected mesh with diagonals.
    """
    rng = np.random.default_rng(seed)
    r = rows * eps_subdiv - (eps_subdiv - 1)
    c = cols * eps_subdiv - (eps_subdiv - 1)
    # smooth hills (~real DEM roughness at 10m sampling) + mild noise
    yy0, xx0 = np.meshgrid(np.arange(rows), np.arange(cols), indexing="ij")
    elev = (
        12.0 * np.sin(yy0 / 6.0) * np.cos(xx0 / 7.0)
        + 6.0 * np.sin((yy0 + xx0) / 11.0)
        + rng.random((rows, cols)) * 1.5
    ).astype(np.float32)
    # bilinear-interpolate elevation at subdivided resolution (paper: TIN
    # interpolates too)
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
    spacing = 10.0 / eps_subdiv  # 10m sampling interval, subdivided
    ys, xs = np.meshgrid(np.arange(r), np.arange(c), indexing="ij")
    coords = np.stack(
        [xs.ravel() * spacing, ys.ravel() * spacing, z.ravel()], axis=1
    ).astype(np.float32)
    n = r * c
    vid = lambda y, x: y * c + x
    src_l, dst_l = [], []
    # 8-connected: horizontal, vertical, both diagonals (cell shortcuts)
    for dy, dx in ((0, 1), (1, 0), (1, 1), (1, -1)):
        y = np.arange(max(0, -dy), r - max(0, dy))
        x = np.arange(max(0, -dx), c - max(0, dx))
        yy, xx = np.meshgrid(y, x, indexing="ij")
        a = vid(yy, xx).ravel()
        b = vid(yy + dy, xx + dx).ravel()
        src_l += [a, b]
        dst_l += [b, a]
    src = np.concatenate(src_l).astype(np.int32)
    dst = np.concatenate(dst_l).astype(np.int32)
    w = np.linalg.norm(coords[src] - coords[dst], axis=1).astype(np.float32)
    g = Graph.from_edges(src, dst, n, w=w, weight_dtype=np.float32)
    return g, coords
