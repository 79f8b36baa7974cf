"""Distributed frontier propagation via shard_map — Quegel's worker
partitioning mapped onto a device mesh (DESIGN.md §6).

Quegel hash-partitions vertices across workers and routes point-to-point
messages.  On a mesh we partition *edges* and replace routing with one
collective per superstep:

  partition="dst" (default) — each device owns a contiguous destination
      block; it combines messages for its block from the (replicated)
      frontier values, then the blocks are all-gathered.  Collective bytes
      per superstep: |V| * C * dtype (an all-gather of the result).  This
      is Pregel+'s receiver-side combiner taken to its limit: combining
      happens *before* any data crosses the interconnect.

  partition="src" — each device owns a source block and produces a dense
      partial combine for *all* destinations; partials are reduced with a
      min/max/sum all-reduce.  More collective bytes (~2x for a ring
      all-reduce) but immune to destination-degree skew (the paper's hub
      problem).

Both paths produce results identical to the single-device reference.
``ShardedBackend`` implements the ``kernels/ops.py`` PropagateBackend
protocol twice over: ``propagate`` is the standalone replicated-x entry
point (one jitted shard_map per semiring), while ``make_local`` returns
the propagate closure used INSIDE an enclosing shard_map body — that is
what lets ``QuegelEngine(mesh=...)`` run the whole fused super-round
(admission + k supersteps + done reduction) as one SPMD program with one
collective per superstep (DESIGN.md §6).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.graph import Graph
from repro.core.semiring import Semiring
from repro.kernels import ref
from repro.kernels.ops import PropagateBackend


def _pad_partition(src, dst, w, n_parts, key):
    """Split COO edges into n_parts buckets by the per-edge ``key`` array,
    padding every bucket to the max bucket size.

    Vectorized: one stable argsort groups edges by bucket (preserving the
    original within-bucket edge order, so segment reductions see the same
    operand order as the single-device reference) and one bincount sizes
    the padding — no Python loop over E.
    """
    key = np.asarray(key)
    order = np.argsort(key, kind="stable")
    counts = np.bincount(key, minlength=n_parts)
    need = int(max(1, counts.max())) if counts.size else 1
    # ~25% headroom (at least 4 rows' worth): ShardedGraph.apply_delta
    # splices mutated rows IN PLACE as long as they fit Emax, and Emax is
    # the shape every compiled SPMD round is keyed on — exact-fit buckets
    # would turn any single-edge add into a full re-partition + re-trace.
    emax = need + max(4, need // 4)
    rows = key[order]
    starts = np.concatenate(([0], np.cumsum(counts)))
    cols = np.arange(len(order)) - starts[rows]
    srcp = np.zeros((n_parts, emax), np.int32)
    dstp = np.zeros((n_parts, emax), np.int32)
    wp = np.zeros((n_parts, emax), w.dtype)
    valid = np.zeros((n_parts, emax), bool)
    srcp[rows, cols] = src[order]
    dstp[rows, cols] = dst[order]
    wp[rows, cols] = w[order]
    valid[rows, cols] = True
    return srcp, dstp, wp, valid


class ShardedGraph:
    """Edge partitions of a Graph for a mesh axis of size n_parts."""

    def __init__(self, graph: Graph, n_parts: int, partition: str = "dst"):
        assert graph.n % n_parts == 0, (
            "pad |V| to a multiple of the mesh axis (Graph.padded)"
        )
        self.graph = graph
        self.n_parts = n_parts
        self.partition = partition
        self.block = graph.n // n_parts
        src, dst, w = graph._edges_np()
        key = (dst if partition == "dst" else src) // self.block
        srcp, dstp, wp, valid = _pad_partition(src, dst, w, n_parts, key)
        self.srcp = jnp.asarray(srcp)
        self.dstp = jnp.asarray(dstp)
        self.wp = jnp.asarray(wp)
        self.valid = jnp.asarray(valid)

    @classmethod
    def _from_parts(cls, graph, n_parts, partition, srcp, dstp, wp, valid):
        sg = cls.__new__(cls)
        sg.graph = graph
        sg.n_parts = n_parts
        sg.partition = partition
        sg.block = graph.n // n_parts
        sg.srcp = jnp.asarray(srcp)
        sg.dstp = jnp.asarray(dstp)
        sg.wp = jnp.asarray(wp)
        sg.valid = jnp.asarray(valid)
        return sg

    def apply_delta(self, new_graph: Graph, delta) -> "ShardedGraph":
        """Partitions of ``new_graph`` spliced from these, touching only the
        rows ``delta`` can change (DESIGN.md §12 addendum).

        Row ``r`` of a dst-partition holds exactly the COO edges with
        ``dst // block == r`` in COO (dst-sorted) order, so a touched row is
        rebuilt from two ``searchsorted`` slices of the new graph's COO view
        — reproducing what a full ``_pad_partition`` would put there (its
        stable argsort preserves within-bucket COO order).  src-partition
        rows hold ``src // block == r`` in the same COO order, rebuilt by
        one boolean pass.  Emax is deliberately KEPT: stable partition
        shapes are what let the compiled SPMD round absorb the mutation
        without a re-trace.  A touched row outgrowing Emax falls back to a
        full re-partition (the shape change forces a re-trace regardless).
        """
        assert new_graph.n == self.graph.n, "vertex repad requires a rebuild"
        if delta is None or delta.is_empty:
            return ShardedGraph._from_parts(
                new_graph, self.n_parts, self.partition,
                self.srcp, self.dstp, self.wp, self.valid,
            )
        d = delta if self.partition == "dst" else delta.reversed()
        touched = d.touched_dst_blocks(self.block)
        touched = touched[(touched >= 0) & (touched < self.n_parts)]
        emax = int(self.srcp.shape[1])
        src, dst, w = new_graph._edges_np()
        srcp, dstp = np.array(self.srcp), np.array(self.dstp)
        wp, valid = np.array(self.wp), np.array(self.valid)
        for r in touched:
            r = int(r)
            if self.partition == "dst":
                lo = int(np.searchsorted(dst, r * self.block, side="left"))
                hi = int(np.searchsorted(dst, (r + 1) * self.block, side="left"))
                rs, rd, rw = src[lo:hi], dst[lo:hi], w[lo:hi]
            else:
                m = (src // self.block) == r
                rs, rd, rw = src[m], dst[m], w[m]
            k = len(rs)
            if k > emax:
                return ShardedGraph(new_graph, self.n_parts,
                                    partition=self.partition)
            srcp[r] = 0
            dstp[r] = 0
            wp[r] = 0
            valid[r] = False
            srcp[r, :k] = rs
            dstp[r, :k] = rd
            wp[r, :k] = rw
            valid[r, :k] = True
        return ShardedGraph._from_parts(
            new_graph, self.n_parts, self.partition, srcp, dstp, wp, valid
        )


class ShardedBackend(PropagateBackend):
    """PropagateBackend over a device mesh: edge partitions + one
    collective per superstep (module docstring; DESIGN.md §6)."""

    name = "sharded"

    def __init__(self, sg: ShardedGraph, mesh: Mesh, axis: str):
        self.sg = sg
        self.graph = sg.graph
        self.mesh = mesh
        self.axis = axis
        self._jitted: dict = {}

    @property
    def parts(self):
        """The (n_parts, Emax) edge-partition arrays, in shard_map arg order."""
        return (self.sg.srcp, self.sg.dstp, self.sg.wp, self.sg.valid)

    @property
    def part_specs(self):
        return (P(self.axis, None),) * 4

    def refresh(self, graph, delta=None):
        """A backend of the same plan serving the mutated ``graph``.

        With a ``delta``, only the partition rows it touches are re-spliced
        (``ShardedGraph.apply_delta``) and Emax — hence every compiled
        round's shapes — stays put, so SPMD mode absorbs in-capacity
        mutations without a re-trace.  Without a delta (or when a touched
        row outgrows Emax) the edges are fully re-partitioned; the
        vectorized ``_pad_partition`` is one argsort over E, cheap next to
        the re-trace the shape change forces anyway.
        """
        if delta is not None:
            sg = self.sg.apply_delta(graph, delta)
        else:
            sg = ShardedGraph(graph, self.sg.n_parts,
                              partition=self.sg.partition)
        return ShardedBackend(sg, self.mesh, self.axis)

    def as_args(self, graph_carrier=None, *, slot_cap=None):
        return {"parts": self.parts}

    def from_args(self, args):
        import copy

        sg = copy.copy(self.sg)
        sg.srcp, sg.dstp, sg.wp, sg.valid = args["parts"]
        new = copy.copy(self)
        new.sg = sg
        new._jitted = {}
        return new

    def make_local(self, parts):
        """Propagate closure for use INSIDE an enclosing shard_map body.

        ``parts`` is this device's (1, Emax) slice of :attr:`parts`; the
        returned ``prop(sr, x, frontier)`` takes the FULL (gathered /
        replicated) (..., V) value, combines over the local edge shard,
        and performs the single collective (all-gather of the owned dst
        block, or a semiring all-reduce of the dense partial).
        """
        srcp, dstp, wp, valid = (p[0] for p in parts)
        sg, axis = self.sg, self.axis
        blockn, n, part = sg.block, sg.graph.n, sg.partition

        def prop(sr: Semiring, x, frontier=None):
            add_id = jnp.asarray(sr.add_id, x.dtype)
            if frontier is not None:
                x = jnp.where(frontier, x, add_id)
            lead = x.shape[:-1]
            xf = x.reshape((-1, n))
            msgs = ref.apply_mul(sr, xf[:, srcp], wp)
            msgs = jnp.where(valid[None, :], msgs, add_id)
            if part == "dst":
                # padding entries fall outside [0, block) and are dropped;
                # their msgs are add_id anyway.
                seg = dstp - jax.lax.axis_index(axis) * blockn
                nseg = blockn
            else:
                seg, nseg = dstp, n

            def one(m):
                return ref._clamp_empty(
                    sr, sr.segment_combine(m, seg, nseg), add_id
                )

            y = jax.vmap(one)(msgs)
            if part == "dst":
                y = jax.lax.all_gather(y, axis, axis=1, tiled=True)
            elif sr.name in ("min_plus", "min_right"):
                y = jax.lax.pmin(y, axis)
            elif sr.name in ("max_plus", "max_right"):
                y = jax.lax.pmax(y, axis)
            else:
                y = jax.lax.psum(y, axis)
            return y.reshape(lead + (n,))

        return prop

    def propagate(self, sr: Semiring, x, frontier=None):
        """Standalone entry point: x (and the result) replicated across the
        mesh, one jitted shard_map per semiring (cached)."""
        fn = self._jitted.get(sr.name)
        if fn is None:

            def body(xf, *parts):
                return self.make_local(parts)(sr, xf)

            fn = jax.jit(
                jax.shard_map(
                    body,
                    mesh=self.mesh,
                    in_specs=(P(None, None),) + self.part_specs,
                    out_specs=P(None, None),
                    check_vma=False,
                )
            )
            self._jitted[sr.name] = fn
        if frontier is not None:
            x = jnp.where(frontier, x, jnp.asarray(sr.add_id, x.dtype))
        lead = x.shape[:-1]
        y = fn(x.reshape((-1, self.graph.n)), *self.parts)
        return y.reshape(lead + (self.graph.n,))


def make_propagate_sharded(sg: ShardedGraph, mesh: Mesh, axis: str, sr: Semiring):
    """Returns a propagate(x, frontier) -> (..., V) replicated — kept as
    the per-semiring functional wrapper over :class:`ShardedBackend`."""
    be = ShardedBackend(sg, mesh, axis)

    def propagate(x, frontier=None):
        return be.propagate(sr, x, frontier)

    return propagate
