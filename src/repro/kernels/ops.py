"""Propagation backends: the physical plans behind one logical superstep.

A *backend* (``PropagateBackend``) owns its prepared graph data — the COO
view, the CSR view for active-edge gathers, per-semiring block-sparse tile
tables, or a mesh's edge partitions — and exposes exactly one operation:

    propagate(sr, x, frontier=None) -> combined incoming messages (shape of x)

This is the logical/physical split Pregelix applies to Pregel plans: the
engine (``core/engine.py``) holds one backend per named propagation view
and never branches on *how* messages move.  Concrete plans:

  * ``coo``        — segment reduction over the COO view; with a frontier
                     and a min/max semiring it reads only the frontier's
                     out-arcs (through the CSR offsets) when they are few
                     enough, else every arc (``ref.propagate_coo_frontier``),
  * ``coo_gated``  — the same with ``gather_edges`` arcs a trip,
  * ``blocks_ref`` — jnp oracle over block-sparse dense tiles,
  * ``pallas``     — the Pallas frontier kernel (compiled on a TPU,
                     interpreted on the CPU for validation),
  * ``sharded``    — edge partitions over a device mesh, one collective per
                     superstep (``core/distributed.py::ShardedBackend``).

Sparsity gating (DESIGN.md §3): on the tile backends the frontier is NOT
applied as a dense pre-mask of x (that costs O(C·V) per superstep and
tells the kernel nothing).  Instead the mask is pushed into the block
path: a per-(dst_block, slot) activity bitmap — the frontier reduced over
the lane/slot axis, looked up per source block — lets the kernels skip
dead tiles entirely, and the per-lane mask is applied inside the visited
tiles only.  ``gate=False`` restores the dense pre-mask as the benchmark
baseline.
"""
from __future__ import annotations

from typing import Optional, Union

import jax.numpy as jnp
import numpy as np

from repro.core.graph import BlockSparse, Graph
from repro.core.semiring import Semiring
from repro.kernels import frontier, ref


def _trace_state_clean() -> bool:
    """True outside any jax trace.  Under ``jit`` or ``eval_shape`` even a
    constant array comes back as a tracer, so one probe array tells the two
    apart."""
    import jax

    return not isinstance(jnp.zeros((), jnp.int32), jax.core.Tracer)


def block_activity(bs: BlockSparse, mask) -> jnp.ndarray:
    """(nb, max_bpr) bool — which adjacency tiles can contribute.

    A tile is dead when it is a padding slot (k >= nslots[i]) or when its
    source block holds no active vertex in ANY lane (``mask`` reduced over
    every leading axis — the slot axis C in engine use).  ``mask=None``
    still gates padding slots.
    """
    nb, b, m = bs.num_dst_blocks, bs.block, bs.max_bpr
    valid = jnp.arange(m, dtype=jnp.int32)[None, :] < bs.nslots[:, None]
    if mask is None:
        return valid
    f = mask.any(axis=tuple(range(mask.ndim - 1)))  # (V,)
    f = jnp.pad(f, (0, nb * b - f.shape[0]))
    return valid & f.reshape(nb, b).any(-1)[bs.src_ids]


class PropagateBackend:
    """Protocol: one physical plan for one propagation view.

    Subclasses own whatever prepared form of the adjacency they need and
    implement ``propagate``; the engine treats them uniformly (DESIGN.md
    §2/§6).  ``name`` is the stable spec string ``make_backend`` accepts.
    """

    name = "?"

    def propagate(self, sr: Semiring, x: jnp.ndarray, frontier=None) -> jnp.ndarray:
        raise NotImplementedError

    def read_plan(self, sr: Semiring, shape, frontier=None):
        """What one ``propagate`` call on x of ``shape`` with this
        frontier does, for the tracing counters (``core/tracing.py``):
        ``(took the frontier path, arcs read)`` as traced scalars, decided
        as ``propagate`` decides; None where the plan cannot say (tile
        plans read whole tiles)."""
        return None

    def export_tables(self):
        """Prepared per-semiring state worth persisting (core/store.py):
        ``{sr.name: BlockSparse}`` for tile backends, else None.  A future
        engine passes the dict back as ``blocks=`` to skip the rebuild."""
        return None

    def refresh(self, graph: Graph, delta=None) -> "PropagateBackend":
        """A new backend of the same plan serving ``graph`` (DESIGN.md §12).

        ``delta`` is the ``EdgeDelta`` that produced ``graph`` from this
        backend's graph; plans with prepared state (tile tables, edge
        partitions) use it to update incrementally rather than rebuild.
        The receiver is left untouched — old editions keep serving
        in-flight slots until their last reader retires.
        """
        raise NotImplementedError(
            f"backend '{self.name}' does not support graph mutation"
        )

    def as_args(self, graph_carrier: Optional[Graph] = None, *,
                slot_cap: Optional[int] = None):
        """This plan's prepared arrays as a shape-stable pytree, for the
        argument-carried round (DESIGN.md §12 addendum).

        The result is passed as a *traced jit argument*; a later edition of
        the same plan must produce the same treedef and avals so the
        compiled round is reused.  ``graph_carrier`` is the engine's
        capacity-padded, lineage-stripped graph; ``slot_cap`` pads tile
        tables' slot axis.  Plans whose arrays cannot be carried (user
        callables) refuse — the engine then falls back to constant-closure
        editions.
        """
        raise NotImplementedError(
            f"backend '{self.name}' cannot be argument-carried"
        )

    def from_args(self, args) -> "PropagateBackend":
        """Rebind this plan to the (possibly traced) arrays from
        :meth:`as_args`.  Called inside the shared round's trace; must not
        build tables or touch the host."""
        raise NotImplementedError(
            f"backend '{self.name}' cannot be argument-carried"
        )


class CooBackend(PropagateBackend):
    """Segment reduction over the destination-sorted COO view.

    With a frontier, a min/max semiring and ``gate`` on, each call reads
    only the frontier's out-arcs where they are few enough, else every arc
    (``ref.propagate_coo_frontier``; DESIGN.md §3).  ``gather_edges`` sets
    the arcs a trip, which otherwise follow from the graph.
    """

    name = "coo"

    def __init__(self, graph: Graph, *, gather_edges: Optional[int] = None,
                 gate: bool = True):
        self.graph = graph
        self.gather_edges = gather_edges
        self.gate = bool(gate)

    def propagate(self, sr, x, frontier=None):
        if self.gate and frontier is not None:
            return ref.propagate_coo_frontier(
                self.graph, sr, x, frontier, self.gather_edges)
        return ref.propagate_coo(self.graph, sr, x, frontier)

    def read_plan(self, sr, shape, frontier=None):
        return ref.frontier_plan(self.graph, sr, shape,
                                 frontier if self.gate else None,
                                 self.gather_edges)

    def refresh(self, graph, delta=None):
        # no prepared state beyond the graph views, which apply_delta
        # already merged incrementally
        return CooBackend(graph, gather_edges=self.gather_edges, gate=self.gate)

    def as_args(self, graph_carrier=None, *, slot_cap=None):
        g = graph_carrier if graph_carrier is not None else self.graph.carrier()
        return {"graph": g}

    def from_args(self, args):
        return CooBackend(args["graph"], gather_edges=self.gather_edges,
                          gate=self.gate)


class _TileBackend(PropagateBackend):
    """Shared plumbing for the block-sparse plans.

    The backend owns its tile tables *per semiring* (a table encodes
    exactly one add-identity, DESIGN.md §2).  ``tables`` may be a single
    ``BlockSparse`` (used for every semiring — the caller asserts one
    semiring ever flows through this view), a prebuilt ``{sr.name: tiles}``
    dict, or None; missing entries are built lazily from the graph unless
    ``strict`` (the functional ``propagate`` path keeps strict=True so a
    backend A/B can never silently rebuild what the caller meant to pass).
    """

    def __init__(self, graph: Graph, *, tables=None, block: int = 128,
                 gate: bool = True, strict: bool = False):
        self.graph = graph
        self.block = int(block)
        self.gate = bool(gate)
        self.strict = bool(strict)
        self._shared = tables if isinstance(tables, BlockSparse) else None
        self.tables: dict = dict(tables) if isinstance(tables, dict) else {}

    def table_for(self, sr: Semiring) -> BlockSparse:
        if self._shared is not None:
            return self._shared
        t = self.tables.get(sr.name)
        if t is None:
            if self.strict:
                raise ValueError(
                    f"no block-sparse table for semiring '{sr.name}': build one "
                    "per semiring with Graph.to_blocks(block, sr.add_id)"
                )
            t = self.graph.to_blocks(
                self.block, sr.add_id, dtype=np.asarray(self.graph.w).dtype
            )
            # Only cache when built OUTSIDE a trace: a table built during a
            # jit trace holds that trace's constants and would leak into
            # later dispatches.  The engine pre-warms tables via a discovery
            # pass so engine use never hits the in-trace (uncached) path.
            if _trace_state_clean():
                self.tables[sr.name] = t
        return t

    def export_tables(self):
        if self._shared is not None:
            return self._shared
        return dict(self.tables) or None

    def refresh(self, graph, delta=None):
        """Incrementally carry every cached tile table to ``graph``.

        Each per-semiring table is patched via ``Graph.update_blocks`` on
        the delta's touched dst-block rows only; without a delta the tables
        are rebuilt in full.  A shared single-table backend refuses — its
        add-identity is unknown, so the padding fill of grown slots would
        be a guess.
        """
        import copy

        from repro.core.semiring import BY_NAME

        if self._shared is not None:
            raise ValueError(
                "cannot refresh a shared single-table tile backend: the "
                "table's semiring (add_id) is unknown; construct with a "
                "{sr.name: BlockSparse} dict instead"
            )
        tables = {}
        for name, bs in self.tables.items():
            sr = BY_NAME[name]
            if delta is not None:
                tables[name] = graph.update_blocks(
                    bs, sr.add_id, delta.touched_dst_blocks(bs.block)
                )
            else:
                tables[name] = graph.to_blocks(
                    bs.block, sr.add_id, dtype=np.asarray(bs.tiles).dtype
                )
        new = copy.copy(self)
        new.graph = graph
        new.tables = tables
        return new

    def as_args(self, graph_carrier=None, *, slot_cap=None):
        from repro.core.graph import pad_block_slots
        from repro.core.semiring import BY_NAME

        if self._shared is not None:
            raise NotImplementedError(
                "cannot argument-carry a shared single-table tile backend: "
                "the table's semiring (add_id) is unknown, so the slot "
                "padding fill would be a guess"
            )
        tables = {}
        for name, bs in self.tables.items():
            sr = BY_NAME[name]
            tables[name] = (pad_block_slots(bs, int(slot_cap), sr.add_id)
                            if slot_cap else bs)
        return {"tables": tables}

    def from_args(self, args):
        import copy

        new = copy.copy(self)
        new.tables = dict(args["tables"])
        new._shared = None
        # an in-trace table miss must fail loudly, never rebuild from the
        # (host, stale) graph this copy still references
        new.strict = True
        return new

    def propagate(self, sr, x, frontier=None):
        bs = self.table_for(sr)
        lead = x.shape[:-1]
        flat = x.reshape((-1, x.shape[-1]))
        mflat = None
        if frontier is not None:
            mflat = jnp.broadcast_to(frontier, x.shape).reshape(flat.shape)
        if not self.gate:
            # dense baseline: pre-mask x over the full (C, V) slab, no tile
            # skipping — the very cost the gated path removes.
            if mflat is not None:
                flat = jnp.where(mflat, flat, jnp.asarray(sr.add_id, x.dtype))
                mflat = None
            active = None
        else:
            active = block_activity(bs, mflat)
        out = self._run(bs, sr, flat, mflat, active)
        return out.reshape(lead + (x.shape[-1],))

    def _run(self, bs, sr, flat, mflat, active):
        raise NotImplementedError


class BlocksRefBackend(_TileBackend):
    name = "blocks_ref"

    def _run(self, bs, sr, flat, mflat, active):
        return ref.propagate_blocks_ref(bs, sr, flat, mask=mflat, active=active)


class PallasBackend(_TileBackend):
    """The Pallas kernel: compiled with Mosaic on a TPU, interpreted on any
    other platform (``frontier.propagate_blocks``)."""

    name = "pallas"

    def _run(self, bs, sr, flat, mflat, active):
        return frontier.propagate_blocks(bs, sr, flat, mask=mflat, active=active)


class CallableBackend(PropagateBackend):
    """Adapter for a user-supplied ``(sr, x, frontier) -> y`` callable (the
    engine's ``propagate_override`` escape hatch)."""

    name = "callable"

    def __init__(self, fn):
        self.fn = fn

    def propagate(self, sr, x, frontier=None):
        return self.fn(sr, x, frontier)


def make_backend(
    spec: Union[str, PropagateBackend],
    graph: Graph,
    *,
    blocks: Optional[Union[BlockSparse, dict]] = None,
    block: int = 128,
    gate: bool = True,
    gather_edges: Optional[int] = None,
    strict_tables: bool = False,
    mesh=None,
    mesh_axis: Optional[str] = None,
    partition: str = "dst",
) -> PropagateBackend:
    """Resolve a backend spec to a ``PropagateBackend`` owning ``graph``.

    ``spec`` may already be a backend instance (returned as-is) or one of
    the plan names in the module docstring.  ``strict_tables`` forbids the
    tile backends from lazily building missing tables (the honesty rule of
    the functional path); the engine leaves it off so tile tables are built
    on demand per semiring.  ``sharded`` needs ``mesh`` (and shards over
    ``mesh_axis``, default the mesh's last axis).
    """
    if isinstance(spec, PropagateBackend):
        return spec
    if spec == "coo":
        return CooBackend(graph, gather_edges=gather_edges, gate=gate)
    if spec == "coo_gated":
        return CooBackend(graph, gather_edges=int(gather_edges or 512), gate=True)
    if spec in ("blocks_ref", "pallas"):
        if blocks is None and strict_tables:
            # A silent COO fallback (or a silently rebuilt table) here would
            # invalidate any backend A/B comparison.
            raise ValueError(
                f"backend '{spec}' needs a block-sparse adjacency: build one "
                "with Graph.to_blocks(block, sr.add_id) and pass blocks="
            )
        kw = dict(tables=blocks, block=block, gate=gate, strict=strict_tables)
        if spec == "pallas":
            return PallasBackend(graph, **kw)
        return BlocksRefBackend(graph, **kw)
    if spec == "sharded":
        from repro.core.distributed import ShardedBackend, ShardedGraph

        if mesh is None:
            raise ValueError(
                "backend 'sharded' needs mesh= (a jax Mesh whose shard axis "
                "divides |V|; see Graph.padded)"
            )
        axis = mesh_axis or mesh.axis_names[-1]
        n_parts = int(mesh.shape[axis])
        sg = graph if isinstance(graph, ShardedGraph) else ShardedGraph(
            graph, n_parts, partition=partition
        )
        return ShardedBackend(sg, mesh, axis)
    raise ValueError(f"unknown propagation backend {spec!r}")


def propagate(
    graph: Graph,
    sr: Semiring,
    x: jnp.ndarray,
    frontier_mask: Optional[jnp.ndarray] = None,
    *,
    blocks: Optional[Union[BlockSparse, dict]] = None,
    backend: Union[str, PropagateBackend] = "coo",
    gate: bool = True,
    gather_edges: Optional[int] = None,
    mesh=None,
    mesh_axis: Optional[str] = None,
    partition: str = "dst",
) -> jnp.ndarray:
    """One superstep of combined message propagation. x: (..., V).

    Functional convenience over :func:`make_backend` for fixpoint jobs and
    tests; long-lived callers (the engine) hold backend objects instead so
    prepared data (tile tables, edge partitions) persists across calls.
    In particular ``backend='sharded'`` re-partitions the edges and
    re-jits its shard_map PER CALL here — for anything repeated, hold a
    backend from ``make_backend`` (or ``make_propagate_sharded``) instead.
    ``blocks`` may be a dict keyed by semiring name (programs mixing
    semirings on one view need one tile table per add-identity); a tile
    backend without a matching table refuses rather than rebuilding.
    ``gather_edges`` (coo only) sets the arcs a frontier pass reads a
    trip.
    """
    be = make_backend(
        backend,
        graph,
        blocks=blocks,
        gate=gate,
        gather_edges=gather_edges,
        strict_tables=True,
        mesh=mesh,
        mesh_axis=mesh_axis,
        partition=partition,
    )
    return be.propagate(sr, x, frontier_mask)
