"""The Quegel engine: query-centric superstep-sharing on JAX.

The paper's central idea (§3.1): up to ``C`` concurrent queries each advance
one superstep per *super-round*, sharing a single synchronization barrier.
Here a super-round is **one jitted dispatch**: per-query state lives in a
dense slot table (leading axis C) and the vertex program is ``vmap``-ed over
slots.  The single device->host sync per round (reading the ``done`` flags)
is the analogue of the paper's one barrier per super-round.

Hot path (DESIGN.md §3): admission and the superstep advance are FUSED into
one jitted call per round.  The slot table is donated
(``donate_argnums=0``) so each round updates the ``(C, V, ...)`` slabs in
place instead of copying them; admission of up to C queued queries is one
batched scatter (``vmap``-ed ``init`` + masked select) inside the same
dispatch; and slot liveness is mirrored host-side so a round performs
exactly ONE device->host sync (the ``done``/``step`` readback).  With
``steps_per_round=k`` the round runs up to k supersteps in a
``lax.while_loop`` (all-live-slots-done early exit), so that one sync
amortizes over k supersteps; propagation itself is sparsity-gated
(``gate``/``gather_edges``, DESIGN.md §3) so superstep cost tracks the
active frontier.  The pre-refactor path (per-query admission dispatches,
live readback before every round, undonated copies) is preserved under
``legacy=True`` as the benchmark baseline.

The slot LIFECYCLE (queue, admission, liveness mirror, retirement,
stats, drain) lives in ``core/runtime.py::SlotRuntime``, shared with the
LM ``SlotServer`` (DESIGN.md §9); the engine implements only the
device-side ``SlotProgram`` hooks below.  Through the runtime the engine
inherits pluggable admission schedulers (fifo/priority/sjf/deadline),
per-query superstep budgets with TIMEOUT eviction, and an opt-in result
cache for repeated queries.

Propagation is pluggable (DESIGN.md §2/§6): the engine holds one
``kernels/ops.py::PropagateBackend`` per named view ('default', 'rev', ...)
and never branches on the physical plan — COO segment ops, block tiles,
Pallas, or a device mesh are interchangeable under the same vertex
program (the Pregelix logical/physical split).

SPMD mode (DESIGN.md §6): ``QuegelEngine(mesh=...)`` shards every
``(C, ..., V)`` slot-table leaf over a mesh axis and runs the ENTIRE fused
round — batched admission, the k-superstep while_loop, the done-flag
reduction — inside one ``shard_map``.  The round body all-gathers the
V-sharded leaves at entry, advances with ONE collective per propagate call
(``ShardedBackend``'s dst/src edge partitions), and slices each device's
V-shard back out, so donation, single-sync rounds and multi-superstep
fusion all survive sharding and results are identical to the
single-device engine.

Data taxonomy (paper §3.2) maps as:
  V-data  : the ``Graph``/index arrays, closed over by the jitted round —
            loaded once, shared by all queries (decoupled from querying).
  VQ-data : slot-table leaves of shape (C, V, ...), lazily *initialized*
            (not lazily allocated — DESIGN.md §2) at admission.
  Q-data  : slot-table leaves of shape (C, ...) — query content, per-query
            superstep counter, live/done flags, aggregator scratch.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import threading
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tracing
from repro.core.graph import BlockSparse, EdgeDelta, Graph
from repro.core.runtime import (
    DONE, QueryTimeoutError, ResumeAdmission, RoundOutcome, SlotProgram,
    SlotRuntime, SlotStats, default_cache_key)
from repro.kernels import ops


def tree_where(pred, a, b):
    """Select whole pytrees by a scalar (or per-slot) predicate."""
    def sel(x, y):
        p = pred
        while p.ndim < x.ndim:
            p = p[..., None]
        return jnp.where(p, x, y)

    return jax.tree.map(sel, a, b)


def view_propagate(backends: dict, fns: Optional[dict] = None) -> Callable:
    """The round's ``propagate(sr, x, frontier, which)``: view ``which``'s
    plan (``backends[which].propagate``, or ``fns[which]`` where the SPMD
    round runs a per-device local closure) under the named scope
    ``propagate.<view>.<plan>``, so a device trace names each view's and
    plan's ops.  Metadata only: the compiled ops are the same."""
    fns = fns or {k: be.propagate for k, be in backends.items()}

    def propagate(sr, x, frontier=None, which="default"):
        with jax.named_scope(f"propagate.{which}.{backends[which].name}"):
            return fns[which](sr, x, frontier)

    return propagate


@dataclasses.dataclass
class StepCtx:
    """Everything ``superstep`` may touch besides its own VQ/Q-data."""

    graph: Graph
    query: Any  # this slot's query content (unstacked)
    step: jnp.ndarray  # scalar int32, 1-based as in Pregel/Quegel
    propagate: Callable  # (semiring, x, frontier) -> combined messages
    index: Any = None  # optional V-data index (hub labels, inverted index..)


class VertexProgram:
    """Base class users subclass per query type (paper §4).

    ``init(graph, query, index)``   -> fresh VQ/Q-data pytree for one query
                                       (the `init_value`/`init_activate` pair:
                                       programs set their own initial frontier
                                       from the query + index).
    ``superstep(state, ctx)``       -> (state, done) — one Pregel superstep
                                       for one query; vectorized over V.
    ``extract(state, query)``       -> small result pytree (reported to the
                                       console / dumped, paper's last round).
    ``frontier_of(state)``          -> optional ``{view: mask}``: per
                                       propagation view ('default', 'rev',
                                       ...), the (..., V) bool mask of the
                                       vertices whose out-arcs in that view
                                       the next superstep propagates.  Under
                                       the profiler the engine counts those
                                       arcs each round (``core/tracing.py``
                                       ``FRONTIER_ARCS``); it is also what
                                       sparsity gating reasons about
                                       (DESIGN.md §3).
    """

    def init(self, graph: Graph, query, index=None):
        raise NotImplementedError

    def superstep(self, state, ctx: StepCtx):
        raise NotImplementedError

    def extract(self, state, query):
        raise NotImplementedError

    def frontier_of(self, state):
        return None


@dataclasses.dataclass
class EngineStats(SlotStats):
    """Shared lifecycle counters (SlotStats) under the engine's names.

    ``super_rounds`` and ``barriers`` both read the runtime's round
    counter — one sync per round by construction (DESIGN.md §3)."""

    # Times an engine-owned jitted entry point traced+compiled (DESIGN.md
    # §12 addendum): every jit the engine builds routes through
    # ``QuegelEngine._jit``, whose wrapped body runs exactly once per
    # compile.  The per-version split lives in ``engine.compile_counts``.
    # In arg-carried mode an in-capacity mutation must leave this flat.
    jit_compiles: int = 0
    # background edition warmups spawned (constant-closure mode)
    warmups: int = 0

    @property
    def super_rounds(self) -> int:
        return self.rounds

    @property
    def barriers(self) -> int:
        return self.rounds


@dataclasses.dataclass
class _Edition:
    """One compiled graph version (DESIGN.md §12).

    Constant-closure mode: every jitted round closure captures its
    graph/index/backend arrays as trace constants, so a version bump cannot
    reuse them — the engine keeps one edition (the immutable Graph snapshot
    plus its compiled round entry points) per version still referenced by a
    live or suspended query.  Argument-carried mode (§12 addendum) instead
    points every edition at ONE shared set of jitted entries and puts the
    version's arrays in ``round_args`` (the traced "carrier"), so an
    in-capacity mutation reuses the compiled round bit-for-bit.
    ``apply_delta`` installs a new edition and prunes editions no reader
    can reach any more.
    """

    version: int
    graph: Graph
    index: Any
    aux: dict                 # view name -> Graph (non-default views)
    backends: dict            # view name -> PropagateBackend
    round: Any = None         # fused/SPMD: jit (slots, vmask, *round_args)
    round_admit: Any = None
    round_resume: Any = None
    round_args: tuple = ()    # SPMD edge parts and/or the arg-carried carrier
    degrees: dict = None      # frontier view name -> (V,) out-degrees
    plans: dict = None        # view name -> as_args() of plans that can
                              # say what a call reads (read_plan)
    count_admit: Any = None   # frontier counters of admission rounds
    admit: Any = None         # legacy: jit per-slot admission
    super_round: Any = None   # legacy: jit (slots, vmask)


class QuegelEngine(SlotProgram):
    """Superstep-sharing scheduler (paper §3).

    capacity  : the paper's C — max queries in flight per super-round.
    backend   : a ``PropagateBackend`` spec — 'coo', 'coo_gated',
                'blocks_ref', 'pallas', 'sharded' (implied by mesh=) — or a
                ready backend instance.  One backend is built per named
                view; tile backends build per-semiring block tables on
                demand (DESIGN.md §2).
    blocks    : optional prebuilt tile table(s) for the default view — a
                single ``BlockSparse`` or a ``{sr.name: BlockSparse}`` dict.
    aux_graphs: named alternate propagation views, e.g. {"rev": g.reverse()}
                for backward BFS; values may be a Graph or (Graph, blocks).
    block     : tile size for lazily-built block tables.
    mesh      : a jax Mesh — turns on SPMD mode (module docstring): slot
                tables sharded over ``mesh_axis`` (default: the mesh's last
                axis), the whole fused round one shard_map, edge partitions
                per ``partition``.  |V| must divide the axis size
                (``Graph.padded``); results and stats are identical to the
                single-device engine.
    partition : 'dst' (all-gather of combined blocks) or 'src' (semiring
                all-reduce of dense partials) — DESIGN.md §6.
    legacy    : keep the pre-overhaul round structure (per-query admission
                dispatches, live readback, per-query extraction, no
                donation) — the A/B baseline for the benchmark harness;
                results and stats are identical.  Single-device only.
    donate    : donate the slot table to the round dispatch so XLA aliases
                outputs to inputs (in-place update, no per-round copy of
                the (C, V, ...) slabs).  Default 'auto': on for TPU/GPU,
                off for CPU where donated calls skip jit's C++ fast path
                and the dispatch penalty exceeds the copy saved
                (DESIGN.md §3).
    steps_per_round : run up to k supersteps inside ONE jitted round via a
                ``lax.while_loop`` with an all-live-slots-done early exit,
                amortizing dispatch + the device->host sync ~k× (the
                barrier invariant becomes "one barrier per k supersteps",
                DESIGN.md §3).  Per-slot superstep accounting stays exact;
                admission still happens at round boundaries only.
    gate      : sparsity gating (DESIGN.md §3): tile backends skip
                frontier-dead adjacency tiles instead of pre-masking x
                densely, and the coo backend reads only the frontier's
                out-arcs where they are few enough.  ``gate=False`` is
                the dense A/B baseline.  (No effect on the sharded
                backend, which combines densely over each device's edge
                shard.)
    gather_edges : coo backend: the arcs its frontier pass reads a trip,
                in place of the share of the view's arcs it otherwise
                takes.
    scheduler : admission policy (DESIGN.md §9) — 'fifo' (default, the
                paper's behavior), 'priority', 'sjf', 'deadline', or a
                ``runtime.Scheduler`` instance.  Changes only WHICH
                queued queries share the next super-round, never their
                results.
    result_cache : LRU size for the opt-in result cache — repeated
                queries (canonicalized+hashed pytrees) are answered from
                host memory without touching the device.  None (default)
                disables it.
    preemptive : round-boundary preemption (DESIGN.md §9, the paper's
                console *suspend*): a waiting query that beats the
                worst-ranked running query by ``preempt_margin`` suspends
                it — state collected to host via ``slot_suspend``, slot
                freed, query re-queued as a resume ticket with its
                superstep accounting intact.  Requires a key-ordered
                scheduler (priority/sjf/deadline); results are identical
                to the non-preemptive run.
    preempt_margin : how decisively a waiting key must beat a running rank
                to trigger suspension (0.0 = any strict win).
    journal / snapshot_every / straggler / max_retries : fault tolerance
                (DESIGN.md §10), passed through to the SlotRuntime — a
                ``QueryJournal`` WAL of the query lifecycle, its in-flight
                snapshot cadence, a ``StragglerMonitor`` fed per-round
                wall time, and the poison-quarantine retry bound.
    index_fn  : index maintainer for mutable graphs (DESIGN.md §12):
                ``fn(new_graph, old_index, delta) -> (new_index, info)``,
                called by ``apply_delta`` whenever the engine carries an
                index (e.g. ``apps/hub2.py::hub_index_updater``).  Required
                for ``apply_delta`` on indexed engines and for journal
                replay of mutations after a crash.
    arg_carried : compile-once serving across graph versions (DESIGN.md
                §12 addendum).  ``True``: the round's graph/index/backend
                arrays are traced jit ARGUMENTS (capacity-padded for shape
                stability) instead of closure constants, so an in-capacity
                ``apply_delta`` reuses the compiled round with 0 recompiles
                — mutate-to-first-answer drops from a full round compile to
                the host splice.  ``'auto'`` (default): on past
                ``arg_carried_threshold`` edges, where constant-folding no
                longer pays for per-version recompiles; off below it.
                ``False``: always constant-closure.  Incompatible with
                ``legacy=True``, ``propagate_override`` and shared
                single-table ``blocks=`` (pass a per-semiring dict).
    arg_carried_threshold : edge count past which ``arg_carried='auto'``
                enables argument-carried editions.
    edge_capacity : initial padded edge capacity per view in arg-carried
                mode (default: ~25% headroom over |E|).  Deltas that fit
                change array values only; overflow grows capacity and pays
                one real recompile.
    warmup    : background edition warmup for constant-closure mode:
                ``apply_delta`` returns immediately and the new edition's
                round/round_admit/round_resume compile on a worker thread
                while prior editions keep serving (mixed-version dispatch
                makes this safe); the new edition swaps in atomically —
                first dispatch after the warm finds the compile cache hot.
                ``wait_warmup()`` joins outstanding warms.  No-op in
                arg-carried mode (nothing to compile per edition).
    """

    def __init__(
        self,
        graph: Graph,
        program: VertexProgram,
        capacity: int = 8,
        *,
        index: Any = None,
        index_fn: Optional[Callable] = None,
        backend: Any = "coo",
        blocks: Optional[Any] = None,
        aux_graphs: Optional[dict] = None,
        block: int = 128,
        example_query: Any = None,
        propagate_override: Optional[dict] = None,
        legacy: bool = False,
        donate: Any = "auto",
        steps_per_round: int = 1,
        gate: bool = True,
        gather_edges: Optional[int] = None,
        mesh: Any = None,
        mesh_axis: Optional[str] = None,
        partition: str = "dst",
        scheduler: Any = "fifo",
        result_cache: Optional[int] = None,
        preemptive: bool = False,
        preempt_margin: float = 0.0,
        journal: Any = None,
        snapshot_every: int = 0,
        straggler: Any = None,
        max_retries: int = 2,
        arg_carried: Any = "auto",
        arg_carried_threshold: int = 100_000,
        edge_capacity: Optional[int] = None,
        warmup: bool = False,
    ):
        """``propagate_override`` maps a view name ('default', 'rev', ...)
        to a callable (semiring, x, frontier) -> y — wrapped in a
        ``CallableBackend`` so even escape hatches route through the
        PropagateBackend protocol."""
        self.graph = graph
        self.program = program
        self.capacity = int(capacity)
        self.index = index
        self.index_fn = index_fn
        self.blocks = blocks
        self.propagate_override = dict(propagate_override or {})
        self.legacy = bool(legacy)
        self.steps_per_round = int(steps_per_round)
        if self.steps_per_round < 1:
            raise ValueError("steps_per_round must be >= 1")
        if self.legacy and self.steps_per_round != 1:
            raise ValueError("legacy mode predates multi-superstep rounds")
        self.gate = bool(gate)
        self.gather_edges = gather_edges
        self.mesh = mesh
        self.partition = partition
        if mesh is not None:
            if not isinstance(backend, str) or backend not in ("coo", "sharded"):
                raise ValueError(
                    f"mesh= implies the sharded backend; got backend={backend!r}"
                )
            backend = "sharded"
            self._mesh_axis = mesh_axis or mesh.axis_names[-1]
            self._n_parts = int(mesh.shape[self._mesh_axis])
            if self.legacy:
                raise ValueError("legacy mode is single-device only")
            if self.propagate_override:
                raise ValueError(
                    "propagate_override and mesh= are mutually exclusive: "
                    "override callables cannot run inside the SPMD round"
                )
            if graph.n % self._n_parts:
                raise ValueError(
                    f"|V|={graph.n} must be a multiple of mesh axis "
                    f"'{self._mesh_axis}'={self._n_parts}: repad via "
                    f"Graph.padded({self._n_parts})"
                )
        elif backend == "sharded":
            raise ValueError("backend='sharded' needs mesh=")
        self.backend = backend

        # One PropagateBackend per named view — the engine's only contact
        # with the physical propagation plan.
        views = {"default": (graph, blocks)}
        for name, val in (aux_graphs or {}).items():
            g_, b_ = val if isinstance(val, tuple) else (val, None)
            views[name] = (g_, b_)
        self.aux_graphs = {k: v for k, v in views.items() if k != "default"}
        if isinstance(backend, ops.PropagateBackend):
            # A ready instance owns ONE view's graph; reusing it for aux
            # views would propagate them over the wrong adjacency.
            unbound = set(self.aux_graphs) - set(self.propagate_override)
            if unbound:
                raise ValueError(
                    f"backend instance cannot serve auxiliary views {sorted(unbound)}: "
                    "pass a spec string, or cover each view via propagate_override"
                )
        for name, (g_, b_) in views.items():
            if mesh is not None and g_.n != graph.n:
                raise ValueError(
                    f"view '{name}' has |V|={g_.n} != {graph.n}: all views "
                    "must share one padded vertex space under mesh="
                )
            if mesh is not None and b_ is not None:
                raise ValueError(
                    f"blocks for view '{name}' have no effect under mesh=: "
                    "the sharded backend combines over edge partitions, not "
                    "tile tables"
                )

        # ---- argument-carried editions (DESIGN.md §12 addendum)
        # Carriable: no override callables, and no tile view sharing one
        # single-semiring table (its add-identity is unknown).
        if isinstance(backend, ops.PropagateBackend):
            shared = getattr(backend, "_shared", None) is not None
        else:
            shared = backend in ("blocks_ref", "pallas") and any(
                isinstance(b_, BlockSparse) for _, b_ in views.values())
        carriable = (not self.legacy and not self.propagate_override
                     and not isinstance(backend, ops.CallableBackend)
                     and not shared)
        if arg_carried == "auto":
            self._arg_carried = (
                carriable and graph.num_edges >= int(arg_carried_threshold)
            )
        elif arg_carried:
            if not carriable:
                raise ValueError(
                    "arg_carried=True needs carriable backends: legacy=False, "
                    "no propagate_override, and no shared single-table "
                    "blocks= (pass a {sr.name: BlockSparse} dict instead)"
                )
            self._arg_carried = True
        else:
            self._arg_carried = False
        self._edge_capacity = None if edge_capacity is None else int(edge_capacity)
        self.warmup = bool(warmup)
        if self.warmup and self.legacy:
            raise ValueError(
                "warmup=True needs the fused round (legacy admission "
                "dispatches per query and cannot be pre-compiled generically)"
            )
        if self.warmup and mesh is not None:
            raise ValueError(
                "warmup=True is a single-device knob (a warm call with "
                "unplaced copies would compile for the wrong shardings); "
                "mesh mode absorbs mutations via arg_carried=True instead"
            )
        # compile accounting + arg-carried/warmup state, needed before _build
        self.compile_counts: dict[int, int] = {}
        self._dispatch_version = int(graph.version)
        self._view_caps: dict[str, int] = {}
        self._slot_caps: dict[str, int] = {}
        self._ac_entries = None     # shared (round, admit, resume) jits
        self._ac_protos: dict = {}  # plan-parameter templates for from_args
        self._spmd_ac = None        # shared SPMD entries + shardings
        self._warm_threads: list = []
        if self._arg_carried and mesh is None and isinstance(backend, str):
            # The carrier is every view capacity-padded.  Hold the views
            # padded from the start so the carrier shares their arrays;
            # padding only at bind time kept a second copy of every view's
            # edges on the device next to the unpadded one.
            views = {
                name: (g_.with_capacity(max_e=self._edge_cap(name, g_)), b_)
                for name, (g_, b_) in views.items()
            }
            self.graph = graph = views["default"][0]
            self.aux_graphs = {k: v for k, v in views.items() if k != "default"}
        self._backends = {
            name: ops.make_backend(
                backend,
                g_,
                blocks=b_,
                block=block,
                gate=self.gate,
                gather_edges=gather_edges,
                mesh=mesh,
                mesh_axis=mesh_axis,
                partition=partition,
            )
            for name, (g_, b_) in views.items()
        }
        for name, fn in self.propagate_override.items():
            self._backends[name] = ops.CallableBackend(fn)

        if donate == "auto":
            donate = jax.default_backend() not in ("cpu",)
        self.donate = bool(donate)
        # Queue, admission, liveness mirror, retirement, stats and drain
        # all live in the shared SlotRuntime (DESIGN.md §9); this class is
        # the device-side SlotProgram.
        self.runtime = SlotRuntime(
            self, self.capacity, scheduler=scheduler, stats=EngineStats(),
            cache_size=result_cache, preemptive=preemptive,
            preempt_margin=preempt_margin, journal=journal,
            snapshot_every=snapshot_every, straggler=straggler,
            max_retries=max_retries,
        )
        self._collective_model: Optional[dict] = None
        if example_query is None:
            raise ValueError("example_query required to shape the slot table")
        self._build(example_query)

    @property
    def stats(self) -> EngineStats:
        return self.runtime.stats

    @stats.setter
    def stats(self, value) -> None:
        self.runtime.stats = value

    @property
    def _results(self) -> dict:
        """qid -> extracted result (the runtime's map; kept as the
        historical attribute name for tests/benchmarks)."""
        return self.runtime.results

    @property
    def status(self) -> dict:
        """qid -> DONE | TIMEOUT | REJECTED (see core/runtime.py)."""
        return self.runtime.status

    # ------------------------------------------------------------ plumbing
    def _jit(self, fn, version: Optional[int] = None, **jit_kw):
        """``jax.jit`` with compile accounting: the wrapped body runs
        exactly once per trace/compile (never per dispatch), bumping
        ``stats.jit_compiles`` and the per-version ``compile_counts``.
        Shared arg-carried entries pass ``version=None`` and charge the
        version being dispatched (``_dispatch_version``); per-edition
        closures charge their own version.  This is the counter the
        mutation bench and CI's zero-recompile assertion read."""

        @functools.wraps(fn)  # the compiled module is named jit_<fn>
        def counted(*args):
            self.stats.jit_compiles += 1
            v = self._dispatch_version if version is None else version
            self.compile_counts[v] = self.compile_counts.get(v, 0) + 1
            return fn(*args)

        return jax.jit(counted, **jit_kw)

    def _build(self, example_query):
        """Version-agnostic scaffolding: the slot table, protos, extraction
        and diagnostics.  Everything that captures graph arrays as jit
        constants lives in per-version ``_Edition`` records built by
        ``_make_edition`` (DESIGN.md §12)."""
        g, prog, C = self.graph, self.program, self.capacity
        proto_q = jax.tree.map(jnp.asarray, example_query)
        proto_state = prog.init(g, proto_q, self.index)
        # host-side copies for cheap np.stack when batching admissions
        # (the state proto fills non-resuming rows of the resume payload)
        self._proto_q_np = jax.tree.map(np.asarray, proto_q)
        self._proto_state_np = jax.tree.map(np.asarray, proto_state)

        def stack(proto):
            return jax.tree.map(lambda x: jnp.zeros((C,) + jnp.shape(x), jnp.asarray(x).dtype), proto)

        self._slots = dict(
            state=stack(proto_state),
            query=stack(proto_q),
            step=jnp.zeros((C,), jnp.int32),
            live=jnp.zeros((C,), bool),
            done=jnp.zeros((C,), bool),
        )

        def extract(slots, idx):
            st = jax.tree.map(lambda tab: tab[idx], slots["state"])
            q = jax.tree.map(lambda tab: tab[idx], slots["query"])
            return prog.extract(st, q)

        self._extract = self._jit(extract, version=int(g.version))

        if self.legacy:
            # resume restoration is a pure scatter of host-collected state
            # — no graph constants, so ONE jitted closure serves every
            # edition (fresh admission does run ``init`` and is per-edition)
            def admit_resume(slots, idx, query, state, steps):
                slots = dict(slots)
                slots["state"] = jax.tree.map(
                    lambda tab, v: tab.at[idx].set(v), slots["state"], state
                )
                slots["query"] = jax.tree.map(
                    lambda tab, v: tab.at[idx].set(v), slots["query"], query
                )
                slots["step"] = slots["step"].at[idx].set(steps)
                slots["live"] = slots["live"].at[idx].set(True)
                slots["done"] = slots["done"].at[idx].set(False)
                return slots

            self._legacy_admit_resume = self._jit(
                admit_resume, version=int(g.version)
            )
        else:

            def extract_all(slots):
                return jax.vmap(prog.extract)(slots["state"], slots["query"])

            # one dispatch extracts every slot; run_round slices the rows
            # of finished queries host-side (results are small Q-data).
            self._extract_all = self._jit(extract_all, version=int(g.version))

        # The round's frontier counters, only under the profiler
        # (core/tracing.py): dispatched before each version's round on the
        # slot table as the round will see it (this round's admissions
        # applied), so they describe the frontiers the round propagates.
        # Per slot and view, the out-degrees of that frontier; per
        # propagate call, its plan's decision and arcs read (``read_plan``,
        # the frontier masked to the advancing slots as in
        # ``make_super_round``).  Each edition passes its degrees, plan
        # arrays and, for admissions, graph and index as arguments, so one
        # compile serves every graph version.
        self._frontier_views = None
        self._frontier_warm = False
        views = prog.frontier_of(proto_state)
        if views is not None:
            unknown = set(views) - set(self._backends)
            if unknown:
                raise ValueError(
                    f"frontier_of names views {sorted(unknown)} the engine "
                    f"does not have: {sorted(self._backends)}"
                )
            names = tuple(sorted(views))

            def counts(slots, vmask, degrees, plans, admission, m):
                if admission:
                    fill = m["admit_batch_resume" if len(admission) > 2
                             else "admit_batch"]
                    slots = fill(slots, *admission)
                adv = slots["live"] & vmask
                fr = jax.vmap(prog.frontier_of)(slots["state"])
                fr = {v: f & adv.reshape((C,) + (1,) * (f.ndim - 1))
                      for v, f in fr.items()}
                arcs = jnp.stack([
                    jnp.where(fr[v], degrees[v], 0).reshape(C, -1).sum(1)
                    for v in names], -1)
                took, reads = [], []
                for which, sr, shape, has_frontier in self._prop_calls:
                    plan = None
                    if which in plans and (not has_frontier or which in fr):
                        f = None
                        if has_frontier:  # as the slot passes it: lanes lead
                            f = fr[which]
                            f = f.reshape((C,) + (1,) * (len(shape) + 1
                                                         - f.ndim)
                                          + f.shape[1:])
                        plan = self._backends[which].from_args(
                            plans[which]).read_plan(sr, (C,) + shape, f)
                    took.append(False if plan is None else plan[0])
                    reads.append(-1 if plan is None else plan[1])
                return (arcs, jnp.array(took, bool).reshape(-1),
                        jnp.array(reads, jnp.int32).reshape(-1))

            def frontier_arcs(slots, vmask, degrees, plans, admission=(),
                              carrier=None):
                m = (self._round_machinery(carrier["graph"], carrier["index"])
                     if admission else None)
                return counts(slots, vmask, degrees, plans, admission, m)

            self._frontier_counts = counts
            self._frontier_views = names
            # admissions of constant-closure editions go through
            # ``_Edition.count_admit``, which closes over the edition's
            # graph and index as its rounds do
            self._frontier_arcs = self._jit(frontier_arcs)

        # Graph versioning (DESIGN.md §12): _slot_version pins each slot to
        # the version it was admitted under; _resume_refs pins editions
        # referenced only by suspended (off-device) payloads.
        self._editions: dict[int, _Edition] = {}
        self._resume_refs: dict[int, int] = {}
        self._slot_version = np.full((C,), int(g.version), dtype=np.int64)
        self._slots_placed = False
        ed = self._make_edition(
            g, self.index,
            {k: gb[0] for k, gb in self.aux_graphs.items()},
            self._backends,
        )
        self._editions[ed.version] = ed
        self._current_version = ed.version

    def _round_machinery(self, g, index) -> dict:
        """The round-path closures, parametrized by the graph/index they
        close over.

        Constant-closure mode traces them with concrete arrays — one
        compile per edition, graph data folded in.  Argument-carried mode
        calls this INSIDE the shared round's trace with the traced
        carrier's graph/index (same shapes every edition), so ONE compile
        serves every version (DESIGN.md §12 addendum).  Every entry point
        takes a per-version ``vmask``: the dispatch advances only the
        slots pinned to this version, leaving other versions'
        live/done/step rows untouched — ``slot_round`` dispatches once per
        version present in the slot table, so mixed-version rounds still
        pay one device->host sync total.
        """
        prog = self.program

        def admit(slots, idx, query):
            st = prog.init(g, query, index)
            slots = dict(slots)
            slots["state"] = jax.tree.map(
                lambda tab, v: tab.at[idx].set(v), slots["state"], st
            )
            slots["query"] = jax.tree.map(
                lambda tab, v: tab.at[idx].set(v), slots["query"], query
            )
            slots["step"] = slots["step"].at[idx].set(0)
            slots["live"] = slots["live"].at[idx].set(True)
            slots["done"] = slots["done"].at[idx].set(False)
            return slots

        def admit_batch(slots, admit_mask, queries):
            """Fill all newly-assigned slots in ONE dispatch (DESIGN.md §3).

            admit_mask : (C,) bool — True where a query is being admitted.
            queries    : (C, ...) query pytree *aligned by slot* (row s is
                         the query admitted into slot s; non-admitted rows
                         hold the old slot query).  Host-side alignment
                         turns admission into a branch-free masked select —
                         no XLA scatter, which is slow on CPU.
            """
            st = jax.vmap(lambda q: prog.init(g, q, index))(queries)
            slots = dict(slots)
            slots["state"] = tree_where(admit_mask, st, slots["state"])
            slots["query"] = tree_where(admit_mask, queries, slots["query"])
            slots["step"] = jnp.where(admit_mask, 0, slots["step"])
            slots["live"] = slots["live"] | admit_mask
            slots["done"] = slots["done"] & ~admit_mask
            return slots

        def admit_batch_resume(slots, admit_mask, queries, resume_mask,
                               rstate, rsteps):
            """Batched admission with suspended queries resuming alongside
            fresh ones: fresh rows (admit_mask) run ``init``; resume rows
            (resume_mask) restore the host-collected state and superstep
            counter instead — suspension must be observationally
            equivalent to never having been admitted, modulo the steps
            already charged (DESIGN.md §9)."""
            st = jax.vmap(lambda q: prog.init(g, q, index))(queries)
            st = tree_where(resume_mask, rstate, st)
            both = admit_mask | resume_mask
            slots = dict(slots)
            slots["state"] = tree_where(both, st, slots["state"])
            slots["query"] = tree_where(both, queries, slots["query"])
            slots["step"] = jnp.where(
                resume_mask, rsteps, jnp.where(admit_mask, 0, slots["step"])
            )
            slots["live"] = slots["live"] | both
            slots["done"] = slots["done"] & ~both
            return slots

        def make_super_round(prop):
            """ONE superstep for this version's live slots, with ``prop``
            as the propagation entry point — the edition's own backends
            outside a mesh, or the per-device local closures inside the
            SPMD round.  ``done`` ACCUMULATES (a slot finishing at
            superstep j of a multi-step round must still read True at the
            round's single readback); callers zero this version's flags at
            round entry via ``zero_done``."""

            def one(state, query, step, adv):
                def prop_adv(sr, x, frontier=None, which="default"):
                    # a slot that does not advance reads no arc: its output
                    # is discarded, and a stale frontier would sway the
                    # round's one frontier-or-dense decision
                    if frontier is not None:
                        frontier = frontier & adv
                    return prop(sr, x, frontier, which)

                ctx = StepCtx(
                    graph=g,
                    query=query,
                    step=step + 1,  # Pregel supersteps are 1-based
                    propagate=prop_adv,
                    index=index,
                )
                new_state, done = prog.superstep(state, ctx)
                state = tree_where(adv, new_state, state)
                return state, done & adv

            def super_round(slots, vmask):
                adv = slots["live"] & vmask
                state, done = jax.vmap(one)(
                    slots["state"], slots["query"], slots["step"], adv
                )
                return dict(
                    state=state,
                    query=slots["query"],
                    step=slots["step"] + adv.astype(jnp.int32),
                    live=slots["live"] & ~done,
                    done=slots["done"] | done,
                )

            return super_round

        def zero_done(slots, vmask):
            # clear only THIS version's done flags at round entry: other
            # versions' flags must survive to the round's single readback
            return dict(slots, done=slots["done"] & ~vmask)

        spr = self.steps_per_round

        def make_round_k(prop):
            """Up to ``spr`` supersteps in ONE dispatch, early-exiting as
            soon as every slot of this version has voted done — barrier
            count drops ~spr× while per-slot ``step`` counters stay
            exact."""
            super_round = make_super_round(prop)

            def round_k(slots, vmask):
                slots = zero_done(slots, vmask)
                if spr == 1:
                    return super_round(slots, vmask)

                def cond(carry):
                    s, it = carry
                    return (it < spr) & (s["live"] & vmask).any()

                def body(carry):
                    s, it = carry
                    return super_round(s, vmask), it + 1

                slots, _ = jax.lax.while_loop(
                    cond, body, (slots, jnp.asarray(0, jnp.int32))
                )
                return slots

            return round_k

        return dict(
            admit=admit, admit_batch=admit_batch,
            admit_batch_resume=admit_batch_resume,
            make_super_round=make_super_round, zero_done=zero_done,
            make_round_k=make_round_k,
        )

    def _make_edition(self, graph, index, aux, backends) -> _Edition:
        """Build one graph version's round entry points.

        All closures capture the LOCAL ``graph``/``index``/``backends``
        (never ``self.graph``) so an installed edition keeps answering on
        its own snapshot while ``self.*`` moves on to the next version.
        Constant-closure mode compiles fresh jits per edition;
        argument-carried mode binds the shared jitted entries and packs
        this version's arrays into ``ed.round_args`` instead.
        """
        g, C = graph, self.capacity
        ed = _Edition(version=int(graph.version), graph=graph, index=index,
                      aux=dict(aux), backends=dict(backends))

        propagate = view_propagate(backends)

        m = self._round_machinery(g, index)

        # Discovery pass (per edition): abstractly trace ONE round with a
        # shape-preserving recording propagate.  This (a) learns every
        # (view, semiring) the program propagates so tile backends can
        # build their per-semiring tables eagerly, OUTSIDE any jit trace
        # (an in-trace build would cache that trace's constants), and (b)
        # records the per-superstep propagate payloads the SPMD collective
        # model reports.  A refreshed tile backend already carries its
        # updated tables, so the warm call is a lookup, not a rebuild.
        self._prop_trace = []

        def recording(sr, x, frontier=None, which="default"):
            self._prop_trace.append(
                (which, sr, tuple(x.shape), np.dtype(x.dtype),
                 frontier is not None)
            )
            return x

        jax.eval_shape(
            m["make_round_k"](recording), self._slots, jnp.zeros((C,), bool)
        )
        for which, sr, *_ in self._prop_trace:
            warm = getattr(backends[which], "table_for", None)
            if warm is not None:
                warm(sr)
        # what the tracing counters read: each view's out-degrees, each
        # view's plan arrays where its plan can say what a call reads, and
        # the program's propagate calls (the same for every edition)
        self._prop_calls = [(w, sr, shape, has_f)
                            for w, sr, shape, _, has_f in self._prop_trace]
        if self._frontier_views is not None:
            views = {"default": graph, **ed.aux}
            ed.degrees = {v: views[v].out_deg for v in self._frontier_views}
            calls = [sum(w == v for w, *_ in self._prop_trace)
                     for v in self._frontier_views]
            self._frontier_weights = np.asarray(calls, np.int64)
            ed.plans = {
                v: be.as_args() for v, be in backends.items()
                if type(be).read_plan is not ops.PropagateBackend.read_plan
            }

        if self.legacy:
            ed.admit = self._jit(m["admit"], version=ed.version)
            legacy_round = m["make_super_round"](propagate)
            zero_done = m["zero_done"]

            def super_round(s, vmask):
                return legacy_round(zero_done(s, vmask), vmask)

            ed.super_round = self._jit(super_round, version=ed.version)
        elif self.mesh is not None:
            if self._arg_carried:
                self._bind_spmd_arg_carried(ed)
            else:
                self._build_spmd_edition(
                    ed, m["make_round_k"], m["admit_batch"],
                    m["admit_batch_resume"],
                )
        elif self._arg_carried:
            self._bind_arg_carried(ed)
        else:
            round_k = m["make_round_k"](propagate)
            admit_batch = m["admit_batch"]
            admit_batch_resume = m["admit_batch_resume"]
            # Donating the slot table lets XLA alias every (C, V, ...) slab
            # output to its input: the hot loop mutates in place, no copy.
            dn = (0,) if self.donate else ()
            ed.round = self._jit(round_k, version=ed.version,
                                 donate_argnums=dn)

            def round_admit(slots, admit_mask, queries, vmask):
                return round_k(admit_batch(slots, admit_mask, queries), vmask)

            # separate entry so rounds with no resuming query keep the
            # no-resume hot path (and its compiled trace) untouched
            def round_resume(slots, am, q, rm, rst, rsp, vmask):
                return round_k(
                    admit_batch_resume(slots, am, q, rm, rst, rsp), vmask
                )

            ed.round_admit = self._jit(round_admit, version=ed.version,
                                       donate_argnums=dn)
            ed.round_resume = self._jit(round_resume, version=ed.version,
                                        donate_argnums=dn)
        return ed

    # ------------------------------------------- argument-carried editions
    def _make_carrier(self, ed: _Edition) -> dict:
        """This edition's arrays as the traced ``carrier`` argument.

        Per view: the graph is capacity-padded (``Graph.with_capacity``) to
        the engine's per-view cap and lineage-stripped (``Graph.carrier``);
        backend arrays come from ``as_args`` (tile tables slot-padded to
        the per-view slot cap).  Caps only grow — an overflowing edition
        raises its view's cap (new shapes, one real recompile) and every
        later in-capacity edition reuses that compile.
        """
        graphs = {"default": ed.graph, **ed.aux}
        views: dict = {}
        g_default = None
        for name, be in ed.backends.items():
            g_v = graphs[name]
            gcar = g_v.with_capacity(max_e=self._edge_cap(name, g_v)).carrier()
            scap = None
            if isinstance(be, ops._TileBackend):
                need = max(
                    (bs.max_bpr for bs in be.tables.values()), default=1
                )
                scap = self._slot_caps.get(name)
                if scap is None or need > scap:
                    scap = need + 2
                    self._slot_caps[name] = scap
            views[name] = be.as_args(gcar, slot_cap=scap)
            if name == "default":
                g_default = gcar
        return {"graph": g_default, "index": ed.index, "views": views}

    def _edge_cap(self, name: str, g: Graph) -> int:
        """View ``name``'s padded edge capacity for graph ``g``.  Caps only
        grow: the first call fixes one, and a later graph that outgrows it
        raises it (new shapes, one real recompile)."""
        from repro.core.graph import grow_capacity

        cap = self._view_caps.get(name)
        if cap is None:
            # an explicit edge_capacity= is taken at face value (tests and
            # benches use it to provoke overflow); otherwise grow with
            # headroom so typical delta streams never overflow
            cap = (self._edge_capacity if self._edge_capacity is not None
                   else grow_capacity(g.num_edges))
            cap = max(cap, g.num_edges)
        elif g.num_edges > cap:
            cap = grow_capacity(g.num_edges)
        self._view_caps[name] = cap
        return cap

    def _ensure_arg_carried_entries(self) -> None:
        """Build the ONE shared set of jitted round entries (single-device
        arg-carried mode).  Unlike constant-closure editions these take the
        carrier as a traced argument: a later edition with the same array
        shapes (in-capacity delta) dispatches through the same compiled
        executable — zero recompiles, asserted by the mutation bench."""
        if self._ac_entries is not None:
            return
        # plan-parameter templates (gate, gather_edges, block, ...): taken
        # from the FIRST edition's backends and never replaced — from_args
        # rebinds them to each carrier's arrays inside the trace.
        self._ac_protos = dict(self._backends)

        def machinery_of(carrier):
            protos = self._ac_protos
            bes = {
                k: protos[k].from_args(v)
                for k, v in carrier["views"].items()
            }
            return self._round_machinery(
                carrier["graph"], carrier["index"]
            ), view_propagate(bes)

        def round_ac(slots, vmask, carrier):
            m, p = machinery_of(carrier)
            return m["make_round_k"](p)(slots, vmask)

        def round_admit_ac(slots, admit_mask, queries, vmask, carrier):
            m, p = machinery_of(carrier)
            return m["make_round_k"](p)(
                m["admit_batch"](slots, admit_mask, queries), vmask
            )

        def round_resume_ac(slots, am, q, rm, rst, rsp, vmask, carrier):
            m, p = machinery_of(carrier)
            return m["make_round_k"](p)(
                m["admit_batch_resume"](slots, am, q, rm, rst, rsp), vmask
            )

        dn = (0,) if self.donate else ()
        self._ac_entries = (
            self._jit(round_ac, donate_argnums=dn),
            self._jit(round_admit_ac, donate_argnums=dn),
            self._jit(round_resume_ac, donate_argnums=dn),
        )

    def _bind_arg_carried(self, ed: _Edition) -> None:
        self._ensure_arg_carried_entries()
        ed.round, ed.round_admit, ed.round_resume = self._ac_entries
        ed.round_args = (self._make_carrier(ed),)

    # ---------------------------------------------------------------- SPMD
    def _build_spmd_edition(self, ed: _Edition, make_round_k, admit_batch,
                            admit_batch_resume):
        """Compile the fused round as ONE shard_map over the mesh axis.

        V-sharded leaves (trailing dim == |V|) are all-gathered at round
        entry, the round body runs on full values (so vertex programs'
        global reductions and indexed lookups stay correct unchanged) with
        each device combining only its edge shard — one collective per
        propagate call — and each device's V-shard is sliced back out for
        the round's outputs.  Compute on the (C, V) slabs is replicated;
        the O(E) edge work, the term that dominates on big graphs, splits
        n_parts ways (DESIGN.md §6).
        """
        from jax.sharding import NamedSharding, PartitionSpec as P

        g, C = ed.graph, self.capacity
        mesh, axis, nparts = self.mesh, self._mesh_axis, self._n_parts

        def is_vq(leaf):
            return jnp.ndim(leaf) >= 2 and jnp.shape(leaf)[-1] == g.n

        def spec_of(leaf):
            nd = jnp.ndim(leaf)
            if is_vq(leaf):
                return P(*([None] * (nd - 1) + [axis]))
            return P(*([None] * nd))

        is_p = lambda x: isinstance(x, P)
        shard_tree = jax.tree.map(is_vq, self._slots)
        slot_specs = jax.tree.map(spec_of, self._slots)
        query_specs = jax.tree.map(
            lambda leaf: P(*([None] * jnp.ndim(leaf))), self._slots["query"]
        )
        edge_parts = {k: be.parts for k, be in ed.backends.items()}
        edge_specs = {
            k: jax.tree.map(lambda _: P(axis, None), v)
            for k, v in edge_parts.items()
        }

        def gather(slots):
            def f(x, s):
                if not s:
                    return x
                return jax.lax.all_gather(x, axis, axis=jnp.ndim(x) - 1, tiled=True)

            return jax.tree.map(f, slots, shard_tree)

        def scatter(slots):
            i = jax.lax.axis_index(axis)

            def f(x, s):
                if not s:
                    return x
                blk = x.shape[-1] // nparts
                return jax.lax.dynamic_slice_in_dim(x, i * blk, blk, jnp.ndim(x) - 1)

            return jax.tree.map(f, slots, shard_tree)

        def local_prop(parts):
            return view_propagate(ed.backends, {
                k: ed.backends[k].make_local(parts[k]) for k in parts})

        def body_round(slots, vmask, parts):
            rk = make_round_k(local_prop(parts))
            return scatter(rk(gather(slots), vmask))

        def body_admit(slots, admit_mask, queries, vmask, parts):
            rk = make_round_k(local_prop(parts))
            return scatter(
                rk(admit_batch(gather(slots), admit_mask, queries), vmask)
            )

        def body_resume(slots, admit_mask, queries, resume_mask, rstate,
                        rsteps, vmask, parts):
            # resume state arrives replicated (host-collected full rows);
            # admission happens on the gathered full-V table, and the exit
            # scatter re-shards the restored V-partitioned leaves.
            rk = make_round_k(local_prop(parts))
            return scatter(rk(admit_batch_resume(
                gather(slots), admit_mask, queries, resume_mask, rstate,
                rsteps), vmask))

        state_specs = jax.tree.map(
            lambda leaf: P(*([None] * jnp.ndim(leaf))), self._slots["state"]
        )
        dn = (0,) if self.donate else ()
        ed.round = self._jit(
            jax.shard_map(
                body_round, mesh=mesh, check_vma=False,
                in_specs=(slot_specs, P(None), edge_specs),
                out_specs=slot_specs,
            ),
            version=ed.version, donate_argnums=dn,
        )
        ed.round_admit = self._jit(
            jax.shard_map(
                body_admit, mesh=mesh, check_vma=False,
                in_specs=(slot_specs, P(None), query_specs, P(None),
                          edge_specs),
                out_specs=slot_specs,
            ),
            version=ed.version, donate_argnums=dn,
        )
        ed.round_resume = self._jit(
            jax.shard_map(
                body_resume, mesh=mesh, check_vma=False,
                in_specs=(slot_specs, P(None), query_specs, P(None),
                          state_specs, P(None), P(None), edge_specs),
                out_specs=slot_specs,
            ),
            version=ed.version, donate_argnums=dn,
        )

        # Place the slot table (once — editions share it) and this
        # edition's edge partitions in the layout the round expects, so no
        # per-call resharding (and donation can alias).
        to_shardings = lambda specs: jax.tree.map(
            lambda s: NamedSharding(mesh, s), specs, is_leaf=is_p
        )
        if not self._slots_placed:
            self._slots = jax.device_put(
                self._slots, to_shardings(slot_specs)
            )
            self._slots_placed = True
        ed.round_args = (self._place_edge_parts(ed, to_shardings(edge_specs)),)

        # Collective payload model from the discovery pass (_build): one
        # entry per propagate call per superstep, each a (C, ..., V) slab.
        prop_bytes = sum(
            int(np.prod(shape)) * dt.itemsize
            for _, _, shape, dt, _ in self._prop_trace
        )
        state_bytes = sum(
            int(np.prod(np.shape(leaf))) * np.dtype(leaf.dtype).itemsize
            for leaf, s in zip(
                jax.tree.leaves(self._slots), jax.tree.leaves(shard_tree)
            )
            if s
        )
        self._collective_model = dict(
            propagate_calls_per_superstep=len(self._prop_trace),
            propagate_payload_bytes_per_superstep=prop_bytes * C,
            state_gather_payload_bytes=state_bytes,
        )

    # ------------------------------------------ SPMD + argument-carried
    def _make_spmd_carrier(self, ed: _Edition) -> dict:
        """The SPMD round's replicated carrier: capacity-padded default
        graph (feeds ``prog.init`` / ``StepCtx``) plus the index.  Edge
        work never reads it — that rides in the mesh-sharded partition
        arrays, passed alongside so they keep their own shardings."""
        cap = self._edge_cap("default", ed.graph)
        return {
            "graph": ed.graph.with_capacity(max_e=cap).carrier(),
            "index": ed.index,
        }

    def _ensure_spmd_ac_entries(self, ed0: _Edition) -> None:
        """Shared shard_map round entries taking ``(..., parts, carrier)``
        as traced arguments — the SPMD analogue of
        ``_ensure_arg_carried_entries``.  Partition arrays shard along the
        mesh axis (``ShardedGraph.apply_delta`` keeps Emax, so in-capacity
        deltas keep their shapes); the carrier replicates.  Built once,
        from the FIRST edition; every later edition re-binds arrays only.
        """
        if self._spmd_ac is not None:
            return
        from jax.sharding import NamedSharding, PartitionSpec as P

        g, C = ed0.graph, self.capacity
        mesh, axis, nparts = self.mesh, self._mesh_axis, self._n_parts
        # statics-only templates: make_local closes over block/n/partition,
        # never over a specific edition's arrays
        self._ac_protos = dict(ed0.backends)
        protos = self._ac_protos

        def is_vq(leaf):
            return jnp.ndim(leaf) >= 2 and jnp.shape(leaf)[-1] == g.n

        def spec_of(leaf):
            nd = jnp.ndim(leaf)
            if is_vq(leaf):
                return P(*([None] * (nd - 1) + [axis]))
            return P(*([None] * nd))

        is_p = lambda x: isinstance(x, P)
        shard_tree = jax.tree.map(is_vq, self._slots)
        slot_specs = jax.tree.map(spec_of, self._slots)
        query_specs = jax.tree.map(
            lambda leaf: P(*([None] * jnp.ndim(leaf))), self._slots["query"]
        )
        state_specs = jax.tree.map(
            lambda leaf: P(*([None] * jnp.ndim(leaf))), self._slots["state"]
        )
        edge_parts0 = {k: be.parts for k, be in ed0.backends.items()}
        edge_specs = {
            k: jax.tree.map(lambda _: P(axis, None), v)
            for k, v in edge_parts0.items()
        }
        carrier0 = self._make_spmd_carrier(ed0)
        carrier_specs = jax.tree.map(
            lambda leaf: P(*([None] * jnp.ndim(leaf))), carrier0
        )

        def gather(slots):
            def f(x, s):
                if not s:
                    return x
                return jax.lax.all_gather(
                    x, axis, axis=jnp.ndim(x) - 1, tiled=True
                )

            return jax.tree.map(f, slots, shard_tree)

        def scatter(slots):
            i = jax.lax.axis_index(axis)

            def f(x, s):
                if not s:
                    return x
                blk = x.shape[-1] // nparts
                return jax.lax.dynamic_slice_in_dim(
                    x, i * blk, blk, jnp.ndim(x) - 1
                )

            return jax.tree.map(f, slots, shard_tree)

        def local_prop(parts):
            return view_propagate(protos, {
                k: protos[k].make_local(parts[k]) for k in parts})

        def body_round(slots, vmask, parts, carrier):
            m = self._round_machinery(carrier["graph"], carrier["index"])
            rk = m["make_round_k"](local_prop(parts))
            return scatter(rk(gather(slots), vmask))

        def body_admit(slots, admit_mask, queries, vmask, parts, carrier):
            m = self._round_machinery(carrier["graph"], carrier["index"])
            rk = m["make_round_k"](local_prop(parts))
            return scatter(rk(
                m["admit_batch"](gather(slots), admit_mask, queries), vmask
            ))

        def body_resume(slots, admit_mask, queries, resume_mask, rstate,
                        rsteps, vmask, parts, carrier):
            m = self._round_machinery(carrier["graph"], carrier["index"])
            rk = m["make_round_k"](local_prop(parts))
            return scatter(rk(m["admit_batch_resume"](
                gather(slots), admit_mask, queries, resume_mask, rstate,
                rsteps), vmask))

        dn = (0,) if self.donate else ()
        entries = (
            self._jit(
                jax.shard_map(
                    body_round, mesh=mesh, check_vma=False,
                    in_specs=(slot_specs, P(None), edge_specs,
                              carrier_specs),
                    out_specs=slot_specs,
                ),
                donate_argnums=dn,
            ),
            self._jit(
                jax.shard_map(
                    body_admit, mesh=mesh, check_vma=False,
                    in_specs=(slot_specs, P(None), query_specs, P(None),
                              edge_specs, carrier_specs),
                    out_specs=slot_specs,
                ),
                donate_argnums=dn,
            ),
            self._jit(
                jax.shard_map(
                    body_resume, mesh=mesh, check_vma=False,
                    in_specs=(slot_specs, P(None), query_specs, P(None),
                              state_specs, P(None), P(None), edge_specs,
                              carrier_specs),
                    out_specs=slot_specs,
                ),
                donate_argnums=dn,
            ),
        )
        to_shardings = lambda specs: jax.tree.map(
            lambda s: NamedSharding(mesh, s), specs, is_leaf=is_p
        )
        self._spmd_ac = dict(
            entries=entries,
            slot_shardings=to_shardings(slot_specs),
            edge_shardings=to_shardings(edge_specs),
            carrier_shardings=to_shardings(carrier_specs),
        )

        # same collective model as the constant-closure SPMD build
        prop_bytes = sum(
            int(np.prod(shape)) * dt.itemsize
            for _, _, shape, dt, _ in self._prop_trace
        )
        state_bytes = sum(
            int(np.prod(np.shape(leaf))) * np.dtype(leaf.dtype).itemsize
            for leaf, s in zip(
                jax.tree.leaves(self._slots), jax.tree.leaves(shard_tree)
            )
            if s
        )
        self._collective_model = dict(
            propagate_calls_per_superstep=len(self._prop_trace),
            propagate_payload_bytes_per_superstep=prop_bytes * C,
            state_gather_payload_bytes=state_bytes,
        )

    def _bind_spmd_arg_carried(self, ed: _Edition) -> None:
        self._ensure_spmd_ac_entries(ed)
        ac = self._spmd_ac
        ed.round, ed.round_admit, ed.round_resume = ac["entries"]
        if not self._slots_placed:
            self._slots = jax.device_put(self._slots, ac["slot_shardings"])
            self._slots_placed = True
        # pre-place this edition's arrays in the round's layout so no
        # per-call resharding (and so jit's sharding cache key is stable)
        edge_parts = self._place_edge_parts(ed, ac["edge_shardings"])
        carrier = jax.device_put(
            self._make_spmd_carrier(ed), ac["carrier_shardings"]
        )
        ed.round_args = (edge_parts, carrier)

    def _place_edge_parts(self, ed: _Edition, shardings) -> dict:
        """Put ``ed``'s edge partitions on the mesh and hand the placed
        arrays back to its backends: the unsharded copy built on the
        default device is then freed, not kept next to its shards."""
        parts = jax.device_put(
            {k: be.parts for k, be in ed.backends.items()}, shardings
        )
        for k, be in ed.backends.items():
            be.sg.srcp, be.sg.dstp, be.sg.wp, be.sg.valid = parts[k]
        self._edge_parts = parts  # current edition's, for introspection
        return parts

    def collective_bytes_per_round(self) -> Optional[dict]:
        """Modeled per-device wire bytes for one SPMD super-round
        (DESIGN.md §6); None outside mesh mode.

        dst partition all-gathers each propagate's combined (C, V) payload
        (ring wire cost ≈ payload · (w-1)/w per device); src all-reduces
        the dense partial (≈ 2× that for a ring).  Round entry additionally
        all-gathers the V-sharded slot leaves.
        """
        if self._collective_model is None:
            return None
        m = self._collective_model
        w = self._n_parts
        f = (w - 1) / w if w > 1 else 0.0
        prop_factor = f if self.partition == "dst" else 2.0 * f
        per_step = m["propagate_payload_bytes_per_superstep"] * prop_factor
        state = m["state_gather_payload_bytes"] * f
        return dict(
            n_parts=w,
            partition=self.partition,
            propagate_calls_per_superstep=m["propagate_calls_per_superstep"],
            state_gather_bytes=state,
            propagate_bytes_per_superstep=per_step,
            round_total_bytes=state + self.steps_per_round * per_step,
        )

    # ------------------------------------------------- background warmup
    def _spawn_warmup(self, ed: _Edition) -> None:
        """Compile a fresh constant-closure edition's round entries on a
        daemon thread while the PREVIOUS edition keeps serving — so
        ``apply_delta`` returns in splice time, not compile time
        (DESIGN.md §12 addendum).  No swap step is needed: mixed-version
        dispatch already routes each slot through its pinned edition, so
        the new version's first real dispatch simply finds the jit cache
        hot.  Races are benign — if a dispatch beats the warm thread, jax
        compiles under its own lock and one of the two calls hits cache.
        """
        self.stats.warmups += 1
        t = threading.Thread(
            target=self._warm_edition, args=(ed,),
            name=f"edition-warmup-v{ed.version}", daemon=True,
        )
        self._warm_threads.append(t)
        t.start()

    def _warm_edition(self, ed: _Edition) -> None:
        """CALL each entry with all-False masks (advancing nothing): only
        a real call installs the executable for the real argument shapes
        (an ahead-of-time ``.lower().compile()`` would not populate the
        jit dispatch cache).  Proto-filled rows give the exact
        query/state dtypes the serving path stacks."""
        C = self.capacity
        zmask = np.zeros((C,), bool)
        queries = jax.tree.map(
            lambda x: np.stack([x] * C), self._proto_q_np
        )
        rstate = jax.tree.map(
            lambda x: np.stack([x] * C), self._proto_state_np
        )
        rsteps = np.zeros((C,), np.int32)

        def slots():
            # donation would consume the live table's buffers — warm on
            # throwaway copies when the round donates its first argument
            if self.donate:
                return jax.tree.map(jnp.array, self._slots)
            return self._slots

        try:
            ed.round(slots(), zmask, *ed.round_args)
            ed.round_admit(slots(), zmask, queries, zmask, *ed.round_args)
            ed.round_resume(slots(), zmask, queries, zmask, rstate, rsteps,
                            zmask, *ed.round_args)
        except Exception:  # pragma: no cover - lazy compile is the fallback
            pass

    def wait_warmup(self, timeout: Optional[float] = None) -> bool:
        """Join outstanding warmup threads (tests/benchmarks sync point);
        True when none remain running."""
        for t in list(self._warm_threads):
            t.join(timeout)
        self._warm_threads = [t for t in self._warm_threads if t.is_alive()]
        return not self._warm_threads

    # ------------------------------------------- SlotProgram (device side)
    def slot_round(self, admitted: dict[int, Any]) -> RoundOutcome:
        """One super-round for the runtime: advance every live slot, fusing
        the batched admission of ``admitted`` ({slot: staged query}) into
        the same dispatch.  The done/step readback below is THE barrier —
        one device->host sync per super-round.

        Versioning (DESIGN.md §12): fresh admissions pin their slot to the
        CURRENT graph version; resume admissions re-pin to the version in
        their suspend payload.  Slots of each version advance through that
        version's edition (one dispatch per version present — normally
        exactly one), and the single readback at the end covers them all.

        Legacy mode preserves the pre-overhaul structure for the A/B
        baseline: a liveness readback before the round (the extra sync the
        overhaul removed) and one admission dispatch per query.

        Under the JAX profiler (``core/tracing.py``) the dispatch and the
        readback are the spans ``quegel.dispatch`` and ``quegel.sync``, and
        the frontier counters are dispatched before each version's round
        and read back in the same sync.
        """
        rid = len(self.stats.round_times)
        if not self._frontier_warm:
            self._warm_frontier_counter()
        counts = ([] if tracing.enabled() and self._frontier_views is not None
                  else None)
        with tracing.span(tracing.DISPATCH, round=rid):
            self._dispatch_round(admitted, counts)
        with tracing.span(tracing.SYNC, round=rid):
            done, steps, counts = jax.device_get(
                (self._slots["done"], self._slots["step"], counts))
        if counts:
            self._record_counts(counts, rid)
        return RoundOutcome(done=done, steps=steps)

    def _count_frontiers(self, ed: _Edition, vmask, admission=()):
        """Dispatch the frontier counters for ``ed``'s slots in ``vmask``,
        on the slot table as ``ed``'s round will see it: ``admission`` is
        the round's admission arguments (masks, queries, resume state)."""
        args = (self._slots, vmask, ed.degrees, ed.plans)
        if not admission:
            return self._frontier_arcs(*args)
        if self._arg_carried:
            return self._frontier_arcs(*args, admission, ed.round_args[-1])
        if ed.count_admit is None:
            m = self._round_machinery(ed.graph, ed.index)
            counts = self._frontier_counts

            def frontier_arcs(slots, vmask, degrees, plans, admission):
                return counts(slots, vmask, degrees, plans, admission, m)

            ed.count_admit = self._jit(frontier_arcs, version=ed.version)
        return ed.count_admit(*args, admission)

    def _record_counts(self, counts: list, rid: int) -> None:
        """The round's counters from each dispatched version's readback:
        frontier arcs (each view's once per call on it), calls, calls that
        took the frontier path, and arcs read where every call's plan can
        say."""
        arcs = sum(a.astype(np.int64).sum(0) for a, _, _ in counts)
        took = np.concatenate([t for _, t, _ in counts])
        reads = np.concatenate([r for _, _, r in counts]).astype(np.int64)
        tracing.count(tracing.FRONTIER_ARCS, int(arcs @ self._frontier_weights),
                      round=rid)
        tracing.count(tracing.PROPAGATE_CALLS, len(took), round=rid)
        tracing.count(tracing.FRONTIER_PATH, int(took.sum()), round=rid)
        if len(reads) and (reads >= 0).all():
            tracing.count(tracing.ARCS_READ, int(reads.sum()), round=rid)

    def _warm_frontier_counter(self) -> None:
        """Compile the frontier counters on the first round, profiler on
        or off, with and without admissions, so that a profiler started
        mid-serving compiles nothing."""
        self._frontier_warm = True
        if self._frontier_views is not None:
            C = self.capacity
            ed = self._editions[self._current_version]
            zmask = np.zeros((C,), bool)
            queries = jax.tree.map(lambda x: np.stack([x] * C),
                                   self._proto_q_np)
            self._count_frontiers(ed, zmask)
            if not self.legacy:
                self._count_frontiers(ed, zmask, (zmask, queries))

    def _dispatch_round(self, admitted: dict[int, Any],
                        counts: Optional[list] = None) -> None:
        """``slot_round``'s dispatches; with ``counts`` a list, each
        version's frontier counters are dispatched before its round and
        appended there."""
        C = self.capacity
        cur = self._current_version
        fresh: dict[int, Any] = {}
        resumes: dict[int, tuple] = {}
        for slot, q in admitted.items():
            if isinstance(q, ResumeAdmission):
                payload = q.payload
                if isinstance(payload, dict) and "v" in payload \
                        and "state" in payload:
                    v, state = int(payload["v"]), payload["state"]
                else:  # pre-versioning payload (external caller): current
                    v, state = cur, payload
                if v not in self._editions:
                    raise RuntimeError(
                        f"cannot resume query pinned to graph version {v}: "
                        "edition was pruned (resume payloads must keep "
                        "their version referenced via slot_register_resume)"
                    )
                self._release_resume_ref(v)
                self._slot_version[slot] = v
                resumes[slot] = (q.query, state, q.steps, v)
            else:
                self._slot_version[slot] = cur
                fresh[slot] = q
        # the runtime's host liveness mirror already includes this round's
        # admissions; every live slot belongs to exactly one version group
        live = np.asarray(self.runtime.live)
        versions = sorted(
            {int(self._slot_version[s]) for s in range(C) if live[s]}
        ) or [cur]

        if self.legacy:
            # The pre-overhaul round paid two extra device->host liveness
            # syncs: free-slot discovery before admission, and the
            # any-live check after it.  Keep both so the A/B baseline
            # stays faithful (DESIGN.md §3).
            _ = np.asarray(self._slots["live"])
            for slot, q in fresh.items():
                self._slots = self._editions[cur].admit(self._slots, slot, q)
            for slot, (query, state, steps, v) in resumes.items():
                self._slots = self._legacy_admit_resume(
                    self._slots, slot, query, state,
                    jnp.asarray(steps, jnp.int32),
                )
            _ = np.asarray(self._slots["live"]).any()
            for v in versions:
                self._dispatch_version = v
                vmask = (self._slot_version == v) & live
                if counts is not None:
                    counts.append(self._count_frontiers(self._editions[v],
                                                        vmask))
                self._slots = self._editions[v].super_round(
                    self._slots, vmask
                )
        else:
            for v in versions:
                ed = self._editions[v]
                # shared arg-carried entries charge compiles to the version
                # being dispatched (see _jit)
                self._dispatch_version = v
                vmask = (self._slot_version == v) & live
                vfresh = fresh if v == cur else {}
                vres = {s: r for s, r in resumes.items() if r[3] == v}
                if vfresh or vres:
                    admit_mask = np.zeros((C,), bool)
                    resume_mask = np.zeros((C,), bool)
                    by_slot = [self._proto_q_np] * C
                    by_state = [self._proto_state_np] * C
                    rsteps = np.zeros((C,), np.int32)
                    for slot, q in vfresh.items():
                        admit_mask[slot] = True
                        by_slot[slot] = q
                    for slot, (query, state, steps, _) in vres.items():
                        resume_mask[slot] = True
                        by_slot[slot] = query
                        by_state[slot] = state
                        rsteps[slot] = steps
                    queries = jax.tree.map(lambda *xs: np.stack(xs), *by_slot)
                    if resume_mask.any():
                        rstate = jax.tree.map(
                            lambda *xs: np.stack(xs), *by_state
                        )
                        if counts is not None:
                            counts.append(self._count_frontiers(
                                ed, vmask, (admit_mask, queries, resume_mask,
                                            rstate, rsteps)))
                        self._slots = ed.round_resume(
                            self._slots, admit_mask, queries, resume_mask,
                            rstate, rsteps, vmask, *ed.round_args
                        )
                    else:
                        if counts is not None:
                            counts.append(self._count_frontiers(
                                ed, vmask, (admit_mask, queries)))
                        self._slots = ed.round_admit(
                            self._slots, admit_mask, queries, vmask,
                            *ed.round_args
                        )
                else:
                    if counts is not None:
                        counts.append(self._count_frontiers(ed, vmask))
                    self._slots = ed.round(self._slots, vmask, *ed.round_args)

    def slot_collect(self, slots: list[int]) -> list[Any]:
        """Results for retiring slots: ONE vmapped dispatch extracts every
        slot, rows sliced host-side (results are small Q-data); legacy
        extracts per slot, as the pre-overhaul engine did."""
        with tracing.span(tracing.COLLECT,
                          round=len(self.stats.round_times)):
            if self.legacy:
                return [
                    jax.tree.map(np.asarray,
                                 self._extract(self._slots, int(s)))
                    for s in slots
                ]
            all_res = jax.tree.map(np.asarray, self._extract_all(self._slots))
        return [
            jax.tree.map(lambda tab: tab[int(s)], all_res) for s in slots
        ]

    def slot_evict(self, slots: list[int]) -> None:
        """Budget-exhausted queries (TIMEOUT): clear device liveness so the
        slot stops advancing and is free for re-admission.  Off the hot
        path — eviction is the paper's console kill, not a per-round op."""
        live = self._slots["live"].at[jnp.asarray(slots, jnp.int32)].set(False)
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            live = jax.device_put(live, NamedSharding(self.mesh, P(None)))
        self._slots = dict(self._slots, live=live)

    def slot_suspend(self, slots: list[int]) -> list[Any]:
        """Preemption (DESIGN.md §9): pull each victim's full VQ/Q state
        row to host and clear its device liveness, freeing the slot.  Off
        the hot path — one host readback per suspension, like the paper's
        console suspend.  Works identically for fused, legacy and SPMD
        tables (np.asarray gathers V-sharded leaves to one host copy; the
        resume round's exit scatter re-shards them).

        The payload carries the slot's pinned graph version (DESIGN.md
        §12) so resumption — possibly after mutations, possibly after a
        crash — re-enters on the SAME edition the state was computed on;
        the version's resume refcount keeps that edition from pruning
        while the payload is off-device."""
        idx = [int(s) for s in slots]
        state_np = jax.tree.map(np.asarray, self._slots["state"])
        payloads = []
        for s in idx:
            v = int(self._slot_version[s])
            self._resume_refs[v] = self._resume_refs.get(v, 0) + 1
            payloads.append({
                "v": v,
                "state": jax.tree.map(lambda tab: tab[s].copy(), state_np),
            })
        self.slot_evict(idx)
        return payloads

    def slot_register_resume(self, payload) -> None:
        """A journal-replayed suspend payload re-entered the queue
        (``SlotRuntime.restore_pending``): re-pin its graph edition so
        pruning cannot drop it before the resume round (DESIGN.md §12)."""
        if isinstance(payload, dict) and "v" in payload:
            v = int(payload["v"])
            if v not in self._editions:
                raise RuntimeError(
                    f"resume payload references graph version {v} but no "
                    "such edition exists — replay the journal's mutation "
                    "records (apply_delta_record) before restore_pending"
                )
            self._resume_refs[v] = self._resume_refs.get(v, 0) + 1

    def _release_resume_ref(self, v: int) -> None:
        c = self._resume_refs.get(v, 0)
        if c <= 1:
            self._resume_refs.pop(v, None)
        else:
            self._resume_refs[v] = c - 1

    # ------------------------------------------------- version-keyed cache
    def cache_key(self, query) -> str:
        """Submit-time cache key: prefixed by the CURRENT graph version's
        content hash, so a lookup can only hit results computed on the
        graph the submitter would query (DESIGN.md §12)."""
        return self.graph.content_hash() + ":" + default_cache_key(query)

    def cache_key_for_slot(self, query, slot: int) -> str:
        """Retirement-time cache key: prefixed by the content hash of the
        edition the slot was PINNED to — which may be older than current
        if the query ran across a mutation.  Editions are pruned only
        between rounds (``apply_delta``), never mid-retirement, so the
        pinned edition is still installed here."""
        ed = self._editions.get(int(self._slot_version[int(slot)]))
        g = self.graph if ed is None else ed.graph
        return g.content_hash() + ":" + default_cache_key(query)

    # ------------------------------------------------------ graph mutation
    def apply_delta(self, adds=None, dels=None, *, w=None,
                    aux_deltas: Any = "reverse", index_fn=None,
                    prune: bool = True, _from_journal: bool = False) -> dict:
        """Mutate the graph between rounds (DESIGN.md §12): apply a batched
        edge delta, bump the version, and install a new edition — views
        merged incrementally (``Graph.apply_delta`` + per-backend
        ``refresh``), index maintained via ``index_fn``, result cache
        invalidated down to the new version's entries.  In-flight queries
        keep answering on the version they were admitted under.

        adds/dels : ``(k, 2)`` (src, dst) pair arrays (or (src, dst)
                    tuples); ``adds`` may instead be a prevalidated
                    ``EdgeDelta``.  ``w`` gives per-added-edge weights.
        aux_deltas: how auxiliary views follow the default view's delta —
                    ``"reverse"`` (default; every aux view is the
                    edge-reversed graph, as for every in-repo engine) maps
                    the delta through ``EdgeDelta.reversed()``; or a dict
                    {view: EdgeDelta | (adds, dels) | None} (None = view
                    unaffected: graph, backend and tables are reused).
        index_fn  : overrides the constructor's ``index_fn`` for this call.
        prune     : drop editions no live slot, suspended payload or the
                    current version references (keep False while replaying
                    a journal, where later records may resume older
                    versions).

        Returns {version, parent_hash, content_hash, delta_size,
        cache_invalidated, editions, index} — ``index`` is the maintainer's
        info dict (e.g. incremental-vs-rebuild mode), None when indexless.
        """
        if self.propagate_override:
            raise ValueError(
                "apply_delta cannot refresh propagate_override callables: "
                "override closures capture graph arrays the engine cannot "
                "see; rebuild the engine instead"
            )
        cur = self._editions[self._current_version]
        if isinstance(adds, EdgeDelta):
            if dels is not None or w is not None:
                raise ValueError(
                    "pass either a prevalidated EdgeDelta or adds/dels/w "
                    "arrays, not both"
                )
            delta = adds
        else:
            delta = cur.graph.make_delta(adds, dels, w=w)
        fn = index_fn if index_fn is not None else self.index_fn
        if cur.index is not None and fn is None:
            raise ValueError(
                "engine carries an index but no index maintainer: pass "
                "index_fn= (e.g. apps/hub2.py::hub_index_updater(...)) at "
                "construction or to apply_delta"
            )

        rt = self.runtime
        old_hash = cur.graph.content_hash()
        if rt.journal is not None and not _from_journal:
            # WAL in-flight state BEFORE the mutation record: each snapshot
            # payload pins its pre-mutation version, so recovery replays
            # submit -> snapshot -> mutation in order and every resumed
            # query still answers on the version it was admitted under.
            rt.snapshot()

        new_graph = cur.graph.apply_delta(delta)

        # ---- auxiliary views: derive each view's delta, reuse untouched
        aux_delta: dict = {}
        if aux_deltas == "reverse":
            rev = delta.reversed()
            aux_delta = {name: rev for name in cur.aux}
        elif aux_deltas is None or isinstance(aux_deltas, dict):
            spec = dict(aux_deltas or {})
            unknown = set(spec) - set(cur.aux)
            if unknown:
                raise ValueError(
                    f"aux_deltas names unknown views {sorted(unknown)}: "
                    f"engine has {sorted(cur.aux)}"
                )
            for name in cur.aux:
                d = spec.get(name)
                if d is not None and not isinstance(d, EdgeDelta):
                    a_, d_ = d
                    d = cur.aux[name].make_delta(a_, d_)
                aux_delta[name] = d
        else:
            raise ValueError(
                "aux_deltas must be 'reverse', None, or a "
                "{view: EdgeDelta | (adds, dels) | None} dict"
            )
        new_aux: dict = {}
        new_backends = {
            "default": cur.backends["default"].refresh(new_graph, delta)
        }
        for name, g_old in cur.aux.items():
            d = aux_delta[name]
            if d is None:  # declared unaffected: reuse graph AND tables
                new_aux[name] = g_old
                new_backends[name] = cur.backends[name]
            else:
                g_new = g_old.apply_delta(d)
                new_aux[name] = g_new
                new_backends[name] = cur.backends[name].refresh(g_new, d)

        # ---- index maintenance (incremental or rebuild — fn decides)
        new_index, index_info = None, None
        if cur.index is not None:
            # maintainers see the logical graph, never capacity padding
            new_index, index_info = fn(new_graph.trimmed(), cur.index, delta)

        new_hash = new_graph.content_hash()
        if rt.journal is not None and not _from_journal:
            rt.journal.mutation(
                version=int(new_graph.version), parent_hash=old_hash,
                content_hash=new_hash,
                adds=np.stack([delta.add_src, delta.add_dst], axis=1),
                add_w=delta.add_w,
                dels=np.stack([delta.del_src, delta.del_dst], axis=1),
            )

        # ---- install the new edition; old ones stay until their readers go
        ed = self._make_edition(new_graph, new_index, new_aux, new_backends)
        self._editions[ed.version] = ed
        self._current_version = ed.version
        self.graph = new_graph
        self.index = new_index
        self._backends = new_backends
        self.aux_graphs = {k: (g_, None) for k, g_ in new_aux.items()}

        # ---- version-keyed cache invalidation: only entries whose prefix
        # matches the new content hash stay servable.  (A retirement may
        # later insert an old-version entry — harmless: submit-time keys
        # carry the current prefix, so it is unreachable unless the content
        # genuinely reverts, in which case serving it is byte-identical.)
        # Entries are bucketed by prefix, so this drops whole buckets
        # instead of sweeping every key per mutation.
        invalidated = 0
        if rt.cache is not None:
            t0 = time.perf_counter()
            invalidated = rt.cache.invalidate_except(new_hash)
            rt.stats.cache_invalidations += invalidated
            rt.stats.cache_invalidation_ms += (time.perf_counter() - t0) * 1e3
        if self.warmup and not self._arg_carried and not self.legacy:
            self._spawn_warmup(ed)
        if prune:
            self._prune_editions()
        return dict(
            version=ed.version, parent_hash=old_hash, content_hash=new_hash,
            delta_size=delta.size, cache_invalidated=invalidated,
            editions=sorted(self._editions), index=index_info,
        )

    def apply_delta_record(self, rec: dict) -> dict:
        """Replay one journaled ``mutation`` record (recovery path,
        launch/supervise.py).  The hash chain makes replay deterministic or
        refused: the record's ``parent_hash`` must match the engine's
        current content, and the replayed graph must reproduce the recorded
        ``content_hash`` exactly."""
        cur_hash = self._editions[self._current_version].graph.content_hash()
        if rec["parent_hash"] != cur_hash:
            raise RuntimeError(
                "mutation chain mismatch: journal expects parent "
                f"{rec['parent_hash'][:12]}… but the engine's graph hashes "
                f"{cur_hash[:12]}… — booted from the wrong store snapshot "
                "for this journal?"
            )
        adds = np.asarray(rec["adds"], np.int32).reshape(-1, 2)
        dels = np.asarray(rec["dels"], np.int32).reshape(-1, 2)
        info = self.apply_delta(
            adds if len(adds) else None,
            dels if len(dels) else None,
            w=np.asarray(rec["add_w"]) if len(adds) else None,
            prune=False, _from_journal=True,
        )
        if info["content_hash"] != rec["content_hash"]:
            raise RuntimeError(
                "mutation replay diverged: journal recorded content "
                f"{rec['content_hash'][:12]}… but replay produced "
                f"{info['content_hash'][:12]}…"
            )
        return info

    def _prune_editions(self) -> None:
        """Drop editions no reader can reach: not current, not pinned by a
        live slot, not referenced by a suspended payload.  Called only
        between rounds (from ``apply_delta``), never mid-retirement."""
        live = np.asarray(self.runtime.live)
        needed = {self._current_version}
        needed.update(
            int(self._slot_version[s])
            for s in range(self.capacity) if live[s]
        )
        needed.update(v for v, c in self._resume_refs.items() if c > 0)
        for v in [v for v in self._editions if v not in needed]:
            del self._editions[v]

    # ---------------------------------------------- fault tolerance hooks
    def export_tables(self) -> dict:
        """Prebuilt per-semiring tile tables by view name, for persistence
        (core/store.py::save_engine_store) — the exact dicts a future
        engine passes back as ``blocks=`` / ``aux_graphs=(g, blocks)`` to
        boot with zero table builds.  Empty for backends (coo, sharded)
        that prepare nothing worth saving."""
        out = {}
        for name, be in self._backends.items():
            t = be.export_tables()
            if t is not None:
                out[name] = t
        return out

    def poison_slot(self, slot: int, value: float = float("nan")) -> int:
        """Fault injection (DESIGN.md §10): overwrite every float leaf of
        one slot's state row with ``value``, modeling in-flight memory
        corruption.  Returns the number of leaves poisoned; raises if the
        program's state has no float leaves (the int lanes saturate at the
        FINITE ``semiring.INF`` sentinel and cannot encode a poison).  The
        runtime detects the non-finite result at extraction and
        quarantines the query instead of publishing it."""
        slot = int(slot)
        n = 0

        def pz(tab):
            nonlocal n
            if np.dtype(tab.dtype).kind != "f":
                return tab
            arr = np.array(np.asarray(tab))  # gather + host copy
            arr[slot] = value
            n += 1
            out = jnp.asarray(arr)
            if self.mesh is not None and hasattr(tab, "sharding"):
                out = jax.device_put(out, tab.sharding)
            return out

        new_state = jax.tree.map(pz, self._slots["state"])
        if n == 0:
            raise ValueError(
                "cannot poison slot state: no float leaves (int-state "
                "programs saturate at the finite INF sentinel)"
            )
        self._slots = dict(self._slots, state=new_state)
        return n

    # -------------------------------------------------------------- client
    def submit(
        self,
        query,
        *,
        qid: Optional[int] = None,
        priority: int = 0,
        deadline: float = math.inf,
        budget: int = 0,
    ) -> int:
        """Queue a query (paper: console or batch file).  ``priority`` /
        ``deadline`` / ``budget`` feed the runtime's scheduler and TIMEOUT
        eviction (DESIGN.md §9); all default to "no policy".  ``qid`` pins
        the query id (the recovery supervisor keeps ids stable across
        restarts); normally left None for auto-assignment.

        Query content is staged host-side (numpy) so batched admission can
        stack it without device round-trips; jit converts on dispatch.
        """
        return self.runtime.submit(
            jax.tree.map(np.asarray, query),
            qid=qid, priority=priority, deadline=deadline, budget=budget,
        )

    def run_round(self) -> list[tuple[int, Any]]:
        """One super-round: admit from queue, advance all live slots one
        superstep, collect finished queries.  Returns [(qid, result)] for
        queries that COMPLETED (voted done) this round — budget-evicted
        TIMEOUT queries are excluded (their partial results land only in
        ``_results``/``run_until_drained`` with ``status[qid]`` marking
        them), so this list never mixes final and partial answers."""
        return [
            (qid, res)
            for qid, res, status in self.runtime.run_round() or []
            if status == DONE
        ]

    def run_until_drained(self, max_rounds: int = 100_000) -> dict[int, Any]:
        """Batch-querying mode (paper scenario ii)."""
        return self.runtime.run_until_drained(max_rounds)

    def pump(self) -> list[tuple[int, Any, str]]:
        """Open-loop mode (DESIGN.md §11): advance at most one round and
        return ALL terminal transitions ``(qid, result, status)`` since the
        last pump — including cache hits, rejections and TIMEOUTs, unlike
        ``run_round`` which reports DONE only.  Never blocks; submit
        between pumps to interleave arrivals with execution."""
        return self.runtime.pump()

    def poll(self, qid: int) -> Optional[tuple[str, Any]]:
        """``(status, result)`` once ``qid`` is terminal, else None."""
        return self.runtime.poll(qid)

    def pending(self) -> int:
        """Queued-but-unadmitted queries (loadgen backlog signal)."""
        return self.runtime.pending()

    def inflight(self) -> int:
        """Queries holding slot state right now (live + suspended)."""
        return self.runtime.inflight()

    def query(self, q, max_rounds: int = 100_000, **submit_kw):
        """Interactive mode (paper scenario i): submit and wait.

        Raises ``QueryTimeoutError`` if the query is still unfinished after
        ``max_rounds`` super-rounds (submit with a superstep ``budget`` to
        retire runaways as TIMEOUT with a partial result instead)."""
        qid = self.submit(q, **submit_kw)
        rounds = 0
        while qid not in self._results and rounds < max_rounds:
            self.runtime.run_round()
            rounds += 1
        if qid not in self._results:
            raise QueryTimeoutError(
                f"query {qid} still unfinished after {max_rounds} "
                f"super-rounds (capacity={self.capacity}, "
                f"steps_per_round={self.steps_per_round}); raise max_rounds "
                "or submit(..., budget=N) to evict it with a TIMEOUT status"
            )
        return self._results[qid]
