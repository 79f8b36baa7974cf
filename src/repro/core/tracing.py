"""The program's own spans and counters, recorded only under the JAX profiler.

The switch is the profiler itself: while a profiler session runs
(``jax.profiler.start_trace`` / ``jax.profiler.trace``),
``jax.profiler.TraceAnnotation.is_enabled()`` is true, and every ``span``
both enters a ``TraceAnnotation`` (so it lands in the profiler's trace on
the same host timeline as the device ops) and appends a record to a
bounded in-memory buffer.  With no session running, ``span`` hands back one
shared no-op context and ``count`` / ``query`` return at once: the serving
path pays a flag check per call and allocates nothing.

Records, in the order they close:

  ``Span(name, t0_ns, t1_ns, parent, round, qid)``  a timed phase; ``parent``
      is the name of the innermost span open around it on the same thread,
      ``round`` the super-round's index into ``SlotStats.round_times``
  ``Count(name, value, round)``  a per-round counter
  ``Query(qid, status, submit_ns, admit_ns, done_ns, admit_round,
      retire_round)``  one retired query: submission, first admission and
      the end of the round that retired it, on ``time.perf_counter_ns``'s
      clock

Every name starts with ``quegel.``, so none can be mistaken for a span of
a caller that times the program from outside.  ``records()`` and
``clear()`` serve readers and tests; ``dump(path)`` writes the buffer as
JSON lines for an operator.
"""
from __future__ import annotations

import collections
import contextlib
import json
import threading
import time
from typing import NamedTuple, Optional

from jax.profiler import StepTraceAnnotation, TraceAnnotation

PREFIX = "quegel."
# spans: the super-round and its phases (runtime and engine)
ROUND = "quegel.round"        # SlotRuntime.run_round, a step annotation
ADMIT = "quegel.admit"        # retry release, scheduler pops, preemption
DISPATCH = "quegel.dispatch"  # slot_round entry until the jitted call returns
SYNC = "quegel.sync"          # the round's done/step readback
RETIRE = "quegel.retire"      # eviction, collect, retirement bookkeeping
COLLECT = "quegel.collect"    # extract_all dispatch and readback
# counters, per round, of the frontiers the round propagates
FRONTIER_ARCS = "quegel.frontier_arcs"    # arcs leaving advancing slots' frontiers
ARCS_READ = "quegel.arcs_read"            # arcs the round's propagates read
PROPAGATE_CALLS = "quegel.propagate_calls"  # the round's propagate calls
FRONTIER_PATH = "quegel.frontier_path"    # ... that read only frontier arcs

MAX_RECORDS = 1 << 18

enabled = TraceAnnotation.is_enabled


class Span(NamedTuple):
    name: str
    t0_ns: int
    t1_ns: int
    parent: Optional[str]
    round: Optional[int]
    qid: Optional[int]
    kind = "span"


class Count(NamedTuple):
    name: str
    value: int
    round: int
    kind = "count"


class Query(NamedTuple):
    qid: int
    status: str
    submit_ns: int
    admit_ns: int
    done_ns: int
    admit_round: int
    retire_round: int
    kind = "query"


_buf: collections.deque = collections.deque(maxlen=MAX_RECORDS)
_open = threading.local()


_OFF = contextlib.nullcontext()


class _On:
    __slots__ = ("name", "round", "qid", "parent", "ann", "t0")

    def __init__(self, name, round, qid):
        self.name, self.round, self.qid = name, round, qid

    def __enter__(self):
        stack = getattr(_open, "stack", None)
        if stack is None:
            stack = _open.stack = []
        self.parent = stack[-1] if stack else None
        if self.name == ROUND:
            self.ann = StepTraceAnnotation(self.name, step_num=self.round)
        else:
            meta = {k: v for k, v in (("round", self.round), ("qid", self.qid))
                    if v is not None}
            self.ann = TraceAnnotation(self.name, **meta)
        self.ann.__enter__()
        stack.append(self.name)
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        _open.stack.pop()
        self.ann.__exit__(*exc)
        _buf.append(Span(self.name, self.t0, t1, self.parent, self.round,
                         self.qid))
        return False


def span(name: str, *, round: Optional[int] = None, qid: Optional[int] = None):
    """A context that times ``name`` while the profiler runs, else a no-op.
    ``ROUND`` is entered as a ``StepTraceAnnotation`` numbered ``round``."""
    if not enabled():
        return _OFF
    if not name.startswith(PREFIX):
        raise ValueError(f"span name {name!r} must start with {PREFIX!r}")
    return _On(name, round, qid)


def count(name: str, value: int, *, round: int) -> None:
    """Record a per-round counter while the profiler runs."""
    if enabled():
        _buf.append(Count(name, int(value), round))


def query(qid: int, status: str, submit_s: float, admit_s: float,
          done_s: float, admit_round: int, retire_round: int) -> None:
    """Record one retired query from ``time.perf_counter()`` readings the
    caller already took."""
    if enabled():
        _buf.append(Query(qid, status, int(submit_s * 1e9),
                          int(admit_s * 1e9), int(done_s * 1e9),
                          admit_round, retire_round))


def records() -> list:
    """Every record still in the buffer, oldest first."""
    return list(_buf)


def clear() -> None:
    _buf.clear()


def dump(path) -> int:
    """Write the buffer to ``path`` as JSON lines, one record each with its
    ``kind``; returns the number written."""
    recs = records()
    with open(path, "w") as f:
        for r in recs:
            f.write(json.dumps(dict(kind=r.kind, **r._asdict())) + "\n")
    return len(recs)
