"""Read the program's own spans and counters for the measured window.

The program records them (``repro.core.tracing``) only while the profiler
runs, so only a ``--trace 1`` run has any.  Rounds are named by their
index into ``SlotStats.round_times``: the window's rounds are
``[window.stats0["rounds"], window.stats1["rounds"])``, and its queries
are ``window.queries``.  Every reader returns None where there is nothing
to read: an untraced run, or a program without these records.
"""
from __future__ import annotations

ROUND = "quegel.round"


def records() -> list:
    try:
        from repro.core import tracing
    except ImportError:  # a program that records nothing
        return []
    return tracing.records()


def _rounds(run) -> range:
    w = run.window
    return range(w.stats0["rounds"], w.stats1["rounds"])


def span_ms_per_round(run, name: str):
    """Total milliseconds of the spans named ``name`` over the window's
    traced rounds, per traced round."""
    rounds = _rounds(run)
    recs = [r for r in records() if r.kind == "span" and r.round in rounds]
    traced = {r.round for r in recs if r.name == ROUND}
    if not traced:
        return None
    ns = sum(r.t1_ns - r.t0_ns for r in recs
             if r.name == name and r.round in traced)
    return ns / len(traced) / 1e6


def counter_sum(run, name: str):
    """The window's sum of the counter ``name``, None where it has none."""
    rounds = _rounds(run)
    vals = [r.value for r in records()
            if r.kind == "count" and r.name == name and r.round in rounds]
    return sum(vals) if vals else None


def queries(run) -> list:
    """The query records of the window's answered queries."""
    done = {qid for qid, q in run.window.queries.items()
            if q.done is not None}
    return [r for r in records() if r.kind == "query" and r.qid in done]
