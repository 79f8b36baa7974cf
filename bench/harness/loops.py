"""Drive the engine's open-loop serving path for a fixed window.

The entry is ``QuegelEngine.submit()`` between calls to
``QuegelEngine.pump()``.  Every time is seconds from the window's start on
the host clock.  A query's latency runs from its scheduled arrival to the
return of the ``pump()`` that reported it, so a stall is charged to the
queries queued behind it.  After the window no new query is offered and
the ones still open are drained, for at most ``DRAIN_S`` seconds.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Optional

import numpy as np

DRAIN_S = 60.0

now = time.perf_counter


@dataclasses.dataclass
class Query:
    pair: np.ndarray
    arrival: float  # scheduled (closed loop: when its predecessor ended)
    submitted: float
    done: Optional[float] = None
    status: Optional[str] = None
    result: Any = None


@dataclasses.dataclass
class Window:
    seconds: float
    queries: dict  # qid -> Query, in submission order
    stats0: dict   # lengths of the SlotStats lists at the window's start
    stats1: dict   # ... and at its end
    drained_s: float = 0.0

    def completed_in_window(self) -> list:
        return [q for q in self.queries.values()
                if q.done is not None and q.done <= self.seconds]

    def latencies(self) -> np.ndarray:
        return np.asarray([q.done - q.arrival for q in self.queries.values()
                           if q.done is not None])

    def lags(self) -> np.ndarray:
        """How late the generator submitted each query."""
        return np.asarray([q.submitted - q.arrival
                           for q in self.queries.values()])


def cycle(pool: list, rng):
    """The pool's queries without end, each pass in a new seeded order."""
    while True:
        for i in rng.permutation(len(pool)):
            yield pool[i]


def per_pair(fn, pairs: list) -> list:
    """``fn`` (a list of pairs -> one value each) over the distinct pairs
    only, mapped back onto ``pairs``: the window repeats its pool."""
    keys = [tuple(int(v) for v in p) for p in pairs]
    distinct = list(dict.fromkeys(keys))
    out = dict(zip(distinct, fn([np.asarray(k, np.int32) for k in distinct])))
    return [out[k] for k in keys]


def _stat_lens(eng) -> dict:
    st = eng.stats
    return dict(rounds=len(st.round_times), done=len(st.queue_waits))


def _span(trace: bool):
    if trace:
        import jax

        return jax.profiler.TraceAnnotation
    return lambda name: contextlib.nullcontext()


def _record(win: Window, out, t: float) -> None:
    for qid, res, status in out:
        q = win.queries[qid]
        q.done, q.status, q.result = t, status, res


def closed_loop(eng, pairs, outstanding: int, seconds: float, *,
                trace: bool = False) -> Window:
    """Keep ``outstanding`` queries in the system: each one that ends is
    replaced at once by the next pair of the stream."""
    span = _span(trace)
    win = Window(seconds, {}, _stat_lens(eng), {})
    t0 = now()

    def submit(t):
        pair = next(pairs)
        qid = eng.submit(pair)
        win.queries[qid] = Query(pair, t, now() - t0)

    with span("window"):
        with span("submit"):
            for _ in range(outstanding):
                submit(0.0)
        while now() - t0 < seconds:
            with span("pump"):
                out = eng.pump()
            t = now() - t0
            _record(win, out, t)
            with span("submit"):
                for _ in out:
                    submit(t)
    win.stats1 = _stat_lens(eng)
    _drain(eng, win, t0, span)
    return win


def open_loop(eng, pairs, arrivals: np.ndarray, seconds: float, *,
              trace: bool = False) -> Window:
    """Offer pair i at ``arrivals[i]`` whatever the engine is doing;
    arrivals after the window are not offered."""
    span = _span(trace)
    win = Window(seconds, {}, _stat_lens(eng), {})
    arrivals = arrivals[arrivals < seconds]
    i = 0
    t0 = now()
    with span("window"):
        while True:
            t = now() - t0
            if t >= seconds:
                break
            if i < len(arrivals) and arrivals[i] <= t:
                with span("submit"):
                    while i < len(arrivals) and arrivals[i] <= t:
                        pair = next(pairs)
                        qid = eng.submit(pair)
                        win.queries[qid] = Query(pair, float(arrivals[i]),
                                                 now() - t0)
                        i += 1
            if eng.pending() or eng.inflight():
                with span("pump"):
                    out = eng.pump()
                _record(win, out, now() - t0)
            else:
                nxt = arrivals[i] if i < len(arrivals) else seconds
                with span("wait"):
                    time.sleep(max(0.0, min(nxt, seconds) - (now() - t0)))
    win.stats1 = _stat_lens(eng)
    _drain(eng, win, t0, span)
    return win


def _drain(eng, win: Window, t0: float, span) -> None:
    t_close = now()
    with span("drain"):
        while (any(q.done is None for q in win.queries.values())
               and now() - t_close < DRAIN_S
               and (eng.pending() or eng.inflight())):
            _record(win, eng.pump(), now() - t0)
    win.drained_s = now() - t_close
