#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, in a process of its own.

    python3 bench/run_cell.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

In order: build the cell's graph and its pool of queries on the host from
``--seed`` with the benchmark's own generator; build the engine through the user entry points
(``make_bibfs_engine`` / ``make_terrain_engine`` -> ``QuegelEngine`` ->
``SlotRuntime``); warm up every program the window uses by running
queries to completion; measure for ``--seconds`` on the open-loop serving
path (``submit()`` between ``pump()`` calls); check every answer due in
the window against the plain host reference; print one JSON line.

With ``--trace 0`` the line's metrics are the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics, read from a profiler trace of
the window.  A run that finds no TPU, or fewer chips than the cell asks
for, exits 2 and prints no result; one whose window compiles anything
exits 3.  ``--tiny`` runs the cell at its configuration's tiny sizes on
any platform, for the tests.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# JAX's persistent compilation cache lives at a fixed path in the checkout
# (the path is part of the cache key); the program takes it from here.
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")

import numpy as np  # noqa: E402

from bench.harness import loops, spec  # noqa: E402
from bench.harness.arrivals import arrivals  # noqa: E402
from bench.harness.roofline import peaks  # noqa: E402

# JAX's monitoring event for each program it compiles or loads from the
# persistent cache, and the cache's own events (a miss is a compile).
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
                "/jax/compilation_cache/cache_misses": "cache_misses"}


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="the configuration's tiny sizes, on any platform")
    return ap.parse_args(argv)


def configured(cell, tiny: bool) -> dict:
    cfg = {k: v for k, v in cell.config.items() if k != "tiny"}
    if tiny:
        cfg.update(cell.config.get("tiny", {}))
    return cfg


def rngs(seed: int):
    """Independent streams for the graph, the query pool, the window's
    order of queries and its arrivals, and the warm-up's order."""
    return [np.random.default_rng([seed, k]) for k in range(5)]


def warm_up(eng, pairs, capacity: int) -> None:
    """Two batches of ``capacity`` queries to completion: the first round
    of each admits (``round_admit``), later ones only advance (``round``),
    and every retirement extracts (``extract_all``)."""
    for _ in range(2):
        open_q = {eng.submit(next(pairs)) for _ in range(capacity)}
        while open_q:
            for qid, _, _ in eng.pump():
                open_q.discard(qid)


def run_window(eng, pool, traffic, rng_q, rng_a, seconds, trace):
    pairs = loops.cycle(pool, rng_q)
    if traffic["loop"] == "closed":
        return loops.closed_loop(eng, pairs, traffic["outstanding"], seconds,
                                 trace=trace)
    rate = traffic["arrivals"]["rate"]
    n = int(rate * seconds * 2) + 64
    return loops.open_loop(eng, pairs, arrivals(traffic["arrivals"], n, rng_a),
                           seconds, trace=trace)


class HostCounters:
    """Programs compiled or loaded since start, compile-cache hits and
    misses, and the process's CPU time: what tells a run that compiled
    from one that loaded, and a slow host from slow work."""

    def __init__(self, jax):
        self.n = dict(programs=0, cache_hits=0, cache_misses=0)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, *a, **k):
        if event == BACKEND_COMPILE_EVENT:
            self.n["programs"] += 1

    def _event(self, event, *a, **k):
        if event in CACHE_EVENTS:
            self.n[CACHE_EVENTS[event]] += 1

    def read(self) -> dict:
        ru = resource.getrusage(resource.RUSAGE_SELF)
        return dict(self.n, cpu_s=ru.ru_utime + ru.ru_stime)


def device_of(devs) -> dict:
    stats = devs[0].memory_stats() or {}
    return dict(platform=devs[0].platform, kind=devs[0].device_kind,
                count=len(devs),
                memory_peak_bytes=int(stats.get("peak_bytes_in_use", 0)))


def main(argv=None) -> int:
    args = parse(argv)
    cell = spec.Cell(spec.benchmark(), args.workload)
    dep, cfg = cell.deployment, configured(cell, args.tiny)

    import jax

    from repro.launch.env import enable_compile_cache

    devs = jax.devices()
    if devs[0].platform != "tpu" and not args.tiny:
        say(f"no TPU: jax.devices()[0].platform is {devs[0].platform!r}")
        return 2
    if len(devs) < cell.chips:
        say(f"{cell.name} needs {cell.chips} chips, jax sees {len(devs)}")
        return 2
    enable_compile_cache()
    host = HostCounters(jax)

    rng_g, rng_p, rng_q, rng_a, rng_w = rngs(args.seed)
    data = dep.build(cfg, rng_g)
    pool = dep.pool(data, cfg, rng_p)
    eng = dep.make_engine(data, cfg)
    warm_up(eng, loops.cycle(pool, rng_w), cfg["capacity"])
    compiles0, host0 = eng.stats.jit_compiles, host.read()
    setup_s = time.perf_counter() - T_START

    log_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    if log_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    win = run_window(eng, pool, cell.traffic, rng_q, rng_a, args.seconds,
                     bool(args.trace))
    host1 = host.read()
    if log_dir:
        jax.profiler.stop_trace()
    in_window = {k: host1[k] - host0[k] for k in host0}
    say(f"setup_s={setup_s} jit_compiles={compiles0} "
        f"window_jit_compiles={eng.stats.jit_compiles - compiles0} "
        f"window_programs={in_window['programs']}")
    if eng.stats.jit_compiles != compiles0 or in_window["programs"]:
        say("the window compiled: its shapes were not all warmed up")
        return 3

    st = eng.stats
    r0, r1 = win.stats0["rounds"], win.stats1["rounds"]
    d0, d1 = win.stats0["done"], win.stats1["done"]
    run = types.SimpleNamespace(
        window=win, setup_s=setup_s,
        round_times=np.asarray(st.round_times[r0:r1]),
        occupancy=np.asarray(st.slot_occupancy[r0:r1]),
        trace=None, least_bytes=0,
        peaks=peaks(devs[0].device_kind) if not args.tiny else None,
    )
    # from submit() to admission: blind to the wait for the round in flight
    waits = st.queue_waits[d0:d1]
    queue_wait_p50_s = float(np.median(waits)) if len(waits) else 0.0
    device = device_of(devs)
    queries = list(win.queries.values())
    answers = [dep.served(q.result) if q.status == "DONE" else None
               for q in queries]
    del eng, st
    gc.collect()

    with jax.profiler.TraceAnnotation("reference"):
        ref = loops.per_pair(lambda ps: dep.reference(data, cfg, ps),
                             [q.pair for q in queries])
    checks, wrong = dep.compare(answers, ref, cfg["limits"])
    unanswered = sum(a is None for a in answers)
    checks["unanswered"] = (unanswered, 0)
    run.least_bytes = sum(r["least_bytes"] for q, r in zip(queries, ref)
                          if q.done is not None and q.done <= win.seconds)

    line = dict(correct=all(v <= lim for v, lim in checks.values()),
                attempted=len(queries),
                failed=wrong + unanswered, metrics={},
                device=device)
    if log_dir:
        from bench.harness import trace as tr

        run.trace = tr.reduce(tr.load_xplane(log_dir))
        shutil.rmtree(log_dir, ignore_errors=True)
        if run.trace is not None:
            device.update(busy_s=run.trace["busy_s"],
                          window_s=run.trace["window_s"])
            line["breakdown"] = dict(device_ops=run.trace["device_ops"],
                                     idle_gaps=run.trace["idle_gaps"])
    metrics = cell.per_layer if args.trace else cell.end_to_end
    for m, reader in zip(metrics, cell.readers(metrics)):
        value = reader.read(run)
        if value is not None:
            line["metrics"][m["name"]] = dict(value=value, unit=m["unit"])
    lags = win.lags()
    line["window"] = dict(
        offered=len(queries), completed_in_window=len(win.completed_in_window()),
        rounds=int(r1 - r0), drained_s=win.drained_s,
        generator_lag_p50_s=float(np.median(lags)) if len(lags) else 0.0,
        generator_lag_max_s=float(lags.max()) if len(lags) else 0.0,
        queue_wait_p50_s=queue_wait_p50_s,
        setup=host0, host=in_window)
    line["checks"] = {k: dict(value=v, limit=lim)
                      for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        say(f"check {k}={v} limit={lim}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
