"""Benchmark harness — one function per paper table (DESIGN.md §7).

Scaled to this container (single CPU, synthetic graphs); the *shapes* of
the paper's results are what's reproduced: superstep-sharing throughput
vs capacity C, Hub^2 access-rate reduction, BFS-vs-BiBFS asymmetry,
label-pruned reachability, terrain early termination, keyword-count
scaling.  Output: ``table,metric,value`` CSV on stdout, plus a JSON dump
under runs/bench/.

The ``hotpath`` table is the engine's own perf trajectory (DESIGN.md §3):
PPSP / reachability / keyword workloads across the coo, blocks_ref and
pallas(interpret) backends at several capacities C, reporting
super-rounds/sec, queries/sec, p50/p95 query latency and barrier count,
plus a same-run A/B of the fused hot path against the pre-overhaul
(``legacy=True``) round structure.  It writes ``BENCH_quegel.json`` at the
repo root so every future PR has a number to beat.

Usage: PYTHONPATH=src python -m benchmarks.run [--only hotpath] [--quick]
"""
from __future__ import annotations

import argparse
import json
import math
import os
import time

import jax.numpy as jnp
import numpy as np

RESULTS: dict[str, dict] = {}


def emit(table: str, metric: str, value):
    RESULTS.setdefault(table, {})[metric] = value
    if isinstance(value, float):
        print(f"{table},{metric},{value:.4f}")
    else:
        print(f"{table},{metric},{value}")


def _pairs(n, k, seed=0):
    rng = np.random.default_rng(seed)
    return [(int(a), int(b)) for a, b in rng.integers(0, n, (k, 2))]


def _drain(eng, pairs):
    for p in pairs:
        eng.submit(jnp.asarray(p, jnp.int32))
    t0 = time.perf_counter()
    res = eng.run_until_drained()
    return time.perf_counter() - t0, res


# ---------------------------------------------------------------- Table 2
def table2_interactive(quick=False):
    """Per-query latency + access rate, Quegel Hub^2 (paper Table 2)."""
    from repro.apps.hub2 import build_hub_index, make_hub2_engine
    from repro.core.graph import barabasi_albert

    g = barabasi_albert(3000 if not quick else 600, 3, seed=0)
    t0 = time.perf_counter()
    idx = build_hub_index(g, k=16, capacity=8)
    emit("table2", "index_s", time.perf_counter() - t0)
    eng = make_hub2_engine(g, idx, capacity=1)  # interactive: one at a time
    pairs = _pairs(g.n_real, 20, seed=1)
    times, access = [], []
    for s, t in pairs:
        t0 = time.perf_counter()
        r = eng.query(jnp.asarray([s, t], jnp.int32))
        times.append(time.perf_counter() - t0)
        access.append(int(r["visited"]) / g.n_real)
    emit("table2", "n_queries", len(pairs))
    emit("table2", "mean_query_s", float(np.mean(times)))
    emit("table2", "p95_query_s", float(np.percentile(times, 95)))
    emit("table2", "mean_access_rate", float(np.mean(access)))


# ------------------------------------------------------------- Tables 3/4
def table3_bfs_vs_bibfs(quick=False):
    """Cumulative BFS vs BiBFS on a power-law graph (Twitter-like, most
    pairs reachable) and a multi-CC graph (BTC-like, most unreachable)."""
    from repro.apps.ppsp import make_bfs_engine, make_bibfs_engine
    from repro.core.graph import barabasi_albert, multi_component_graph

    n_q = 10 if quick else 20
    for tag, g in (
        ("twitterlike", barabasi_albert(2000 if not quick else 400, 3, seed=2)),
        ("btclike", multi_component_graph(8, 250 if not quick else 50, 2.0, seed=3)),
    ):
        pairs = _pairs(g.n_real, n_q, seed=4)
        for name, mk in (("bfs", make_bfs_engine), ("bibfs", make_bibfs_engine)):
            eng = mk(g, capacity=8)
            dt, res = _drain(eng, pairs)
            acc = np.mean([int(r["visited"]) for r in res.values()]) / g.n_real
            emit("table3", f"{tag}_{name}_query_s", dt)
            emit("table3", f"{tag}_{name}_access_rate", float(acc))


# ------------------------------------------------------------- Tables 5/6
def table5_hub2(quick=False):
    """Hub^2 index: build time and query speed/access vs k."""
    from repro.apps.hub2 import build_hub_index, make_hub2_engine
    from repro.apps.ppsp import make_bibfs_engine
    from repro.core.graph import barabasi_albert

    g = barabasi_albert(2000 if not quick else 400, 3, seed=5)
    pairs = _pairs(g.n_real, 10 if quick else 30, seed=6)
    eng0 = make_bibfs_engine(g, capacity=8)
    dt0, res0 = _drain(eng0, pairs)
    emit("table5", "bibfs_query_s", dt0)
    emit("table5", "bibfs_access_rate",
         float(np.mean([int(r["visited"]) for r in res0.values()]) / g.n_real))
    for k in (8, 32):
        t0 = time.perf_counter()
        idx = build_hub_index(g, k=k, capacity=8)
        emit("table5", f"k{k}_index_s", time.perf_counter() - t0)
        eng = make_hub2_engine(g, idx, capacity=8)
        dt, res = _drain(eng, pairs)
        emit("table5", f"k{k}_query_s", dt)
        emit("table5", f"k{k}_access_rate",
             float(np.mean([int(r["visited"]) for r in res.values()]) / g.n_real))


# -------------------------------------------------------------- Table 7a
def table7a_capacity(quick=False):
    """Throughput vs capacity C — the superstep-sharing headline.

    Light-weight (Hub²-indexed) queries, the paper's target workload.  Two
    numbers per C: measured single-device wall time, and a modeled cluster
    time  measured/W + barriers × t_sync  (W=120 workers, t_sync=10 ms —
    the paper's GbE/MPI setting, where compute is spread over the cluster
    and each super-round pays one synchronization).  On ONE device the
    dense (C, V) slabs make compute grow with C, so the *measured* curve
    is flat; the barrier count drops ~C-fold — that is the quantity
    superstep-sharing optimizes, and the modeled curve shows the paper's
    Table 7a shape (steep rise, saturation by C≈8)."""
    from repro.apps.hub2 import build_hub_index, make_hub2_engine
    from repro.core.graph import barabasi_albert

    T_BARRIER = 0.010
    W = 120
    g = barabasi_albert(1500 if not quick else 300, 3, seed=7)
    idx = build_hub_index(g, k=16, capacity=8)
    pairs = _pairs(g.n_real, 16 if quick else 48, seed=8)
    for c in (1, 2, 4, 8, 16):
        eng = make_hub2_engine(g, idx, capacity=c)
        dt, res = _drain(eng, pairs)
        assert len(res) == len(pairs)
        emit("table7a", f"C{c}_total_s", dt)
        emit("table7a", f"C{c}_barriers", eng.stats.barriers)
        emit("table7a", f"C{c}_qps", len(pairs) / dt)
        modeled = dt / W + eng.stats.barriers * T_BARRIER
        emit("table7a", f"C{c}_modeled_cluster_s", modeled)
        emit("table7a", f"C{c}_modeled_qps", len(pairs) / modeled)


# -------------------------------------------------------------- Table 7b
def table7b_scaling(quick=False):
    """Worker scaling — balance of the edge partition and the collective
    bytes per super-round as worker count grows (simulated: we report the
    partition statistics the runtime would see; real speedup needs a pod)."""
    from repro.core.distributed import ShardedGraph
    from repro.core.graph import barabasi_albert

    g = barabasi_albert(1024 if not quick else 256, 3, seed=9)
    for w in (2, 4, 8, 16):
        if g.n % w:
            continue
        sg = ShardedGraph(g, w, partition="dst")
        per = np.asarray(sg.valid).sum(axis=1)
        emit("table7b", f"w{w}_max_edges", int(per.max()))
        emit("table7b", f"w{w}_balance", float(per.max() / max(per.mean(), 1)))
        # dst partition all-gathers the (C, V/w) result per round
        emit("table7b", f"w{w}_collective_bytes_per_round", int(8 * g.n * 4))


# --------------------------------------------------------------- Table 8
def table8_xml(quick=False):
    """XML keyword search: SLCA (naive vs level-aligned), ELCA, MaxMatch."""
    from repro.apps.keyword import MAXK, make_vertex_text
    from repro.apps.xmlkw import (
        MaxMatch, SLCALevelAligned, SLCANaive, build_xml_index, make_xml_engine)
    from repro.core.graph import random_tree

    n = 2000 if not quick else 400
    g, parent = random_tree(n, max_fanout=6, seed=10)
    tokens = make_vertex_text(n, 40, 3, seed=11)
    idx = build_xml_index(parent, tokens, g.n)
    rng = np.random.default_rng(12)
    queries = [rng.integers(0, 20, 2).tolist() for _ in range(8 if quick else 16)]

    def run(cls, tag):
        eng = make_xml_engine(cls, g, idx, capacity=8)
        for kws in queries:
            q = np.full(MAXK, -1, np.int32)
            q[: len(kws)] = kws
            eng.submit(jnp.asarray(q))
        t0 = time.perf_counter()
        eng.run_until_drained()
        emit("table8", f"{tag}_total_s", time.perf_counter() - t0)

    run(SLCANaive, "slca_naive")
    run(SLCALevelAligned, "slca_level_aligned")
    run(MaxMatch, "maxmatch")


# -------------------------------------------------------------- Table 10
def table10_terrain(quick=False):
    """Terrain SSSP: time/steps/access vs query distance; early stop."""
    from repro.apps.terrain import make_terrain_engine
    from repro.core.graph import grid_terrain

    g, coords = grid_terrain(24 if quick else 40, 28 if quick else 45,
                             eps_subdiv=2, seed=13)
    eng = make_terrain_engine(g, coords, capacity=1)
    s = 0
    for i, hop in enumerate((4, 16, 64, 256)):
        t = min(g.n_real - 1, hop * 40)
        t0 = time.perf_counter()
        r = eng.query(jnp.asarray([s, t], jnp.int32))
        emit("table10", f"q{i+1}_s", time.perf_counter() - t0)
        emit("table10", f"q{i+1}_len_m", float(r["dist"]))
        emit("table10", f"q{i+1}_access_rate", int(r["visited"]) / g.n_real)


# -------------------------------------------------------------- Table 11
def table11_reach(quick=False):
    """Reachability: index build phases + pruned query access rate."""
    from repro.apps.ppsp import make_bibfs_engine
    from repro.apps.reach import build_reach_index, make_reach_engine, scc_condense
    from repro.core.graph import random_graph

    g = random_graph(3000 if not quick else 600, 2.5, seed=14)
    t0 = time.perf_counter()
    comp, dag = scc_condense(g)
    emit("table11", "scc_s", time.perf_counter() - t0)
    emit("table11", "dag_vertices", dag.n_real)
    t0 = time.perf_counter()
    idx = build_reach_index(dag)
    emit("table11", "label_s", time.perf_counter() - t0)
    pairs = _pairs(dag.n_real, 10 if quick else 30, seed=15)
    eng = make_reach_engine(dag, idx, capacity=8)
    dt, res = _drain(eng, pairs)
    emit("table11", "query_s", dt)
    emit("table11", "access_rate",
         float(np.mean([int(r["visited"]) for r in res.values()]) / dag.n_real))
    plain = make_bibfs_engine(dag, capacity=8)
    dtp, resp = _drain(plain, pairs)
    emit("table11", "plain_bibfs_access_rate",
         float(np.mean([int(r["visited"]) for r in resp.values()]) / dag.n_real))


# -------------------------------------------------------------- Table 12
def table12_keyword(quick=False):
    """RDF keyword search: 2 vs 3 keywords."""
    from repro.apps.keyword import MAXK, make_keyword_engine, make_vertex_text
    from repro.core.graph import random_graph

    g = random_graph(2000 if not quick else 400, 3.0, seed=16, directed=True)
    tokens = make_vertex_text(g.n_real, 30, 2, seed=17)
    tokens = np.pad(tokens, ((0, g.n - g.n_real), (0, 0)), constant_values=-2)
    eng = make_keyword_engine(g, tokens, capacity=8, delta_max=3)
    rng = np.random.default_rng(18)
    for m in (2, 3):
        qs = []
        for _ in range(8 if quick else 16):
            q = np.full(MAXK, -1, np.int32)
            q[:m] = rng.integers(0, 12, m)
            qs.append(jnp.asarray(q))
        for q in qs:
            eng.submit(q)
        t0 = time.perf_counter()
        res = eng.run_until_drained()
        emit("table12", f"kw{m}_total_s", time.perf_counter() - t0)
        emit("table12", f"kw{m}_mean_touched",
             float(np.mean([int(r["touched"]) for r in res.values()]) / g.n_real))
        eng._results.clear()


def _bench_meta() -> dict:
    """Provenance block stamped into BENCH_quegel.json on every merge, so
    committed rows across PRs say what host/tree/tunings produced them."""
    import platform as _platform
    import subprocess
    from datetime import datetime, timezone

    from repro.launch import env as _env

    meta = {
        "platform": _platform.platform(),
        "python": _platform.python_version(),
        "cpus": os.cpu_count() or 1,
        "timestamp": datetime.now(timezone.utc).isoformat(
            timespec="seconds"),
        "env": _env.describe(),
    }
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            capture_output=True, text=True, timeout=10,
        )
        if sha.returncode == 0:
            meta["git_sha"] = sha.stdout.strip()
            dirty = subprocess.run(
                ["git", "status", "--porcelain", "--untracked-files=no"],
                capture_output=True, text=True, timeout=10,
            )
            if dirty.returncode == 0 and dirty.stdout.strip():
                meta["git_sha"] += "+dirty"
    except (OSError, subprocess.SubprocessError):
        pass
    return meta


def _merge_bench_json(update: dict, path: str = "BENCH_quegel.json"):
    """Update top-level keys of the committed bench JSON in place, so
    ``--only sparsity`` and ``--only hotpath`` each land without clobbering
    the other table's numbers.  Every merge re-stamps the provenance
    ``meta`` block (platform, cpus, git SHA, timestamp, active tunings)."""
    data = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, json.JSONDecodeError):
            data = {}
    data.update(update)
    data.setdefault("meta", {}).update(_bench_meta())
    with open(path, "w") as f:
        json.dump(data, f, indent=2)
    print(f"# wrote {path}")
    return data


# ------------------------------------------------------- hot-path bench
def _reset_stats(eng):
    from repro.core.engine import EngineStats

    eng.stats = EngineStats()


def _measure_drain(eng, queries):
    """Submit ``queries``, drain, return hot-path metrics from EngineStats."""
    _reset_stats(eng)
    for q in queries:
        eng.submit(q)
    t0 = time.perf_counter()
    res = eng.run_until_drained()
    wall = time.perf_counter() - t0
    st = eng.stats
    assert st.queries_done == len(queries), (st.queries_done, len(queries))
    return dict(
        wall_s=wall,
        super_rounds=st.super_rounds,
        barriers=st.barriers,
        super_rounds_per_sec=st.super_rounds / wall,
        queries_per_sec=len(queries) / wall,
        p50_query_latency_s=st.latency_percentile(50),
        p95_query_latency_s=st.latency_percentile(95),
        supersteps_total=st.supersteps_total,
    ), res


def _warm(eng, queries):
    """Compile every round variant (admit / no-admit / extract) off-clock."""
    for q in queries:
        eng.submit(q)
    eng.run_until_drained()
    eng._results.clear()


def _hotpath_cell(make_engine, queries, warmup=4, reps=1):
    eng = make_engine()
    _warm(eng, queries[: max(2, min(warmup, len(queries)))])
    best = None
    for _ in range(reps):
        m, _ = _measure_drain(eng, queries)
        eng._results.clear()
        if best is None or m["wall_s"] < best["wall_s"]:
            best = m
    return best


def bench_hotpath(quick=False):
    """Engine hot-path trajectory + fused-vs-legacy A/B (DESIGN.md §3/§7).

    Emits BENCH_quegel.json at the repo root.  The acceptance number is
    ``ab.speedup_super_rounds_per_sec``: fused (donation + batched
    admission + single-sync rounds) over the pre-overhaul legacy path,
    both measured in this same run on the PPSP workload (coo, C=8).
    """
    import jax

    from repro.apps.keyword import MAXK, make_keyword_engine, make_vertex_text
    from repro.apps.ppsp import make_bfs_engine
    from repro.apps.reach import build_reach_index, make_reach_engine, scc_condense
    from repro.core.graph import barabasi_albert, random_graph

    out: dict = {
        "meta": {
            "jax": jax.__version__,
            "backend": jax.default_backend(),
            "quick": bool(quick),
        },
        "workloads": {},
        "ab": {},
    }

    # ---------------- workload: PPSP (BFS) — capacity sweep on coo -------
    g = barabasi_albert(300 if quick else 1000, 3, seed=7)
    pairs = _pairs(g.n_real, 24 if quick else 64, seed=8)
    qs = [jnp.asarray(p, jnp.int32) for p in pairs]
    ppsp: dict = {"coo": {}}
    for c in (1, 8) if quick else (1, 2, 4, 8, 16):
        cell = _hotpath_cell(lambda c=c: make_bfs_engine(g, capacity=c), qs)
        ppsp["coo"][f"C{c}"] = cell
        emit("hotpath", f"ppsp_coo_C{c}_rounds_per_s", cell["super_rounds_per_sec"])
        emit("hotpath", f"ppsp_coo_C{c}_qps", cell["queries_per_sec"])
        emit("hotpath", f"ppsp_coo_C{c}_p95_s", cell["p95_query_latency_s"])
        emit("hotpath", f"ppsp_coo_C{c}_barriers", cell["barriers"])
    # backend sweep at C=8 on a tile-friendly size (pallas runs interpret
    # mode on CPU — correctness-grade, not TPU-representative).
    gb = barabasi_albert(256 if quick else 512, 3, seed=9)
    pb = _pairs(gb.n_real, 8 if quick else 16, seed=10)
    qb = [jnp.asarray(p, jnp.int32) for p in pb]
    for be in ("coo", "blocks_ref", "pallas"):
        cell = _hotpath_cell(
            lambda be=be: make_bfs_engine(gb, capacity=8, backend=be, block=128),
            qb,
        )
        ppsp.setdefault(be, {})["C8_small"] = cell
        emit("hotpath", f"ppsp_{be}_C8small_rounds_per_s",
             cell["super_rounds_per_sec"])
    out["workloads"]["ppsp"] = ppsp

    # ---------------- workload: reachability (label-pruned BiBFS) --------
    gr = random_graph(300 if quick else 1200, 2.5, seed=11)
    _, dag = scc_condense(gr)
    idx = build_reach_index(dag)
    pr = _pairs(dag.n_real, 12 if quick else 32, seed=12)
    qr = [jnp.asarray(p, jnp.int32) for p in pr]
    reach: dict = {}
    for be in ("coo",) if quick else ("coo", "blocks_ref", "pallas"):
        for c in (8,) if be != "coo" else ((8,) if quick else (1, 8)):
            cell = _hotpath_cell(
                lambda be=be, c=c: make_reach_engine(
                    dag, idx, capacity=c, backend=be, block=128
                ),
                qr,
            )
            reach.setdefault(be, {})[f"C{c}"] = cell
            emit("hotpath", f"reach_{be}_C{c}_rounds_per_s",
                 cell["super_rounds_per_sec"])
            emit("hotpath", f"reach_{be}_C{c}_qps", cell["queries_per_sec"])
    out["workloads"]["reach"] = reach

    # ---------------- workload: RDF keyword search -----------------------
    gk = random_graph(200 if quick else 600, 3.0, seed=13, directed=True)
    tokens = make_vertex_text(gk.n_real, 30, 2, seed=14)
    tokens = np.pad(tokens, ((0, gk.n - gk.n_real), (0, 0)), constant_values=-2)
    rng = np.random.default_rng(15)
    qk = []
    for _ in range(6 if quick else 16):
        q = np.full(MAXK, -1, np.int32)
        q[:2] = rng.integers(0, 12, 2)
        qk.append(jnp.asarray(q))
    kw: dict = {}
    for be in ("coo",) if quick else ("coo", "blocks_ref", "pallas"):
        cell = _hotpath_cell(
            lambda be=be: make_keyword_engine(
                gk, tokens, capacity=8, delta_max=3, backend=be, block=128
            ),
            qk,
        )
        kw[be] = {"C8": cell}
        emit("hotpath", f"keyword_{be}_C8_rounds_per_s",
             cell["super_rounds_per_sec"])
    out["workloads"]["keyword"] = kw

    # ---------------- A/B: fused hot path vs pre-overhaul legacy ---------
    # Regime note (DESIGN.md §3): legacy admission copies the whole
    # (C, V, ...) slot table once per admitted query, so its cost grows
    # with V; the fused path admits via one masked select inside the round
    # dispatch.  V here is large enough for that copy to be visible but
    # small enough that one super-round is still overhead-dominated — the
    # paper's light-workload regime.
    import gc

    ga = barabasi_albert(600, 3, seed=16)
    pa = _pairs(ga.n_real, 64 if quick else 96, seed=17)
    qa = [jnp.asarray(p, jnp.int32) for p in pa]
    reps = 5 if quick else 7
    eng_legacy = make_bfs_engine(ga, capacity=8, legacy=True)
    eng_fused = make_bfs_engine(ga, capacity=8)
    for e in (eng_legacy, eng_fused):
        _warm(e, qa[:10])
    cells: dict = {"legacy": [], "fused": []}
    for _ in range(reps):  # interleave reps so machine drift hits both
        for eng, mode in ((eng_legacy, "legacy"), (eng_fused, "fused")):
            gc.collect()
            gc.disable()
            try:
                m, _ = _measure_drain(eng, qa)
            finally:
                gc.enable()
            eng._results.clear()
            cells[mode].append(m)
    med = lambda ms: sorted(ms, key=lambda m: m["wall_s"])[len(ms) // 2]
    cell_legacy, cell_fused = med(cells["legacy"]), med(cells["fused"])
    speedup = (
        cell_fused["super_rounds_per_sec"] / cell_legacy["super_rounds_per_sec"]
    )
    out["ab"] = {
        "workload": "ppsp_bfs_coo_C8",
        "legacy": cell_legacy,
        "fused": cell_fused,
        "speedup_super_rounds_per_sec": speedup,
        "speedup_queries_per_sec": (
            cell_fused["queries_per_sec"] / cell_legacy["queries_per_sec"]
        ),
    }
    emit("hotpath", "ab_legacy_rounds_per_s", cell_legacy["super_rounds_per_sec"])
    emit("hotpath", "ab_fused_rounds_per_s", cell_fused["super_rounds_per_sec"])
    emit("hotpath", "ab_speedup_rounds_per_s", speedup)

    _merge_bench_json(out)
    RESULTS.setdefault("hotpath", {})["json"] = out


# ----------------------------------------------------------- sparsity
def _time_median(fn, *args, reps=20):
    fn(*args).block_until_ready()  # compile + warm
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def bench_sparsity(quick=False):
    """Sparsity-aware propagation (DESIGN.md §3/§7).

    Two sub-tables, merged into ``BENCH_quegel.json`` under ``sparsity``:

    * ``propagation`` — dense-vs-gated A/B per backend on a low-frontier
      workload (PPSP superstep-1: one active vertex per query, C=8).
      Dense applies the frontier as a full pre-mask of x and visits every
      tile / reduces over every edge; gated skips frontier-dead tiles
      (active-block bitmaps) resp. gathers only active edges (coo).
    * ``rounds`` — multi-superstep fused rounds on the PPSP engine:
      barriers/query and throughput at steps_per_round k ∈ {1, 4, 8},
      with qid→result maps checked identical across k.  Run on a mesh
      (terrain-like) graph whose diameter gives queries dozens of
      supersteps — the regime where amortizing the per-superstep dispatch
      + sync pays (a power-law graph's ~4-superstep BFS caps the
      reduction at ~4× regardless of k).
    """
    import jax

    from repro.apps.ppsp import make_bfs_engine
    from repro.core.graph import barabasi_albert, grid_terrain
    from repro.core.semiring import INF, MIN_RIGHT
    from repro.kernels import ops

    out: dict = {
        "meta": {
            "jax": jax.__version__,
            "backend": jax.default_backend(),
            "quick": bool(quick),
        },
        "propagation": {},
        "rounds": {},
    }

    # ---------------- propagation-level dense-vs-gated A/B ---------------
    g = barabasi_albert(512 if quick else 2048, 3, seed=21)
    C = 8
    rng = np.random.default_rng(22)
    srcs = rng.choice(g.n_real, C, replace=False)
    dist = np.full((C, g.n), INF, np.int32)
    dist[np.arange(C), srcs] = 0
    x = jnp.asarray(dist)
    f = jnp.asarray(dist == 0)  # superstep-1 frontier: 1 vertex per query
    bs = g.to_blocks(128, MIN_RIGHT.add_id)
    chunk = 512
    emit("sparsity", "frontier_density", 1.0 / g.n)
    emit("sparsity", "edges", g.num_edges)
    for be in ("coo", "blocks_ref", "pallas"):
        blk = None if be == "coo" else bs
        reps = 5 if be == "pallas" else (15 if quick else 30)

        def dense(x, f, be=be, blk=blk):
            return ops.propagate(
                g, MIN_RIGHT, x, f, blocks=blk, backend=be, gate=False
            )

        def gated(x, f, be=be, blk=blk):
            return ops.propagate(
                g, MIN_RIGHT, x, f, blocks=blk, backend=be, gate=True,
                gather_edges=chunk if be == "coo" else None,
            )

        t_dense = _time_median(jax.jit(dense), x, f, reps=reps)
        t_gated = _time_median(jax.jit(gated), x, f, reps=reps)
        # parity on the measured inputs — a wrong fast path is worthless
        np.testing.assert_array_equal(
            np.asarray(gated(x, f)), np.asarray(dense(x, f))
        )
        cell = dict(
            dense_s=t_dense,
            gated_s=t_gated,
            speedup=t_dense / t_gated,
        )
        out["propagation"][be] = cell
        emit("sparsity", f"{be}_dense_us", t_dense * 1e6)
        emit("sparsity", f"{be}_gated_us", t_gated * 1e6)
        emit("sparsity", f"{be}_speedup", cell["speedup"])

    # ---------------- multi-superstep fused rounds -----------------------
    g2, _ = grid_terrain(12 if quick else 24, 15 if quick else 30, seed=7)
    pairs = _pairs(g2.n_real, 24 if quick else 64, seed=8)
    qs = [jnp.asarray(p, jnp.int32) for p in pairs]
    base_map = None
    for k in (1, 4, 8):
        eng = make_bfs_engine(g2, capacity=8, steps_per_round=k)
        _warm(eng, qs[: max(2, min(4, len(qs)))])
        m, res = _measure_drain(eng, qs)
        eng._results.clear()
        res_map = {
            qid: {kk: np.asarray(v).tolist() for kk, v in r.items()}
            for qid, r in res.items()
        }
        if base_map is None:
            base_map = res_map
        m["results_match_k1"] = res_map == base_map
        assert m["results_match_k1"], f"steps_per_round={k} changed results"
        out["rounds"][f"k{k}"] = m
        emit("sparsity", f"k{k}_barriers", m["barriers"])
        emit("sparsity", f"k{k}_rounds_per_s", m["super_rounds_per_sec"])
        emit("sparsity", f"k{k}_qps", m["queries_per_sec"])
    red = out["rounds"]["k1"]["barriers"] / out["rounds"]["k8"]["barriers"]
    out["barrier_reduction_k8"] = red
    emit("sparsity", "barrier_reduction_k8", red)

    _merge_bench_json({"sparsity": out})
    RESULTS.setdefault("sparsity", {})["json"] = out


# ------------------------------------------------------------- serving
def bench_serving(quick=False):
    """Scheduler A/B on a mixed light/heavy query workload (DESIGN.md §9).

    The convoy experiment: heavy queries (corner-to-corner PPSP on a grid,
    dozens of supersteps) are submitted AHEAD of many light ones (adjacent
    pairs, 1-2 supersteps) against a small capacity.  fifo — the paper's
    admission rule — makes the lights wait behind the convoy; sjf (by
    declared superstep budget), deadline (EDF) and priority admit them
    first.  Per scheduler: wall time, qps, p50/p95 light-query latency,
    heavy p95, mean slot occupancy — with qid->result maps asserted
    IDENTICAL across schedulers (admission order must never change
    results).  A second sub-table stages arrivals (heavies occupy every
    slot BEFORE the lights are submitted) and A/Bs non-preemptive vs
    preemptive sjf: suspend/resume at round boundaries oversubscribes the
    slot table and rescues light latency when admission order alone no
    longer can.  A third measures the opt-in result cache on a
    repeated-query workload (Quegel's interactive console regime).

    Merged into BENCH_quegel.json under ``serving``; the acceptance
    number is ``light_p95_speedup`` for sjf/deadline vs fifo at equal
    throughput.
    """
    import jax

    from repro.apps.ppsp import make_bfs_engine
    from repro.core.graph import grid_terrain

    rows = 14 if quick else 26
    cols = 16 if quick else 30
    g, _ = grid_terrain(rows, cols, seed=31)
    C = 4
    n_heavy = 3 if quick else 4
    n_light = 12 if quick else 28
    rng = np.random.default_rng(32)
    # heavy: opposite corners of the grid (row-major ids) — ~rows+cols
    # supersteps each; light: horizontal neighbors — 1 superstep.
    heavy = [
        (int(rng.integers(0, cols // 2)),
         g.n_real - 1 - int(rng.integers(0, cols // 2)))
        for _ in range(n_heavy)
    ]
    light_base = rng.integers(0, g.n_real - 2, n_light)
    light = list(dict.fromkeys(
        (int(v), int(v) + 1) for v in light_base if (int(v) + 1) % cols != 0
    )) or [(0, 1)]
    budget_heavy = 4 * (rows + cols)   # way above the true cost: no eviction
    budget_light = 16
    workload = [("heavy", p, budget_heavy, 1e6, 5) for p in heavy] + [
        ("light", p, budget_light, 1.0, 0) for p in light
    ]

    out: dict = {
        "meta": {
            "jax": jax.__version__,
            "backend": jax.default_backend(),
            "quick": bool(quick),
            "capacity": C,
            "n_heavy": len(heavy),
            "n_light": len(light),
        },
        "schedulers": {},
    }

    def run_sched(name, reps):
        """Median-of-reps cell (one engine, reps drains of the workload):
        walltime latencies are noisy at the ms scale, so each cell also
        carries deterministic ROUND-INDEX latencies (the super-round in
        which each query completed — pure scheduling, no clock)."""
        eng = make_bfs_engine(g, capacity=C, scheduler=name)
        _warm(eng, [jnp.asarray(p, jnp.int32) for p in (heavy[0], light[0])])
        cells, maps = [], []
        for _ in range(reps):
            _reset_stats(eng)
            eng._results.clear()
            kinds, idx_of = {}, {}
            t0 = time.perf_counter()
            for i, (kind, p, budget, deadline, prio) in enumerate(workload):
                qid = eng.submit(jnp.asarray(p, jnp.int32), budget=budget,
                                 deadline=deadline, priority=prio)
                kinds[qid] = kind
                idx_of[qid] = i
            done_t: dict = {}
            done_round: dict = {}
            rnd = 0
            while eng.runtime.pending() or eng.runtime.live.any():
                out = eng.run_round()
                now = time.perf_counter()
                rnd += 1
                for qid, _ in out:
                    done_t[qid] = now - t0
                    done_round[qid] = rnd
            wall = time.perf_counter() - t0
            st = eng.stats
            assert st.queries_done == len(workload), name
            assert st.timeouts == 0, name  # budgets are estimates here
            lat = lambda kind, d: [d[q] for q in d if kinds[q] == kind]
            cells.append(dict(
                wall_s=wall,
                queries_per_sec=len(workload) / wall,
                super_rounds=st.super_rounds,
                light_p50_s=float(np.percentile(lat("light", done_t), 50)),
                light_p95_s=float(np.percentile(lat("light", done_t), 95)),
                heavy_p95_s=float(np.percentile(lat("heavy", done_t), 95)),
                light_p95_rounds=float(
                    np.percentile(lat("light", done_round), 95)
                ),
                heavy_p95_rounds=float(
                    np.percentile(lat("heavy", done_round), 95)
                ),
                mean_occupancy=float(np.mean(st.slot_occupancy)),
                # latency split (DESIGN.md §11): is slowness queueing or
                # execution?  fifo shows the convoy as queue wait.
                qwait_p50_s=st.queue_wait_percentile(50),
                qwait_p95_s=st.queue_wait_percentile(95),
                service_p50_s=st.service_percentile(50),
                service_p95_s=st.service_percentile(95),
            ))
            maps.append({
                idx_of[qid]: {k: np.asarray(v).tolist() for k, v in r.items()}
                for qid, r in eng._results.items()
            })
        assert all(m == maps[0] for m in maps[1:]), name
        cell = sorted(cells, key=lambda c: c["light_p95_s"])[len(cells) // 2]
        return cell, maps[0]

    reps = 3 if quick else 5
    base_map = None
    for name in ("fifo", "priority", "sjf", "deadline"):
        cell, res_map = run_sched(name, reps)
        if base_map is None:
            base_map = res_map
        cell["results_match_fifo"] = res_map == base_map
        assert cell["results_match_fifo"], (
            f"scheduler {name} changed query results"
        )
        out["schedulers"][name] = cell
        emit("serving", f"{name}_wall_s", cell["wall_s"])
        emit("serving", f"{name}_qps", cell["queries_per_sec"])
        emit("serving", f"{name}_light_p95_s", cell["light_p95_s"])
        emit("serving", f"{name}_mean_occupancy", cell["mean_occupancy"])
        emit("serving", f"{name}_qwait_p95_s", cell["qwait_p95_s"])
        emit("serving", f"{name}_service_p95_s", cell["service_p95_s"])
    fifo_p95 = out["schedulers"]["fifo"]["light_p95_s"]
    out["light_p95_speedup"] = {
        name: fifo_p95 / out["schedulers"][name]["light_p95_s"]
        for name in ("priority", "sjf", "deadline")
    }
    for name, x in out["light_p95_speedup"].items():
        emit("serving", f"light_p95_speedup_{name}", x)
    if not quick:
        # acceptance: sjf or deadline must beat fifo on light p95 at equal
        # throughput (quick/CI runs only assert result-set identity above —
        # toy walltimes are too noisy to gate on)
        assert max(out["light_p95_speedup"]["sjf"],
                   out["light_p95_speedup"]["deadline"]) > 1.0

    # -------------- staged-arrival preemption (oversubscription) ---------
    # The scheduler A/B above submits everything up front, so sjf fixes the
    # convoy at ADMISSION time.  Here the heavies ARRIVE FIRST and occupy
    # every slot before the lights are even submitted — admission-order
    # scheduling can no longer help; only suspending a running heavy can.
    # preemptive sjf (SRPT) suspends heavies at the next round boundary,
    # oversubscribes the slot table (max_inflight > C), and resumes them
    # after the lights drain — with qid->result maps asserted identical to
    # the non-preemptive run in-run (suspend/resume parity, DESIGN.md §9).
    def run_staged(preemptive, reps):
        eng = make_bfs_engine(g, capacity=C, scheduler="sjf",
                              preemptive=preemptive)
        _warm(eng, [jnp.asarray(p, jnp.int32) for p in (heavy[0], light[0])])
        cells, maps = [], []
        for _ in range(reps):
            _reset_stats(eng)
            eng._results.clear()
            kinds, idx_of = {}, {}
            t0 = time.perf_counter()
            for i, p in enumerate(heavy):
                qid = eng.submit(jnp.asarray(p, jnp.int32),
                                 budget=budget_heavy)
                kinds[qid], idx_of[qid] = "heavy", i
            eng.run_round()  # heavies take the slots before lights arrive
            for i, p in enumerate(light):
                qid = eng.submit(jnp.asarray(p, jnp.int32),
                                 budget=budget_light)
                kinds[qid], idx_of[qid] = "light", len(heavy) + i
            done_t, done_round, rnd = {}, {}, 1
            while eng.runtime.pending() or eng.runtime.live.any():
                res = eng.run_round()
                now = time.perf_counter()
                rnd += 1
                for qid, _ in res:
                    done_t[qid] = now - t0
                    done_round[qid] = rnd
            st = eng.stats
            assert st.queries_done == len(heavy) + len(light)
            lat = lambda kind, d: [d[q] for q in d if kinds[q] == kind]
            cells.append(dict(
                wall_s=time.perf_counter() - t0,
                light_p95_s=float(np.percentile(lat("light", done_t), 95)),
                light_p95_rounds=float(
                    np.percentile(lat("light", done_round), 95)
                ),
                heavy_p95_rounds=float(
                    np.percentile(lat("heavy", done_round), 95)
                ),
                preemptions=st.preemptions,
                resumes=st.resumes,
                max_inflight=st.max_inflight,
            ))
            maps.append({
                idx_of[qid]: {k: np.asarray(v).tolist() for k, v in r.items()}
                for qid, r in eng._results.items()
            })
        assert all(m == maps[0] for m in maps[1:])
        cell = sorted(cells, key=lambda c: c["light_p95_s"])[len(cells) // 2]
        return cell, maps[0]

    pre_reps = 3 if quick else 5
    staged: dict = {}
    staged["sjf"], base = run_staged(False, pre_reps)
    staged["sjf_preemptive"], pre_map = run_staged(True, pre_reps)
    assert pre_map == base, "preemption changed query results"
    staged["sjf"]["results_match"] = staged["sjf_preemptive"]["results_match"] = True
    # preemption must actually fire and oversubscribe the slot table...
    assert staged["sjf_preemptive"]["preemptions"] > 0
    assert staged["sjf_preemptive"]["max_inflight"] > C
    assert staged["sjf"]["preemptions"] == 0
    # ...and beat non-preemptive sjf on light latency.  Round-index p95 is
    # deterministic (pure scheduling), so it gates even quick/CI runs.
    staged["light_p95_rounds_speedup"] = (
        staged["sjf"]["light_p95_rounds"]
        / staged["sjf_preemptive"]["light_p95_rounds"]
    )
    staged["light_p95_speedup"] = (
        staged["sjf"]["light_p95_s"]
        / staged["sjf_preemptive"]["light_p95_s"]
    )
    assert staged["light_p95_rounds_speedup"] > 1.0
    out["staged_preemption"] = staged
    for name in ("sjf", "sjf_preemptive"):
        c = staged[name]
        emit("serving", f"staged_{name}_light_p95_s", c["light_p95_s"])
        emit("serving", f"staged_{name}_light_p95_rounds",
             c["light_p95_rounds"])
        emit("serving", f"staged_{name}_max_inflight", c["max_inflight"])
    emit("serving", "staged_preemptions",
         staged["sjf_preemptive"]["preemptions"])
    emit("serving", "staged_light_p95_rounds_speedup",
         staged["light_p95_rounds_speedup"])
    emit("serving", "staged_light_p95_speedup",
         staged["light_p95_speedup"])

    # ---------------- result cache on a repeated-query workload ----------
    reps = 2 if quick else 3
    qs = [jnp.asarray(p, jnp.int32) for p in light]  # deduped above
    eng_nc = make_bfs_engine(g, capacity=C)
    eng_c = make_bfs_engine(g, capacity=C, result_cache=256)
    for e in (eng_nc, eng_c):
        # warm with queries DISJOINT from qs so the cache engine's first
        # pass over qs is all misses (heavy pairs never reappear)
        _warm(e, [jnp.asarray(p, jnp.int32) for p in heavy[:2]])
        _reset_stats(e)
    cache: dict = {}
    for tag, eng in (("off", eng_nc), ("on", eng_c)):
        t0 = time.perf_counter()
        for _ in range(reps):
            for q in qs:
                eng.submit(q)
            eng.run_until_drained()
        cache[tag] = dict(
            wall_s=time.perf_counter() - t0,
            rounds=eng.stats.rounds,
            cache_hits=eng.stats.cache_hits,
        )
    assert cache["on"]["cache_hits"] == (reps - 1) * len(qs)
    cache["speedup"] = cache["off"]["wall_s"] / cache["on"]["wall_s"]
    out["cache"] = cache
    emit("serving", "cache_hits", cache["on"]["cache_hits"])
    emit("serving", "cache_speedup", cache["speedup"])

    _merge_bench_json({"serving": out})
    RESULTS.setdefault("serving", {})["json"] = out


# ------------------------------------------------------------- sharded
def bench_sharded(quick=False):
    """Mesh-sharded super-rounds (DESIGN.md §6).

    PPSP (BFS) and label-pruned reachability through ``QuegelEngine(mesh=…)``
    — the WHOLE fused round (admission + supersteps + done reduction) as one
    shard_map — swept over partition ∈ {dst, src} × mesh size, against the
    single-device engine on the same queries (results asserted identical
    in-run).  Each cell reports rounds/sec, queries/sec and the modeled
    per-device collective bytes per round (``collective_bytes_per_round``:
    round-entry state gather + one collective per propagate per superstep).

    Needs >1 device: run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (CPU hosts).
    On one device the table is skipped without touching the committed JSON.
    """
    import jax

    from repro.apps.ppsp import make_bfs_engine
    from repro.apps.reach import build_reach_index, make_reach_engine, scc_condense
    from repro.core.graph import barabasi_albert, random_graph
    from repro.launch.mesh import make_mesh

    ndev = len(jax.devices())
    if ndev < 2:
        print("# sharded bench needs >1 device: set "
              "XLA_FLAGS=--xla_force_host_platform_device_count=8 — skipping")
        return
    sizes = [w for w in (2, 4, 8) if w <= ndev]
    out: dict = {
        "meta": {
            "jax": jax.__version__,
            "backend": jax.default_backend(),
            "devices": ndev,
            "quick": bool(quick),
        },
    }

    def measure(mk, qs):
        eng = mk()
        _warm(eng, qs[: max(2, min(4, len(qs)))])
        m, res = _measure_drain(eng, qs)
        rmap = {q: {k: np.asarray(v).tolist() for k, v in r.items()}
                for q, r in res.items()}
        eng._results.clear()
        return m, rmap, eng

    def sweep(tag, g, mk_single, mk_sharded, qs):
        cells: dict = {}
        base, base_map, _ = measure(mk_single, qs)
        cells["single"] = base
        emit("sharded", f"{tag}_single_rounds_per_s", base["super_rounds_per_sec"])
        for part in ("dst", "src"):
            cells[part] = {}
            for w in sizes:
                if g.n % w:
                    continue
                mesh = make_mesh((w,), ("w",))
                m, rmap, eng = measure(
                    lambda part=part, mesh=mesh: mk_sharded(mesh, part), qs
                )
                assert rmap == base_map, f"sharded {tag} {part} w{w} changed results"
                coll = eng.collective_bytes_per_round()
                m["collective"] = coll
                cells[part][f"w{w}"] = m
                emit("sharded", f"{tag}_{part}_w{w}_rounds_per_s",
                     m["super_rounds_per_sec"])
                emit("sharded", f"{tag}_{part}_w{w}_coll_bytes_per_round",
                     coll["round_total_bytes"])
        out[tag] = cells

    # ---------------- PPSP (BFS), power-law graph ------------------------
    g = barabasi_albert(512 if quick else 1024, 3, seed=7).padded(max(sizes))
    pairs = _pairs(g.n_real, 8 if quick else 16, seed=8)
    qs = [jnp.asarray(p, jnp.int32) for p in pairs]
    sweep(
        "ppsp", g,
        lambda: make_bfs_engine(g, capacity=8),
        lambda mesh, part: make_bfs_engine(g, capacity=8, mesh=mesh, partition=part),
        qs,
    )

    # ---------------- reachability (label-pruned BiBFS, two views) -------
    gr = random_graph(400 if quick else 1200, 2.5, seed=11)
    _, dag = scc_condense(gr)
    dag = dag.padded(max(sizes))  # pad BEFORE the index so |V| matches
    idx = build_reach_index(dag)
    pr = _pairs(dag.n_real, 8 if quick else 16, seed=12)
    qr = [jnp.asarray(p, jnp.int32) for p in pr]
    sweep(
        "reach", dag,
        lambda: make_reach_engine(dag, idx, capacity=8),
        lambda mesh, part: make_reach_engine(
            dag, idx, capacity=8, mesh=mesh, partition=part
        ),
        qr,
    )

    _merge_bench_json({"sharded": out})
    RESULTS.setdefault("sharded", {})["json"] = out


# ----------------------------------------------------------- kernel bench
def bench_kernels(quick=False):
    """Frontier-propagation backends, wall time on the default platform.
    Off a TPU the Pallas kernel runs in the interpreter, and its key says
    so (``pallas_interpret_us``)."""
    import jax

    from repro.core.graph import barabasi_albert
    from repro.core.semiring import INF, MIN_RIGHT
    from repro.kernels import frontier, ref

    g = barabasi_albert(1024 if not quick else 256, 4, seed=19)
    rng = np.random.default_rng(20)
    x = rng.integers(0, 30, (8, g.n)).astype(np.int32)
    x[rng.random(x.shape) < 0.5] = INF
    x = jnp.asarray(x)
    bs = g.to_blocks(128, MIN_RIGHT.add_id)

    f_coo = jax.jit(lambda x: ref.propagate_coo(g, MIN_RIGHT, x))
    f_blk = jax.jit(lambda x: ref.propagate_blocks_ref(bs, MIN_RIGHT, x))
    for name, fn in (("coo", f_coo), ("blocks_ref", f_blk)):
        fn(x).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(10):
            fn(x).block_until_ready()
        emit("kernels", f"{name}_us", (time.perf_counter() - t0) / 10 * 1e6)
    t0 = time.perf_counter()
    frontier.propagate_blocks(bs, MIN_RIGHT, x).block_until_ready()
    key = "pallas_us" if jax.default_backend() == "tpu" else "pallas_interpret_us"
    emit("kernels", key, (time.perf_counter() - t0) * 1e6)
    emit("kernels", "edges", g.num_edges)


# ------------------------------------------------------------- recovery
def bench_recovery(quick=False):
    """Crash tolerance (DESIGN.md §10): what durability costs and buys.

    Three sub-tables, merged into ``BENCH_quegel.json`` under ``recovery``:

    * ``restore`` — cold boot (build the Hub² index through the engine)
      vs durable-store restore (``load_or_build_hub_index`` hit) for a
      query-ready serving state.  The store hit runs ZERO
      index-construction super-rounds (asserted); restore must be ≥ 5x
      faster than cold start (asserted in non-quick runs).
    * ``journal`` — WAL + snapshot overhead on a mixed light/heavy BFS
      drain at cadences {off, WAL-only, snapshot every 8, every 1}, with
      qid→result maps asserted identical across cadences (journaling and
      snapshot/resume must never change answers) plus journal bytes and
      record counts per cadence.
    * ``mttr`` — mean time to recovery: a journaled drain is cut mid-
      flight; measured are journal replay time on a fresh engine and the
      wall time until that engine retires its first query (the serving
      gap a crash actually causes).
    """
    import shutil
    import tempfile

    import jax

    from repro.apps.hub2 import load_or_build_hub_index, make_hub2_engine
    from repro.apps.ppsp import make_bfs_engine
    from repro.core.graph import barabasi_albert, grid_terrain
    from repro.core.runtime import QueryJournal
    from repro.core.store import Store
    from repro.launch.supervise import recover

    out: dict = {
        "meta": {
            "jax": jax.__version__,
            "backend": jax.default_backend(),
            "quick": bool(quick),
        },
    }
    tmp = tempfile.mkdtemp(prefix="bench_recovery_")
    try:
        # ---------------- cold start vs store restore -----------------------
        g = barabasi_albert(300 if quick else 1200, 3, seed=41)
        root = os.path.join(tmp, "store")
        t0 = time.perf_counter()
        idx, info = load_or_build_hub_index(Store(root), g, k=16, capacity=8)
        cold_s = time.perf_counter() - t0
        assert info["built"] and info["index_rounds"] > 0
        t0 = time.perf_counter()
        idx2, info2 = load_or_build_hub_index(Store(root), g, k=16, capacity=8)
        restore_s = time.perf_counter() - t0
        assert not info2["built"] and info2["index_rounds"] == 0
        q = jnp.asarray([0, g.n_real - 1], jnp.int32)
        want = make_hub2_engine(g, idx, capacity=1).query(q)
        got = make_hub2_engine(g, idx2, capacity=1).query(q)
        assert int(got["dist"]) == int(want["dist"])
        entry = os.path.join(root, "index")
        out["restore"] = dict(
            cold_start_s=cold_s,
            restore_s=restore_s,
            speedup=cold_s / restore_s,
            index_rounds_cold=info["index_rounds"],
            index_rounds_restore=0,
            store_bytes=sum(
                os.path.getsize(os.path.join(entry, f))
                for f in os.listdir(entry)
            ),
        )
        emit("recovery", "cold_start_s", cold_s)
        emit("recovery", "restore_s", restore_s)
        emit("recovery", "restore_speedup", out["restore"]["speedup"])
        if not quick:
            assert out["restore"]["speedup"] >= 5.0, out["restore"]

        # ---------------- journal + snapshot overhead -----------------------
        rows, cols = (10, 12) if quick else (20, 24)
        g2, _ = grid_terrain(rows, cols, seed=42)
        rng = np.random.default_rng(43)
        subs = [(jnp.asarray([int(a), int(b)], jnp.int32), dict(budget=64))
                for a, b in rng.integers(0, g2.n_real, (12 if quick else 24, 2))]
        subs += [(jnp.asarray([0, g2.n_real - 1], jnp.int32),
                  dict(budget=4 * (rows + cols)))] * 2  # heavies

        def run_cadence(tag, cadence):
            eng = make_bfs_engine(g2, capacity=4)
            if cadence:
                # snapshots resume through a separate jitted dispatch
                # (admit_batch_resume) specialized per resume-batch size:
                # warm it at every size up to capacity, else the snapshot
                # cadences get charged its one-time compiles
                eng.runtime.journal = QueryJournal(
                    os.path.join(tmp, f"warm_{tag}.wal"))
                eng.runtime.snapshot_every = 1
            _warm(eng, [q for q, _ in subs[:6]])
            if cadence:
                eng.runtime.journal.close()
                eng.runtime.journal = None
                eng.runtime.snapshot_every = 0
            jp = None
            if cadence is not None:
                jp = os.path.join(tmp, f"j_{tag}.wal")
                eng.runtime.journal = QueryJournal(jp)
                eng.runtime.snapshot_every = cadence
            _reset_stats(eng)
            for q, kw in subs:
                eng.submit(q, **kw)
            t0 = time.perf_counter()
            eng.run_until_drained()
            wall = time.perf_counter() - t0
            res_map = {
                qid: {k: np.asarray(v).tolist() for k, v in r.items()}
                for qid, r in eng.runtime.results.items()
            }
            j = eng.runtime.journal
            cell = dict(
                wall_s=wall,
                rounds=eng.runtime.stats.rounds,
                snapshots=eng.runtime.stats.snapshots,
                journal_bytes=j.bytes_written if j else 0,
                journal_records=j.records_written if j else 0,
            )
            if j:
                j.close()
            return cell, res_map

        cadences = [("off", None), ("wal", 0), ("snap8", 8), ("snap1", 1)]
        jout: dict = {}
        base_map = None
        for tag, cadence in cadences:
            cell, res_map = run_cadence(tag, cadence)
            if base_map is None:
                base_map = res_map
            cell["results_match_off"] = res_map == base_map
            assert cell["results_match_off"], (
                f"journal cadence {tag} changed query results"
            )
            cell["overhead_pct"] = 100.0 * (
                cell["wall_s"] / jout["off"]["wall_s"] - 1.0
            ) if tag != "off" else 0.0
            jout[tag] = cell
            emit("recovery", f"journal_{tag}_wall_s", cell["wall_s"])
            emit("recovery", f"journal_{tag}_bytes", cell["journal_bytes"])
        out["journal"] = jout

        # ---------------- MTTR: crash mid-drain, measure the gap ------------
        jp = os.path.join(tmp, "mttr.wal")
        eng1 = make_bfs_engine(g2, capacity=4)
        _warm(eng1, [q for q, _ in subs[:2]])
        eng1.runtime.journal = QueryJournal(jp)
        eng1.runtime.snapshot_every = 4
        for i, (q, kw) in enumerate(subs):
            eng1.submit(q, qid=i, **kw)
        crash_round = 4
        for _ in range(crash_round):
            eng1.runtime.run_round()
        done_at_crash = len(eng1.runtime.results)
        eng1.runtime.journal.close()  # the process "dies" here

        t0 = time.perf_counter()
        eng2 = make_bfs_engine(g2, capacity=4)  # cold boot (includes jit)
        eng2.runtime.journal = QueryJournal(jp)
        info = recover(eng2.runtime, jp)
        replay_s = time.perf_counter() - t0
        rounds = 0
        while len(eng2.runtime.results) <= done_at_crash:
            eng2.runtime.run_round()
            rounds += 1
            assert rounds < 10_000
        mttr_s = time.perf_counter() - t0
        eng2.run_until_drained()
        assert len(eng2.runtime.results) == len(subs)
        out["mttr"] = dict(
            crash_round=crash_round,
            replayed_done=info["replayed_done"],
            resumed_from_snapshot=info["resumed_from_snapshot"],
            resubmitted=info["resubmitted"],
            replay_s=replay_s,
            mttr_s=mttr_s,
            rounds_to_first_retirement=rounds,
        )
        emit("recovery", "mttr_replay_s", replay_s)
        emit("recovery", "mttr_s", mttr_s)
        emit("recovery", "mttr_rounds_to_first_retirement", rounds)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    _merge_bench_json({"recovery": out})
    RESULTS.setdefault("recovery", {})["json"] = out


# ------------------------------------------------------------- loadgen
def bench_loadgen(quick=False):
    """Open-loop serving under sustained offered load (DESIGN.md §11).

    Four sub-tables, merged into ``BENCH_quegel.json`` under ``loadgen``:

    * ``curves`` — latency-throughput curves on a mixed light/heavy PPSP
      workload: Poisson arrivals on a deterministic virtual clock (1 tick
      = 1 super-round), swept over offered rate for scheduler ∈ {fifo,
      sjf} x replicas ∈ {1, 2, 4}, plus deadline and preemptive sjf at
      R=1.  Each cell: p50/p95/p99 latency (ticks), achieved-vs-offered
      qps, delivered capacity (``busy_qps``), backlog high-water mark and
      the wall-time queue-wait/service split; each curve carries its
      saturation knee.  In-run asserts: every configuration keeps up
      (busy_qps >= offered) at the lowest sweep point, and R=4 serves a
      rate far beyond the R=1 knee.
    * ``arrivals`` — poisson vs constant vs bursty MMPP at the same mean
      rate (burstiness shows up as tail latency, not throughput).
    * ``routing`` — hash-affine vs round-robin vs p2c on a Zipf-skewed
      repeated-query mix over Hub^2 replicas booted from ONE durable
      store read (zero per-replica index rebuilds), each replica with a
      small per-replica LRU result cache.  Affinity keeps each LRU hot on
      1/N of the key space; round-robin churns all of them.  In-run
      asserts: affine hit rate strictly beats round-robin, and every
      policy's merged result map is IDENTICAL to a single engine run.
    * ``wall`` (full runs only) — one wall-clock-mode point, same
      machinery against real time.
    """
    import shutil
    import tempfile

    import jax

    from repro.apps.hub2 import build_hub_index, make_hub2_engine
    from repro.apps.ppsp import make_bfs_engine
    from repro.core.graph import barabasi_albert, grid_terrain
    from repro.core.store import Store, save_engine_store
    from repro.launch import env as envmod
    from repro.launch.loadgen import (
        make_arrivals, run_open_loop, saturation_knee, sweep_qps)
    from repro.launch.router import ReplicaPool, boot_replicas_from_store

    print(f"# env: {envmod.describe()}")
    out: dict = {
        "meta": {
            "jax": jax.__version__,
            "backend": jax.default_backend(),
            "quick": bool(quick),
            "clock": "virtual (1 tick = 1 super-round)",
        },
        "curves": {},
        "arrivals": {},
        "routing": {},
    }

    # ---------------- latency-throughput curves --------------------------
    rows, cols = (10, 12) if quick else (14, 16)
    g, _ = grid_terrain(rows, cols, seed=51)
    C = 4  # slots per replica
    rng = np.random.default_rng(52)
    n_q = 20 if quick else 64
    budget_heavy = 4 * (rows + cols)
    items = []
    for i in range(n_q):
        if i % 4 == 0:  # heavy: corner-to-corner, ~rows+cols supersteps
            a = int(rng.integers(0, cols // 2))
            b = g.n_real - 1 - int(rng.integers(0, cols // 2))
            items.append((jnp.asarray([a, b], jnp.int32),
                          dict(budget=budget_heavy, deadline=1e6)))
        else:           # light: horizontal neighbors, 1-2 supersteps
            v = int(rng.integers(0, g.n_real - 2))
            v -= 1 if (v + 1) % cols == 0 else 0
            items.append((jnp.asarray([v, v + 1], jnp.int32),
                          dict(budget=16, deadline=1.0)))
    rates = (0.25, 2.0) if quick else (0.25, 0.5, 1.0, 2.0, 4.0)
    replica_counts = (1, 2) if quick else (1, 2, 4)
    configs = [("fifo", False, r) for r in replica_counts]
    configs += [("sjf", False, r) for r in replica_counts]
    if not quick:
        configs += [("deadline", False, 1), ("sjf", True, 1)]
    out["meta"].update(capacity=C, n_queries=n_q, rates=list(rates),
                       graph=f"grid {rows}x{cols}")
    knees: dict = {}
    for sched, preemptive, R in configs:
        tag = f"{sched}{'_preemptive' if preemptive else ''}"
        pool = ReplicaPool([
            make_bfs_engine(g, capacity=C, scheduler=sched,
                            preemptive=preemptive)
            for _ in range(R)
        ])
        # warm every replica's round variants off-clock
        for q, kw in items[:3]:
            pool.submit(q, **kw)
        pool.drain()
        swept = sweep_qps(lambda: pool, items, rates, process="poisson",
                          seed=53)
        curve = swept["curve"]
        for rate, cell in curve.items():
            assert cell["statuses"].get("DONE", 0) == n_q, (tag, rate, cell)
        low = min(curve)
        assert curve[low]["busy_qps"] >= low, (
            f"{tag} R={R} cannot keep up at the lowest offered rate: "
            f"delivered {curve[low]['busy_qps']:.3f} < offered {low}"
        )
        out["curves"].setdefault(tag, {})[f"R{R}"] = swept
        knees[(tag, R)] = swept["knee"]
        emit("loadgen", f"{tag}_R{R}_knee_qps", swept["knee"])
        hi = max(curve)
        emit("loadgen", f"{tag}_R{R}_p99_at_q{hi}", curve[hi]["lat_p99"])
    if not quick:
        # replicas buy throughput: R=4 keeps up at a rate R=1 has dropped
        for sched in ("fifo", "sjf"):
            assert knees[(sched, 4)] >= knees[(sched, 1)], (sched, knees)

    # ---------------- arrival-process A/B at one rate --------------------
    rate = 1.0
    n_a = 16 if quick else 48
    arr_items = items[:n_a] if len(items) >= n_a else items * 3
    arr_items = arr_items[:n_a]
    for process in ("poisson", "constant", "mmpp"):
        pool = ReplicaPool([
            make_bfs_engine(g, capacity=C, scheduler="sjf")
            for _ in range(2)
        ])
        for q, kw in arr_items[:3]:
            pool.submit(q, **kw)
        pool.drain()
        for rt in (rep.runtime for rep in pool.replicas):
            rt.stats = type(rt.stats)()
        arr = make_arrivals(process, rate, n_a, seed=54)
        res = run_open_loop(pool, arr_items, arr, offered_qps=rate)
        out["arrivals"][process] = res.summary()
        emit("loadgen", f"arr_{process}_p99", res.latency_percentile(99))

    # ---------------- routing A/B: affine vs rr vs p2c on Zipf -----------
    gb = barabasi_albert(200 if quick else 600, 3, seed=55)
    idx = build_hub_index(gb, k=16, capacity=8)
    R = 2 if quick else 4
    cache_size = 8 if quick else 16
    n_keys = 12 if quick else 48   # distinct queries; K/R fits one LRU,
    n_zipf = 60 if quick else 240  # the full key set does not
    tmp = tempfile.mkdtemp(prefix="bench_loadgen_")
    try:
        store = Store(os.path.join(tmp, "store"))
        save_engine_store(store, gb, index=idx)
        rngz = np.random.default_rng(56)
        keys = [(int(a), int(b))
                for a, b in rngz.integers(0, gb.n_real, (n_keys, 2))]
        p = 1.0 / np.arange(1, n_keys + 1) ** 1.1
        p /= p.sum()
        picks = rngz.choice(n_keys, n_zipf, p=p)
        zipf_items = [jnp.asarray(keys[k], jnp.int32) for k in picks]
        out["routing"]["meta"] = dict(
            replicas=R, cache_size=cache_size, n_keys=n_keys,
            n_queries=n_zipf, zipf_s=1.1, store_loads=1,
        )

        def boot_pool(policy):
            t0 = time.perf_counter()
            reps = boot_replicas_from_store(
                store,
                lambda i, parts: make_hub2_engine(
                    parts["graph"], parts["index"], capacity=2,
                    result_cache=cache_size,
                ),
                R,
            )
            boot_s = time.perf_counter() - t0
            # zero per-replica index rebuild: nobody ran a single round
            assert all(r.runtime.stats.rounds == 0 for r in reps)
            return ReplicaPool(reps, policy=policy), boot_s

        # single-engine baseline for the identity assert
        single = make_hub2_engine(gb, idx, capacity=2,
                                  result_cache=cache_size)
        for q in zipf_items:
            single.submit(q)
        single.run_until_drained()
        norm = lambda res: {
            qid: {k: np.asarray(v).tolist() for k, v in sorted(r.items())}
            for qid, r in res.items()
        }
        base_map = norm(single.runtime.results)

        hits = {}
        for policy in ("affine", "rr", "p2c"):
            pool, boot_s = boot_pool(policy)
            arr = make_arrivals("constant", 2.0, n_zipf, seed=57)
            res = run_open_loop(pool, zipf_items, arr, offered_qps=2.0)
            assert norm(pool.results) == base_map, (
                f"router policy {policy!r} changed the merged result map"
            )
            cell = res.summary()
            cell.update(pool.stats_summary())
            cell["boot_s"] = boot_s
            cell["hit_rate"] = pool.cache_hits / n_zipf
            cell["results_match_single"] = True
            out["routing"][policy] = cell
            hits[policy] = pool.cache_hits
            emit("loadgen", f"routing_{policy}_hit_rate", cell["hit_rate"])
            emit("loadgen", f"routing_{policy}_balance", cell["balance"])
        assert hits["affine"] > hits["rr"], (
            "hash-affine routing must beat round-robin on cache hits "
            f"(affine={hits['affine']}, rr={hits['rr']})"
        )
        out["routing"]["affine_vs_rr_hit_ratio"] = (
            hits["affine"] / max(hits["rr"], 1)
        )
        emit("loadgen", "routing_affine_vs_rr_hit_ratio",
             out["routing"]["affine_vs_rr_hit_ratio"])
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    # ---------------- one wall-clock-mode point (full runs) --------------
    if not quick:
        eng = make_bfs_engine(g, capacity=C, scheduler="sjf")
        for q, kw in items[:3]:
            eng.submit(q, **kw)
        eng.run_until_drained()
        eng.stats = type(eng.stats)()
        wall_items = items[:24]
        arr = make_arrivals("poisson", 20.0, len(wall_items), seed=58)
        res = run_open_loop(eng, wall_items, arr, clock="wall",
                            offered_qps=20.0)
        out["wall"] = res.summary()
        emit("loadgen", "wall_p95_s", res.latency_percentile(95))

    _merge_bench_json({"loadgen": out})
    RESULTS.setdefault("loadgen", {})["json"] = out


# ------------------------------------------------------------- mutation
def bench_mutation(quick=False):
    """Incremental mutation vs full rebuild (DESIGN.md §12).

    For delta sizes from 1 edge up to 10% of |E| on a BA graph with a
    Hub^2 index, time the two ways of absorbing a batched edge delta:

    * ``incremental`` — ``Graph.apply_delta`` (CSR/COO splice) +
      ``update_blocks`` on the touched dst-block rows + fixed-hub
      ``maintain_hub_index`` (eager batched BFS for affected hubs only).
    * ``rebuild`` — ``Graph.from_edges`` from the merged edge arrays +
      ``to_blocks`` from scratch + canonical ``build_hub_index`` (hubs
      re-picked; runs the k indexing queries through a freshly built
      engine, so this timing INCLUDES the engine's per-graph trace/compile
      cost — which is exactly what a serving deployment pays today if it
      rebuilds on every delta, and why the incremental path exists).

    Emits per-size incremental/rebuild wall, speedup and affected-hub
    counts, the measured crossover fraction (smallest tested delta where
    the rebuild wins), and a ``parity_ok`` flag: Hub^2 answers on the
    incrementally-maintained index must match ground-truth BFS distances
    on the mutated graph.  In-run asserts: parity always; at <= 1% deltas
    the incremental path must win by >= 5x (>= 1x under --quick, where
    the graph is small enough that constant overheads blur the ratio).
    """
    from repro.apps import hub2
    from repro.core.graph import Graph, barabasi_albert
    from repro.core.semiring import INF, MIN_RIGHT

    g = barabasi_albert(600 if quick else 1500, 3, seed=21)
    k = 8 if quick else 16
    E = g.num_edges
    emit("mutation", "n", g.n)
    emit("mutation", "edges", E)
    emit("mutation", "hubs", k)
    idx = hub2.build_hub_index(g, k)
    bs = g.to_blocks(64, MIN_RIGHT.add_id)
    rng = np.random.default_rng(22)
    present = set(zip(np.asarray(g.src).tolist(), np.asarray(g.dst).tolist()))

    def symmetric_delta(rows):
        """~rows delta rows, half adds half deletes, kept symmetric (the
        BA graph is undirected: every logical edge is two arcs)."""
        n_add = max(1, rows // 4)  # logical adds -> 2 arcs each
        n_del = max(1, rows // 4)
        adds, seen = [], set()
        while len(adds) < n_add:
            a, b = (int(v) for v in rng.integers(0, g.n_real, 2))
            if a == b or (a, b) in present or (a, b) in seen or (b, a) in seen:
                continue
            seen.add((a, b))
            adds += [(a, b), (b, a)]
        es, ed = np.asarray(g.src), np.asarray(g.dst)
        dels, used = [], set()
        for i in rng.permutation(len(es)):
            s, d = int(es[i]), int(ed[i])
            if s < d and s not in used and d not in used:
                dels += [(s, d), (d, s)]
                used |= {s, d}
            if len(dels) >= 2 * n_del:
                break
        return g.make_delta(adds, dels)

    def bfs_dist(graph, s):
        row = np.asarray(graph.csr_row)
        cdst = np.asarray(graph.csr_dst)
        dist = np.full(graph.n, INF, np.int64)
        dist[s] = 0
        frontier = [s]
        d = 0
        while frontier:
            d += 1
            nxt = []
            for u in frontier:
                for v in cdst[row[u]:row[u + 1]]:
                    if dist[v] >= INF:
                        dist[v] = d
                        nxt.append(int(v))
            frontier = nxt
        return dist

    # warm the relabel path's jnp op caches off-clock (the first eager
    # dispatch pays one-time lowering, ~1s — not a per-delta cost), same
    # idea as _hotpath_cell's engine warmup
    warm = symmetric_delta(2)
    gw = g.apply_delta(warm)
    gw.update_blocks(bs, MIN_RIGHT.add_id, warm.touched_dst_blocks(bs.block))
    hub2.maintain_hub_index(gw, idx, warm, threshold=1.1)

    sizes = [("1edge", 2), ("0.1pct", max(4, E // 1000)),
             ("1pct", max(4, E // 100)), ("10pct", max(4, E // 10))]
    out: dict = dict(n=g.n, edges=E, k=k, sizes={})
    crossover = None
    for label, rows in sizes:
        delta = symmetric_delta(rows)
        frac = delta.size / E

        t_inc = math.inf
        for _ in range(2):  # best-of-2, timer noise on a busy CPU
            t0 = time.perf_counter()
            g1 = g.apply_delta(delta)
            bs1 = g1.update_blocks(bs, MIN_RIGHT.add_id,
                                   delta.touched_dst_blocks(bs.block))
            idx1, info = hub2.maintain_hub_index(g1, idx, delta,
                                                 threshold=1.1)
            t_inc = min(t_inc, time.perf_counter() - t0)

        t_reb = math.inf
        for _ in range(2):
            t0 = time.perf_counter()
            g2 = Graph.from_edges(np.asarray(g1.src), np.asarray(g1.dst),
                                  g1.n_real, w=np.asarray(g1.w))
            bs2 = g2.to_blocks(64, MIN_RIGHT.add_id)
            idx2 = hub2.build_hub_index(g2, k)
            t_reb = min(t_reb, time.perf_counter() - t0)
        del bs1, bs2, idx2

        speedup = t_reb / t_inc
        out["sizes"][label] = dict(
            delta_rows=delta.size, frac=frac, inc_ms=t_inc * 1e3,
            rebuild_ms=t_reb * 1e3, speedup=speedup,
            affected_hubs=info["affected_hubs"],
        )
        emit("mutation", f"inc_ms_{label}", t_inc * 1e3)
        emit("mutation", f"rebuild_ms_{label}", t_reb * 1e3)
        emit("mutation", f"speedup_{label}", speedup)
        emit("mutation", f"affected_hubs_{label}", info["affected_hubs"])
        if speedup < 1.0 and crossover is None:
            crossover = frac

        if label == "1edge":
            # answer parity: Hub^2 over the incrementally-maintained index
            # vs ground-truth BFS on the mutated graph
            eng = hub2.make_hub2_engine(g1, idx1, capacity=4)
            pairs = [(int(a), int(b))
                     for a, b in rng.integers(0, g.n_real, (5, 2))]
            qids = {eng.submit(jnp.asarray(p, jnp.int32)): p for p in pairs}
            res = eng.run_until_drained()
            for qid, (s, t) in qids.items():
                want = int(bfs_dist(g1, s)[t])
                got = int(np.asarray(res[qid]["dist"]))
                assert got == want, (s, t, got, want)
            emit("mutation", "parity_ok", 1)
            out["parity_ok"] = True

    out["crossover_frac"] = crossover  # None: rebuild never won in range
    emit("mutation", "crossover_frac",
         -1.0 if crossover is None else crossover)
    floor = 1.0 if quick else 5.0
    for label in ("1edge", "0.1pct", "1pct"):
        sp = out["sizes"][label]["speedup"]
        assert sp >= floor, (
            f"incremental path lost its edge at {label}: {sp:.2f}x < "
            f"{floor}x (see DESIGN.md §12)")

    out["serving_ab"] = _mutation_serving_ab(quick)
    _merge_bench_json({"mutation": out})
    RESULTS.setdefault("mutation", {})["json"] = out


def _mutation_serving_ab(quick=False) -> dict:
    """Serving-path A/B (DESIGN.md §12 addendum): how each edition strategy
    absorbs an in-capacity delta while a query is IN FLIGHT.

    Per mutation (10-delta sequence, same deltas for every mode):

    * ``mutate_to_first_answer_ms`` — apply_delta + submit one fresh query
      (pinned to the new version) + rounds until it answers.  Constant
      closures pay the new edition's round compile inside the first
      dispatch; arg-carried reuses the shared compiled round (headline:
      >= 5x better, asserted); warmup pays the remaining compile its head
      start did not cover.
    * ``old_answer_ms`` — mutation until the IN-FLIGHT old-version query
      answers.  Warmup's differentiator: the old edition keeps serving
      rounds while the warm thread compiles, so service never stalls;
      constant mode's old query is stuck behind the same slot_round that
      is compiling the new edition.
    * ``apply_ms`` — the apply_delta call itself (always splice-fast:
      compiles are lazy or on the warm thread, never in apply_delta).
    * ``compiles`` — jit compiles across the whole 10-mutation sequence
      (arg-carried: asserted ZERO, the compile-once pin).

    qid→result maps are asserted identical across the three modes, and
    the final graph's answers against a legacy-mode engine (the SPMD
    path's parity is pinned by tests/test_mutation.py's 8-device
    subprocess, which CI runs alongside this table).
    """
    from repro.apps.ppsp import make_bfs_engine
    from repro.core.graph import Graph, random_graph

    nc = 48 if quick else 96
    tail = 10 if quick else 14
    core = random_graph(nc, 3.0, seed=31, directed=True)
    s2 = np.concatenate([np.asarray(core.src), np.arange(nc, nc + tail - 1)])
    d2 = np.concatenate([np.asarray(core.dst), np.arange(nc + 1, nc + tail)])
    sg = Graph.from_edges(s2.astype(np.int32), d2.astype(np.int32), nc + tail)
    emit("mutation_serving", "n", sg.n)
    emit("mutation_serving", "edges", sg.num_edges)
    n_mut = 10  # the CI smoke's zero-recompile window (quick included)
    rng = np.random.default_rng(33)
    deltas = []
    for _ in range(n_mut):
        a, b = (int(v) for v in rng.integers(0, nc, 2))
        if a == b:
            b = (a + 1) % nc
        deltas.append((a, b))
    q_old = [nc, nc + tail - 1]  # tail walk: many rounds, stays in flight
    q_new = [0, nc + tail - 1]

    def run_mode(**kw):
        eng = make_bfs_engine(sg, capacity=4, **kw)
        wq = eng.submit(jnp.asarray(q_new, jnp.int32))
        eng.run_until_drained()  # v0 build+compile off-clock (hotpath's job)
        base_compiles = eng.stats.jit_compiles
        firsts, olds, applies = [], [], []
        resmap = {}
        for r, (a, b) in enumerate(deltas):
            oldq = eng.submit(jnp.asarray(q_old, jnp.int32))
            eng.run_round()  # in flight on the pre-mutation version
            t0 = time.perf_counter()
            eng.apply_delta(adds=[(a, b)])
            applies.append(time.perf_counter() - t0)
            t_old = time.perf_counter() if oldq in eng._results else None
            if kw.get("warmup"):
                # service continues while the warm thread compiles: keep
                # advancing the in-flight old-version query ON CLOCK
                while not eng.wait_warmup(timeout=0.0):
                    if bool(np.asarray(eng.runtime.live).any()):
                        eng.run_round()
                        if t_old is None and oldq in eng._results:
                            t_old = time.perf_counter()
                    else:
                        time.sleep(0.001)
            newq = eng.submit(jnp.asarray(q_new, jnp.int32))
            t_new = None
            while t_new is None or t_old is None:
                eng.run_round()
                now = time.perf_counter()
                if t_old is None and oldq in eng._results:
                    t_old = now
                if t_new is None and newq in eng._results:
                    t_new = now
            firsts.append(t_new - t0)
            olds.append(t_old - t0)
            resmap[f"old{r}"] = {k: np.asarray(v)
                                 for k, v in eng._results[oldq].items()}
            resmap[f"new{r}"] = {k: np.asarray(v)
                                 for k, v in eng._results[newq].items()}
        eng.run_until_drained()
        med = lambda xs: float(np.median(xs) * 1e3)
        return dict(
            mutate_to_first_answer_ms=med(firsts),
            old_answer_ms=med(olds),
            apply_ms=med(applies),
            compiles=eng.stats.jit_compiles - base_compiles,
        ), resmap, eng.graph

    ab, maps = {}, {}
    for mode, kw in [("constant", {}),
                     ("arg_carried", dict(arg_carried=True)),
                     ("warmup", dict(warmup=True))]:
        ab[mode], maps[mode], g_final = run_mode(**kw)
        for k, v in ab[mode].items():
            emit("mutation_serving", f"{k}_{mode}", v)

    # parity: identical qid→result maps across all three serving modes
    for mode in ("arg_carried", "warmup"):
        assert set(maps[mode]) == set(maps["constant"])
        for key, want in maps["constant"].items():
            got = maps[mode][key]
            assert set(got) == set(want), (mode, key)
            for f in want:
                np.testing.assert_array_equal(got[f], want[f], err_msg=(
                    f"{mode} diverged from constant at {key}.{f}"))
    # ... and against the legacy baseline on the final graph
    leg = make_bfs_engine(g_final, capacity=2, legacy=True)
    lq = leg.submit(jnp.asarray(q_new, jnp.int32))
    lres = leg.run_until_drained()[lq]
    last = maps["constant"][f"new{n_mut - 1}"]
    for f in lres:
        np.testing.assert_array_equal(np.asarray(lres[f]), last[f])
    ab["parity_ok"] = True
    emit("mutation_serving", "parity_ok", 1)

    # the compile-once pin: zero recompiles across ten in-capacity deltas
    assert ab["arg_carried"]["compiles"] == 0, (
        "arg-carried mode recompiled on an in-capacity delta: "
        f"{ab['arg_carried']['compiles']} compiles")
    speedup = (ab["constant"]["mutate_to_first_answer_ms"]
               / ab["arg_carried"]["mutate_to_first_answer_ms"])
    ab["first_answer_speedup"] = speedup
    emit("mutation_serving", "first_answer_speedup", speedup)
    floor = 1.0 if quick else 5.0
    assert speedup >= floor, (
        f"arg-carried mutate-to-first-answer only {speedup:.2f}x better "
        f"than constant-closure (< {floor}x)")
    return ab


TABLES = {
    "hotpath": bench_hotpath,
    "mutation": bench_mutation,
    "loadgen": bench_loadgen,
    "recovery": bench_recovery,
    "sparsity": bench_sparsity,
    "serving": bench_serving,
    "sharded": bench_sharded,
    "table2": table2_interactive,
    "table3": table3_bfs_vs_bibfs,
    "table5": table5_hub2,
    "table7a": table7a_capacity,
    "table7b": table7b_scaling,
    "table8": table8_xml,
    "table10": table10_terrain,
    "table11": table11_reach,
    "table12": table12_keyword,
    "kernels": bench_kernels,
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default="runs/bench")
    ap.add_argument(
        "--assert-floor", type=float, default=None, metavar="X",
        help="regression gate: fail unless BENCH_quegel.json reports "
        "ab.speedup_super_rounds_per_sec >= X (run after --only hotpath)",
    )
    args = ap.parse_args()
    from repro.launch import env as _env

    print(f"# env: {_env.describe()}")
    print(f"# compile cache: {_env.enable_compile_cache()}")
    names = [args.only] if args.only else list(TABLES)
    for name in names:
        print(f"# --- {name} ---")
        t0 = time.perf_counter()
        TABLES[name](quick=args.quick)
        emit(name, "bench_wall_s", time.perf_counter() - t0)
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "results.json"), "w") as f:
        json.dump(RESULTS, f, indent=2)
    if args.assert_floor is not None:
        if "hotpath" not in names:
            print("# --assert-floor requires the hotpath table in this run")
            return 1
        speedup = RESULTS["hotpath"]["json"]["ab"]["speedup_super_rounds_per_sec"]
        if speedup < args.assert_floor:
            print(
                f"# REGRESSION: fused-vs-legacy speedup {speedup:.3f} "
                f"< floor {args.assert_floor}"
            )
            return 1
        print(f"# floor OK: {speedup:.3f} >= {args.assert_floor}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
