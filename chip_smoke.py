#!/usr/bin/env python3
"""Bring-up smoke run of the Quegel engine on a TPU.

Drives the serving path once through the entry points a user calls
(``make_bibfs_engine`` / ``make_terrain_engine`` -> ``QuegelEngine`` ->
``SlotRuntime``) at real graph size, and checks every answer against an
independent host reference (``scipy.sparse.csgraph``) on the same edges.
All data is generated in-process from ``--seed``.  Phases, in order, one
printed line each:

  device    the default device is a TPU (never falls back to the CPU);
            device kind, count, jax and libtpu versions
  ppsp      BiBFS on a Graph500-shaped random digraph, 2**22 vertices with
            edge factor 16 (~67M edges), capacity 8, engine defaults (the
            argument-carried round, donation on); 32 queries drained and 8
            more through pump()/poll()
  mutation  apply_delta with a query in flight: it answers on its admission
            version, later queries on the new one, and the compiled round
            is reused (0 recompiles)
  pallas    the paper's terrain SSSP on a 512x512 grid (262,144 vertices)
            with the compiled Pallas kernel (``tpu_custom_call`` in the
            round) against the coo backend on the same queries: identical
            distances, and both within float tolerance of Dijkstra

``--chips 4`` runs only the mesh-sharded BiBFS engine (partition dst) over
all four chips on the same graph, 8 queries (one per slot), against the
single-device engine on device 0 and scipy.

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Any failed
check raises, so the script exits non-zero before that line.

``--rehearse`` runs the same phases at a tiny size on whatever platform
JAX has (on the CPU the Pallas kernel is interpreted): the pre-flight check
before a chip run.

Usage:
    python3 chip_smoke.py [--seed S] [--scale N]
    python3 chip_smoke.py --chips 4
    JAX_PLATFORMS=cpu python3 chip_smoke.py --rehearse
    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python3 chip_smoke.py --chips 4 --rehearse
"""
from __future__ import annotations

import argparse
import gc
import importlib.metadata
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

# Import the program before touching JAX: without it there is nothing to
# smoke-test, and the script fails here with nothing on stdout.
from repro.apps.ppsp import make_bibfs_engine  # noqa: E402
from repro.apps.terrain import make_terrain_engine  # noqa: E402
from repro.core.graph import grid_terrain, random_graph  # noqa: E402
from repro.core.runtime import DONE  # noqa: E402
from repro.core.semiring import INF  # noqa: E402
from repro.launch.env import enable_compile_cache  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402
from scipy.sparse import csgraph, csr_matrix  # noqa: E402

GRAPH500_SCALE = 22  # Graph500 scale 22, edge factor 16
EDGE_FACTOR = 16
CAPACITY = 8
TERRAIN_SIDE = 512  # 262,144 vertices: a 2048-block tile table


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


_T0 = time.perf_counter()


def say(phase: str, **kv) -> None:
    kv["t"] = time.perf_counter() - _T0
    print(f"{phase}: " + " ".join(f"{k}={v}" for k, v in kv.items()), flush=True)


def peak_bytes(dev, key: str = "peak_bytes_in_use"):
    stats = dev.memory_stats()
    return None if stats is None else stats.get(key)


# ------------------------------------------------------------ references
def hop_matrix(n: int, src, dst) -> csr_matrix:
    return csr_matrix(
        (np.ones(len(src), np.int8), (np.asarray(src), np.asarray(dst))),
        shape=(n, n),
    )


def bfs_distances(a: csr_matrix, pairs) -> list[int]:
    """Hop distance d(s, t) per pair: one scipy BFS per distinct source, the
    predecessor chain walked back from t (INF when t is unreachable)."""
    out = {}
    for s in sorted({int(s) for s, _ in pairs}):
        _, pred = csgraph.breadth_first_order(
            a, s, directed=True, return_predecessors=True
        )
        for s_, t in pairs:
            if int(s_) != s:
                continue
            d, v = 0, int(t)
            while v != s:
                v = int(pred[v])
                if v < 0:
                    d = int(INF)
                    break
                d += 1
            out[(s, int(t))] = d
    return [out[(int(s), int(t))] for s, t in pairs]


def seeded_pairs(rng, n: int, k: int) -> np.ndarray:
    return rng.integers(0, n, (k, 2)).astype(np.int32)


# ----------------------------------------------------------------- phases
def phase_device(chips: int, rehearse: bool) -> dict:
    devs = jax.devices()
    d0 = devs[0]
    if not rehearse:
        check(d0.platform == "tpu",
              f"no TPU: jax.devices()[0].platform is {d0.platform!r}")
    check(len(devs) >= chips,
          f"--chips {chips} needs {chips} devices, jax sees {len(devs)}")
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "absent"
    say("device", platform=d0.platform, kind=repr(d0.device_kind),
        count=len(devs), jax=jax.__version__, libtpu=libtpu,
        compile_cache=enable_compile_cache())
    return dict(platform=d0.platform, kind=d0.device_kind, count=len(devs))


def build_graph(scale: int, seed: int):
    t0 = time.perf_counter()
    g = random_graph(1 << scale, EDGE_FACTOR, seed=seed)
    src, dst = np.asarray(g.src), np.asarray(g.dst)
    return g, src, dst, time.perf_counter() - t0


def drain(eng, pairs) -> list:
    qids = [eng.submit(p) for p in pairs]
    eng.run_until_drained()
    out = []
    for q in qids:
        st, res = eng.poll(q)
        check(st == DONE, f"query {q} ended {st}")
        out.append(int(res["dist"]))
    return out


def phase_ppsp(args, rng):
    """Returns the engine, the host edges and the reference matrix, for the
    mutation phase to continue on."""
    g, src, dst, t_gen = build_graph(args.scale, args.seed)
    n = g.n
    t0 = time.perf_counter()
    eng = make_bibfs_engine(g, capacity=CAPACITY)
    t_build = time.perf_counter() - t0
    del g  # the engine holds the graph now (capacity-padded, see engine)
    check(eng._arg_carried, "engine defaults did not pick the arg-carried round")
    check(eng.donate or jax.default_backend() == "cpu",
          "donation is off on an accelerator")
    a = hop_matrix(n, src, dst)

    pairs = seeded_pairs(rng, n, 32)
    t0 = time.perf_counter()
    got = drain(eng, pairs)
    t_drain = time.perf_counter() - t0
    warm_compiles = eng.stats.jit_compiles
    dists = bfs_distances(a, pairs)
    bad = [(tuple(p), g_, w_) for p, g_, w_ in zip(pairs, got, dists) if g_ != w_]
    check(not bad, f"ppsp distances differ from scipy: {bad[:5]}")

    extra = seeded_pairs(rng, n, 8)
    qids = [eng.submit(p) for p in extra]
    open_q = set(qids)
    t0 = time.perf_counter()
    while open_q:
        for qid, _, _ in eng.pump():
            open_q.discard(qid)
    t_pump = time.perf_counter() - t0
    polled = [eng.poll(q) for q in qids]
    check(all(st == DONE for st, _ in polled), f"pumped queries: {polled}")
    want = bfs_distances(a, extra)
    got = [int(res["dist"]) for _, res in polled]
    check(got == want, f"pumped distances {got} != scipy {want}")
    check(eng.stats.jit_compiles == warm_compiles,
          f"{eng.stats.jit_compiles - warm_compiles} compiles after warm-up")
    reduced = ("" if args.scale == GRAPH500_SCALE else
               f" (reduced from Graph500 scale {GRAPH500_SCALE})")
    say("ppsp", vertices=n, edges=eng.graph.num_edges,
        edge_capacity=eng.graph.edge_capacity, scale=f"{args.scale}{reduced}",
        queries=len(pairs) + len(extra), all_match_scipy=True,
        dist_range=f"{min(dists)}..{max(dists)}",
        rounds=eng.stats.rounds, jit_compiles=warm_compiles,
        compiles_after_warmup=eng.stats.jit_compiles - warm_compiles,
        peak_bytes_in_use=peak_bytes(jax.devices()[0]),
        gen_s=t_gen, build_s=t_build,
        drain32_s=t_drain, pump8_s=t_pump)
    return eng, src, dst, pairs, dists


def phase_mutation(eng, src, dst, pairs, d_old, rng) -> None:
    n = eng.graph.n
    i = int(np.argmax(d_old))
    s, t = (int(x) for x in pairs[i])
    check(3 <= d_old[i] < INF,
          f"no query pair far enough to stay in flight (max d={d_old[i]})")
    q_inflight = eng.submit(np.asarray([s, t], np.int32))
    eng.run_round()
    check(eng.poll(q_inflight) is None, "the in-flight query finished early")

    # adds: the edge s->t (its distance drops to 1 on the new version) and
    # a few random pairs; dels: a few existing edges
    adds = [(s, t)] + [
        (int(u), int(v)) for u, v in seeded_pairs(rng, n, 4) if u != v
    ]
    pick = rng.choice(len(src), 4, replace=False)
    dels = [(int(src[k]), int(dst[k])) for k in pick
            if (int(src[k]), int(dst[k])) != (s, t)]
    compiles0, v0 = eng.stats.jit_compiles, eng.graph.version
    t0 = time.perf_counter()
    info = eng.apply_delta(np.asarray(adds, np.int32), np.asarray(dels, np.int32))
    t_delta = time.perf_counter() - t0

    key = src.astype(np.int64) * n + dst
    gone = np.isin(key, [u * n + v for u, v in dels + adds])
    src2 = np.concatenate([src[~gone], [u for u, _ in adds]])
    dst2 = np.concatenate([dst[~gone], [v for _, v in adds]])
    a2 = hop_matrix(n, src2, dst2)

    after = np.concatenate([[[s, t]], seeded_pairs(rng, n, 7)]).astype(np.int32)
    got_after = drain(eng, after)
    st, res = eng.poll(q_inflight)
    check(st == DONE, f"in-flight query ended {st}")
    check(int(res["dist"]) == d_old[i],
          f"in-flight query answered {int(res['dist'])}, its admission "
          f"version says {d_old[i]}")
    want_after = bfs_distances(a2, after)
    check(got_after == want_after,
          f"post-delta distances {got_after} != scipy {want_after}")
    check(got_after[0] == 1, "the added edge s->t is not seen by new queries")
    recompiles = eng.stats.jit_compiles - compiles0
    check(recompiles == 0, f"{recompiles} recompiles across the delta")
    say("mutation", version=f"{v0}->{info['version']}", adds=len(adds),
        dels=len(dels), inflight_dist=int(res["dist"]),
        new_version_dist=got_after[0], post_queries_match_scipy=True,
        recompiles=recompiles, apply_delta_s=t_delta,
        peak_bytes_in_use=peak_bytes(jax.devices()[0]))


def round_has_kernel(eng) -> bool:
    """Whether the current edition's round lowers to a Mosaic kernel."""
    ed = eng._editions[eng._current_version]
    vmask = np.zeros((eng.capacity,), bool)
    text = ed.round.lower(eng._slots, vmask, *ed.round_args).as_text()
    return "tpu_custom_call" in text


def phase_pallas(args, rng) -> None:
    side = 32 if args.rehearse else TERRAIN_SIDE
    g, coords = grid_terrain(side, side, seed=args.seed)
    pairs = seeded_pairs(rng, g.n_real, CAPACITY)
    results, info = {}, {}
    for backend in ("pallas", "coo"):
        t0 = time.perf_counter()
        eng = make_terrain_engine(g, coords, capacity=CAPACITY,
                                  backend=backend, arg_carried=True)
        t_build = time.perf_counter() - t0
        t0 = time.perf_counter()
        qids = [eng.submit(p) for p in pairs]
        eng.run_until_drained()
        t_run = time.perf_counter() - t0
        polled = [eng.poll(q) for q in qids]
        check(all(st == DONE for st, _ in polled), f"{backend}: {polled}")
        results[backend] = np.asarray([res["dist"] for _, res in polled],
                                      np.float32)
        info[backend] = dict(rounds=eng.stats.rounds,
                             build_s=t_build, run_s=t_run)
        if backend == "pallas":
            bs = eng._backends["default"].tables["min_plus"]
            info["tiles"] = f"{bs.num_dst_blocks}x{bs.max_bpr}x{bs.block}^2"
            kernel = round_has_kernel(eng)
            if jax.default_backend() == "tpu":
                check(kernel, "the Pallas round holds no tpu_custom_call: "
                              "the kernel was interpreted")
            else:
                check(not kernel, "a Mosaic kernel in a round off the TPU")
            info["compiled_kernel"] = kernel
        del eng
        gc.collect()
    check(np.array_equal(results["pallas"], results["coo"]),
          f"pallas {results['pallas']} != coo {results['coo']}")
    src, dst, w = (np.asarray(x) for x in (g.src, g.dst, g.w))
    wm = csr_matrix((w.astype(np.float64), (src, dst)), shape=(g.n, g.n))
    ref = csgraph.dijkstra(wm, directed=True, indices=pairs[:, 0])
    want = ref[np.arange(len(pairs)), pairs[:, 1]]
    check(np.allclose(results["coo"], want, rtol=1e-4),
          f"terrain distances {results['coo']} vs Dijkstra {want}")
    say("pallas", vertices=g.n, edges=g.num_edges, tiles=info["tiles"],
        compiled_kernel=info["compiled_kernel"], pallas_equals_coo=True,
        matches_dijkstra=True, pallas=info["pallas"], coo=info["coo"],
        peak_bytes_in_use=peak_bytes(jax.devices()[0]))


def phase_mesh(args, rng) -> None:
    """--chips 4: the SPMD engine over every chip against the single-device
    engine on device 0 and scipy."""
    from repro.launch.mesh import host_device_mesh

    devs = jax.devices()
    g, src, dst, t_gen = build_graph(args.scale, args.seed)
    g = g.padded(len(devs))
    pairs = seeded_pairs(rng, g.n_real, CAPACITY)
    t0 = time.perf_counter()
    eng = make_bibfs_engine(g, capacity=CAPACITY, mesh=host_device_mesh(),
                            partition="dst")
    t_build = time.perf_counter() - t0
    gc.collect()
    built = [peak_bytes(d, "bytes_in_use") for d in devs]
    t0 = time.perf_counter()
    got_mesh = drain(eng, pairs)
    t_mesh = time.perf_counter() - t0
    rounds_mesh = eng.stats.rounds
    holders = set()
    for leaf in jax.tree.leaves(eng._edge_parts):
        holders |= {sh.device for sh in leaf.addressable_shards
                    if sh.data.size}
    check(holders == set(devs),
          f"edge partitions live on {len(holders)} of {len(devs)} devices")
    peaks = [peak_bytes(d) for d in devs]
    del eng
    gc.collect()

    t0 = time.perf_counter()
    one = make_bibfs_engine(g, capacity=CAPACITY)
    got_one = drain(one, pairs)
    t_one = time.perf_counter() - t0
    del one
    gc.collect()
    want = bfs_distances(hop_matrix(g.n, src, dst), pairs)
    check(got_mesh == got_one, "mesh engine differs from the single device")
    check(got_mesh == want, "mesh engine differs from scipy")
    say("mesh", devices=len(devs), partition="dst", vertices=g.n,
        edges=g.num_edges, queries=len(pairs), rounds=rounds_mesh,
        mesh_equals_single_device=True, all_match_scipy=True,
        bytes_in_use_after_build_per_device=built,
        peak_bytes_in_use_per_device=peaks, gen_s=t_gen,
        build_s=t_build, mesh_drain_s=t_mesh,
        single_device_s=t_one)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scale", type=int, default=None,
                    help=f"log2 vertices of the PPSP graph "
                         f"(default {GRAPH500_SCALE}; 13 with --rehearse)")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any platform (pre-flight check)")
    args = ap.parse_args(argv)
    if args.scale is None:
        args.scale = 13 if args.rehearse else GRAPH500_SCALE
    rng = np.random.default_rng(args.seed)

    device = phase_device(args.chips, args.rehearse)
    if args.chips == 4:
        phase_mesh(args, rng)
    else:
        eng, src, dst, pairs, dists = phase_ppsp(args, rng)
        phase_mutation(eng, src, dst, pairs, dists, rng)
        del eng
        gc.collect()
        phase_pallas(args, rng)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
