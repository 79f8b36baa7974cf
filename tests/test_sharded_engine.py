"""SPMD engine parity (DESIGN.md §6): ``QuegelEngine(mesh=...)`` must be
observationally identical to the single-device engine — same qid->result
maps, same EngineStats (super_rounds/barriers/queries_done/supersteps) —
on all five semirings, both edge partitions, steps_per_round ∈ {1, 4},
with mid-stream admission.

Multi-device paths need >1 host device, so the parity matrix runs in a
subprocess with --xla_force_host_platform_device_count=8 (the main test
process must keep seeing ONE device).  Validation and the 1-part mesh
smoke run in-process on the single default device."""
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import QuegelEngine
from repro.core.graph import random_graph
from repro.kernels import ops
from repro.launch.mesh import make_mesh


# Shared by the in-process smoke and the subprocess matrix: a program that
# runs a fixed number of supersteps of ONE semiring's propagation.
PROBE = '''
from repro.core.engine import QuegelEngine, VertexProgram
import jax.numpy as jnp


class Probe(VertexProgram):
    """steps supersteps of one semiring from a query-seeded state."""

    def __init__(self, sr, steps=3):
        self.sr = sr
        self.steps = steps

    def init(self, graph, query, index=None):
        dt = jnp.float32 if self.sr.name == "sum_times" else jnp.int32
        seed = 1.0 if self.sr.name == "sum_times" else 0
        x = jnp.full((graph.n,), self.sr.add_id, dt).at[query[0] % graph.n].set(seed)
        return dict(x=x)

    def superstep(self, state, ctx):
        y = ctx.propagate(self.sr, state["x"])
        return dict(x=self.sr.add(state["x"], y)), ctx.step >= self.steps

    def extract(self, state, query):
        return dict(x=state["x"])


def run_staged(eng):
    """3 queries with mid-stream admission under capacity 2."""
    for s in (3, 17):
        eng.submit(jnp.asarray([s], jnp.int32))
    eng.run_round()
    eng.submit(jnp.asarray([41], jnp.int32))
    res = eng.run_until_drained()
    st = eng.stats
    return res, (st.super_rounds, st.barriers, st.queries_done, st.supersteps_total)


def assert_same(res_a, res_b, approx=False):
    import numpy as np
    assert set(res_a) == set(res_b)
    for q in res_a:
        for key in res_a[q]:
            a, b = np.asarray(res_a[q][key]), np.asarray(res_b[q][key])
            if approx:
                np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
            else:
                np.testing.assert_array_equal(a, b)
'''

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.apps.ppsp import make_bfs_engine, make_bibfs_engine
    from repro.core.engine import QuegelEngine
    from repro.core.graph import Graph, random_graph
    from repro.core.semiring import (
        INF, MAX_PLUS, MAX_RIGHT, MIN_PLUS, MIN_RIGHT, SUM_TIMES)
    from repro.launch.mesh import make_mesh
    """
) + PROBE + textwrap.dedent(
    """
    assert len(jax.devices()) == 8
    mesh8 = make_mesh((8,), ("w",))
    g = random_graph(64, 3.0, seed=1, directed=True)
    rng = np.random.default_rng(0)
    gf = Graph.from_edges(
        np.asarray(g.src), np.asarray(g.dst), g.n_real,
        w=rng.standard_normal(g.num_edges), weight_dtype=np.float32)

    # ---- parity matrix: 5 semirings x {dst, src} x steps_per_round {1, 4}
    for sr in (MIN_PLUS, MIN_RIGHT, MAX_PLUS, MAX_RIGHT, SUM_TIMES):
        gg = gf if sr.name == "sum_times" else g
        q0 = jnp.zeros((1,), jnp.int32)
        for k in (1, 4):
            ref = QuegelEngine(gg, Probe(sr), 2, example_query=q0,
                               steps_per_round=k)
            want, want_stats = run_staged(ref)
            for part in ("dst", "src"):
                sh = QuegelEngine(gg, Probe(sr), 2, example_query=q0,
                                  steps_per_round=k, mesh=mesh8, partition=part)
                got, got_stats = run_staged(sh)
                assert got_stats == want_stats, (sr.name, part, k, got_stats, want_stats)
                assert_same(got, want, approx=(sr.name == "sum_times"))
                m = sh.collective_bytes_per_round()
                assert m["propagate_calls_per_superstep"] == 1
                assert m["round_total_bytes"] > 0 and m["partition"] == part
        print("parity ok:", sr.name)

    # ---- real programs: BFS on a 2-axis mesh (replicated 'data' axis),
    # BiBFS (auxiliary reverse view) on both partitions, mid-stream admission
    def res_map(res):
        return {q: {kk: np.asarray(v).tolist() for kk, v in r.items()}
                for q, r in res.items()}

    def stat(e):
        s = e.stats
        return (s.super_rounds, s.barriers, s.queries_done, s.supersteps_total)

    pairs = [(int(a), int(b))
             for a, b in np.random.default_rng(3).integers(0, g.n_real, (6, 2))]

    def drain_staged(eng):
        for p in pairs[:4]:
            eng.submit(jnp.asarray(p, jnp.int32))
        eng.run_round()
        for p in pairs[4:]:
            eng.submit(jnp.asarray(p, jnp.int32))
        return res_map(eng.run_until_drained()), stat(eng)

    mesh24 = make_mesh((2, 4), ("data", "model"))  # shards the last axis
    want = drain_staged(make_bfs_engine(g, capacity=3))
    assert drain_staged(make_bfs_engine(g, capacity=3, mesh=mesh24)) == want
    print("bfs mesh(2,4) ok")

    for k in (1, 4):
        ref = drain_staged(make_bibfs_engine(g, capacity=3, steps_per_round=k))
        for part in ("dst", "src"):
            got = drain_staged(make_bibfs_engine(
                g, capacity=3, steps_per_round=k, mesh=mesh8, partition=part))
            assert got == ref, (part, k)
            # two views -> two collectives per superstep
    eng = make_bibfs_engine(g, capacity=3, mesh=mesh8)
    assert eng.collective_bytes_per_round()["propagate_calls_per_superstep"] == 2
    print("bibfs ok")

    # ---- |V| not divisible by the mesh axis: refuse, then Graph.padded fixes
    g60 = random_graph(60, 3.0, seed=2, directed=True)
    try:
        make_bfs_engine(g60, capacity=2, mesh=mesh8)
        raise AssertionError("expected ValueError for |V| % 8 != 0")
    except ValueError as e:
        assert "Graph.padded" in str(e)
    want60 = drain_staged(make_bfs_engine(g60, capacity=3))
    got60 = drain_staged(make_bfs_engine(g60.padded(8), capacity=3, mesh=mesh8))
    assert got60 == want60
    print("SHARDED_ENGINE_OK")
    """
)


def test_sharded_engine_matches_single_device():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep)
    )
    # the forced host devices exist only on the cpu platform
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, env=env, timeout=560)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "SHARDED_ENGINE_OK" in r.stdout


# ------------------------------------------------ in-process (one device)
def _bfs(g, **kw):
    from repro.apps.ppsp import make_bfs_engine

    return make_bfs_engine(g, capacity=2, **kw)


def test_mesh_validation(small_directed):
    g = small_directed
    mesh1 = make_mesh((1,), ("w",))
    with pytest.raises(ValueError):
        _bfs(g, mesh=mesh1, legacy=True)  # legacy is single-device only
    with pytest.raises(ValueError):
        _bfs(g, mesh=mesh1, propagate_override={"default": lambda sr, x, f: x})
    with pytest.raises(ValueError):
        _bfs(g, mesh=mesh1, backend="pallas")  # mesh implies sharded
    with pytest.raises(ValueError):  # ...even for backend instances
        _bfs(g, mesh=mesh1, backend=ops.CooBackend(g))
    with pytest.raises(ValueError):  # tile tables are ignored under mesh=
        _bfs(g, mesh=mesh1, blocks=g.to_blocks(16, 0))
    with pytest.raises(ValueError):
        _bfs(g, backend="sharded")  # sharded needs a mesh
    with pytest.raises(ValueError):
        ops.make_backend("no_such_plan", g)
    from repro.apps.ppsp import make_bibfs_engine

    with pytest.raises(ValueError):  # one instance cannot serve the rev view
        make_bibfs_engine(g, capacity=2, backend=ops.CooBackend(g))


def test_backend_instance_for_single_view(small_directed):
    """A ready backend instance is honored when there is only one view."""
    g = small_directed
    want = _bfs(g).query(jnp.asarray([0, 5], jnp.int32))
    got = _bfs(g, backend=ops.CooBackend(g)).query(jnp.asarray([0, 5], jnp.int32))
    assert int(got["dist"]) == int(want["dist"])


def test_every_view_routes_through_backend_protocol(small_directed):
    """No string dispatch left in the engine: each view resolves to a
    PropagateBackend instance, including override callables."""
    eng = _bfs(small_directed, backend="blocks_ref", block=16)
    assert all(
        isinstance(b, ops.PropagateBackend) for b in eng._backends.values()
    )
    eng2 = _bfs(small_directed,
                propagate_override={"default": lambda sr, x, f: x})
    assert isinstance(eng2._backends["default"], ops.CallableBackend)


def test_one_part_mesh_parity(small_directed):
    """mesh with a size-1 shard axis runs the full SPMD round structure on
    the default device and must already match the plain engine."""
    g = small_directed
    pairs = [(int(a), int(b))
             for a, b in np.random.default_rng(7).integers(0, g.n_real, (5, 2))]

    def drain(eng):
        for p in pairs:
            eng.submit(jnp.asarray(p, jnp.int32))
        res = eng.run_until_drained()
        return {q: {k: np.asarray(v).tolist() for k, v in r.items()}
                for q, r in res.items()}

    want = drain(_bfs(g))
    eng = _bfs(g, mesh=make_mesh((1,), ("w",)), steps_per_round=2)
    # steps_per_round=2 halves barriers but must not change results
    got = drain(eng)
    assert got == want
    assert eng.collective_bytes_per_round()["n_parts"] == 1


@pytest.mark.parametrize("arg_carried", [False, True],
                         ids=["constant", "arg_carried"])
def test_mesh_backends_hold_only_the_placed_partitions(small_directed,
                                                       arg_carried):
    """The backends keep the mesh-placed partition arrays, not the unsharded
    copy they were built as, which would stay on the default device next
    to its shards; still so after a delta."""
    from jax.sharding import NamedSharding

    from repro.apps.ppsp import make_bibfs_engine

    eng = make_bibfs_engine(small_directed, capacity=2,
                            mesh=make_mesh((1,), ("w",)),
                            arg_carried=arg_carried)

    def check_placed():
        ed = eng._editions[eng._current_version]
        for name, be in ed.backends.items():
            placed = ed.round_args[0][name]
            assert all(a is b for a, b in zip(be.parts, placed))
            assert all(isinstance(a.sharding, NamedSharding) for a in placed)

    check_placed()
    eng.apply_delta(adds=[(0, 1)], dels=None)
    eng.submit(jnp.asarray([0, 5], jnp.int32))
    eng.run_until_drained()
    check_placed()


def test_graph_padded():
    g = random_graph(60, 3.0, seed=2, directed=True)
    assert g.padded(4) is g  # 60 % 4 == 0 already
    p = g.padded(8)
    assert p.n % 8 == 0 and p.n == 64
    assert p.n_real == g.n_real and p.num_edges == g.num_edges
    from repro.core.semiring import INF, MIN_RIGHT
    from repro.kernels import ref

    x = jnp.asarray(
        np.random.default_rng(3).integers(0, 20, (2, g.n)).astype(np.int32))
    xp = jnp.pad(x, ((0, 0), (0, p.n - g.n)), constant_values=INF)
    np.testing.assert_array_equal(
        np.asarray(ref.propagate_coo(p, MIN_RIGHT, xp))[:, : g.n],
        np.asarray(ref.propagate_coo(g, MIN_RIGHT, x)),
    )
