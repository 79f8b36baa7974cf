"""shard_map distributed propagation equals the single-device reference.

Multi-device paths need >1 host device, so the checks run in a subprocess
with --xla_force_host_platform_device_count=8 (the main test process must
keep seeing ONE device)."""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.distributed import ShardedGraph, make_propagate_sharded
    from repro.core.graph import random_graph
    from repro.core.semiring import INF, MIN_PLUS, MIN_RIGHT, MAX_RIGHT, SUM_TIMES
    from repro.kernels import ref
    from repro.launch.mesh import make_mesh

    assert len(jax.devices()) == 8
    mesh = make_mesh((2, 4), ("data", "model"))
    g = random_graph(64, 3.0, seed=1, directed=True)
    assert g.n % 4 == 0
    rng = np.random.default_rng(0)

    for sr in (MIN_PLUS, MIN_RIGHT, MAX_RIGHT):
        x = rng.integers(0, 20, (3, g.n)).astype(np.int32)
        x[rng.random((3, g.n)) < 0.5] = INF if sr.name.startswith("min") else -(2**30)
        x = jnp.asarray(x)
        want = np.asarray(ref.propagate_coo(g, sr, x))
        for part in ("dst", "src"):
            sg = ShardedGraph(g, 4, partition=part)
            prop = make_propagate_sharded(sg, mesh, "model", sr)
            got = np.asarray(prop(x))
            np.testing.assert_array_equal(got, want), (sr.name, part)
    # float sum_times via psum
    gw = random_graph(64, 3.0, seed=2, directed=True)
    from repro.core.graph import Graph
    g2 = Graph.from_edges(np.asarray(gw.src), np.asarray(gw.dst), gw.n_real,
                          w=rng.standard_normal(gw.num_edges), weight_dtype=np.float32)
    x = jnp.asarray(rng.standard_normal((2, g2.n)).astype(np.float32))
    want = np.asarray(ref.propagate_coo(g2, SUM_TIMES, x))
    for part in ("dst", "src"):
        sg = ShardedGraph(g2, 4, partition=part)
        prop = make_propagate_sharded(sg, mesh, "model", SUM_TIMES)
        got = np.asarray(prop(x))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5), part
    # end-to-end: the ENGINE running BFS through the sharded propagate
    from repro.apps.ppsp import BFSProgram
    from repro.core.engine import QuegelEngine
    import networkx as nx
    g3 = random_graph(64, 2.5, seed=5, directed=True)
    sg3 = ShardedGraph(g3, 4, partition="dst")
    prop = make_propagate_sharded(sg3, mesh, "model", MIN_RIGHT)
    eng = QuegelEngine(
        g3, BFSProgram(), capacity=4,
        example_query=jnp.zeros((2,), jnp.int32),
        # inside the engine's vmap a slot sees (V,); the sharded propagate
        # is (Q, V) -> reshape around it (vmap batches the shard_map)
        propagate_override={"default": lambda sr, x, f: prop(
            x.reshape(1, -1), None if f is None else f.reshape(1, -1))[0]},
    )
    G = nx.DiGraph()
    G.add_nodes_from(range(g3.n_real))
    for s, d in zip(np.asarray(g3.src), np.asarray(g3.dst)):
        if s < g3.n_real and d < g3.n_real:
            G.add_edge(int(s), int(d))
    rng2 = np.random.default_rng(3)
    for s, t in rng2.integers(0, g3.n_real, (6, 2)):
        got = int(eng.query(jnp.asarray([int(s), int(t)], jnp.int32))["dist"])
        try:
            want = nx.shortest_path_length(G, int(s), int(t))
        except nx.NetworkXNoPath:
            want = INF
        assert got == want, (s, t, got, want)
    print("DISTRIBUTED_OK")
    """
)


def test_sharded_propagate_matches_reference():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep)
    )
    # the forced host devices exist only on the cpu platform
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, env=env, timeout=420)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "DISTRIBUTED_OK" in r.stdout
