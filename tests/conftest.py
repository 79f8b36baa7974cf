"""Shared fixtures. Tests run on ONE CPU device (the dry-run sets its own
512-device flag in a subprocess; never here)."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest

from repro.core.graph import Graph, barabasi_albert, random_graph


@pytest.fixture(scope="session")
def small_directed():
    return random_graph(60, 3.0, seed=1, directed=True)


@pytest.fixture(scope="session")
def small_undirected():
    return random_graph(60, 3.0, seed=2, directed=False)


@pytest.fixture(scope="session")
def ba_graph():
    return barabasi_albert(120, 3, seed=3, directed=False)


@pytest.fixture
def frontier_passes(monkeypatch) -> list:
    """Patch the coo plan's frontier pass to record, as it runs, the arcs
    it was handed: one entry per call that took the pass (read after
    ``jax.effects_barrier()``).  Engines built after the patch trace it."""
    import jax

    from repro.kernels import ref

    totals, real = [], ref._frontier_pass

    def recording(graph, sr, xf, d, total, sizes):
        jax.debug.callback(lambda t: totals.append(int(t)), total)
        return real(graph, sr, xf, d, total, sizes)

    monkeypatch.setattr(ref, "_frontier_pass", recording)
    return totals


def nx_of(graph: Graph, directed: bool = True):
    import networkx as nx

    g = nx.DiGraph() if directed else nx.Graph()
    g.add_nodes_from(range(graph.n_real))
    src = np.asarray(graph.src)
    dst = np.asarray(graph.dst)
    for s, d in zip(src, dst):
        if s < graph.n_real and d < graph.n_real:
            g.add_edge(int(s), int(d))
    return g
