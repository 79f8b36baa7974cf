"""Ahead-of-time compiles of the Pallas frontier kernel and of the coo
plan's frontier pass for a described TPU v5e chip: what the TPU compiler
refuses shows up here, with no chip.

Shapes are a 512x512 terrain's tile table (2,048 destination blocks of
B = 128, 11 slots with the argument-carried headroom), the size at which a
2-D scalar-prefetch table overflowed SMEM.  The topology is described in a
module fixture, never at import: only the worker that runs this file loads
the TPU library.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.graph import BlockSparse, Graph
from repro.core.semiring import MAX_PLUS, MAX_RIGHT, MIN_PLUS, MIN_RIGHT, SUM_TIMES
from repro.kernels import frontier, ops, ref

B, NB, MAX_BPR = 128, 2048, 11


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no description
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _table(sds, dtype):
    return BlockSparse(
        src_ids=sds((NB, MAX_BPR), jnp.int32),
        tiles=sds((NB, MAX_BPR, B, B), dtype),
        block=B,
        nslots=sds((NB,), jnp.int32),
    )


def _compile(one_chip, fn, *shapes):
    """Lower ``fn`` for the described chip, check the kernel is in it, and
    compile: raises whatever the TPU compiler would."""
    lowered = jax.jit(fn).lower(*shapes)
    assert "tpu_custom_call" in lowered.as_text()
    return lowered.compile()


CASES = [(MIN_PLUS, jnp.int32), (MIN_RIGHT, jnp.int32), (MAX_RIGHT, jnp.int32),
         (MAX_PLUS, jnp.int32), (SUM_TIMES, jnp.float32)]


@pytest.mark.parametrize("q", [8, 256])
@pytest.mark.parametrize("sr,dtype", CASES,
                         ids=[f"{s.name}-{jnp.dtype(d).name}" for s, d in CASES])
def test_kernel_compiles_for_v5e(one_chip, sr, dtype, q):
    """The gated kernel as the tile backends call it: per-lane mask and
    per-tile activity flags."""
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    v = NB * B
    _compile(
        one_chip,
        lambda bs, x, m, a: frontier.propagate_blocks(
            bs, sr, x, m, a, interpret=False),
        _table(sds, dtype), sds((q, v), dtype), sds((q, v), jnp.bool_),
        sds((NB, MAX_BPR), jnp.bool_),
    )


@pytest.mark.parametrize("sr,dtype", [(MIN_RIGHT, jnp.int32), (MIN_PLUS, jnp.float32)],
                         ids=["min_right-int32", "min_plus-float32"])
def test_kernel_compiles_under_vmap_like_the_engine(one_chip, sr, dtype):
    """The engine's round vmaps each slot's (V,) propagate over C = 8: one
    lane per call, activity computed per slot (BiBFS and terrain SSSP)."""
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    v = NB * B

    def round_prop(bs, x, m):
        def one(xv, mv):
            act = ops.block_activity(bs, mv[None])
            return frontier.propagate_blocks(
                bs, sr, xv[None], mv[None], act, interpret=False)[0]

        return jax.vmap(one)(x, m)

    _compile(one_chip, round_prop, _table(sds, dtype), sds((8, v), dtype),
             sds((8, v), jnp.bool_))


def test_integer_sum_times_is_refused():
    """Mosaic has no integer matmul for the sum_times tile product: the
    Pallas backend says so before lowering."""
    sds = jax.ShapeDtypeStruct
    with pytest.raises(ValueError, match="sum_times on the Pallas backend"):
        jax.eval_shape(
            lambda bs, x: frontier.propagate_blocks(bs, SUM_TIMES, x, interpret=False),
            _table(sds, jnp.int32), sds((8, NB * B), jnp.int32),
        )


# the benchmark's views: Graph500 scale 18 padded to 2 x 16 x 2**18 arcs,
# and the 256 x 256 terrain mesh at its edge capacity
COO_CASES = [(MIN_RIGHT, jnp.int32, 262_144, 8_388_608),
             (MIN_PLUS, jnp.float32, 65_536, 651_584)]


@pytest.mark.parametrize("sr,dtype,v,e", COO_CASES,
                         ids=["kron-min_right-int32", "terrain-min_plus-float32"])
def test_coo_frontier_pass_compiles_under_vmap_like_the_engine(
        one_chip, sr, dtype, v, e):
    """The coo plan as the engine's round calls it, vmapped over C = 8
    slots on a capacity-padded view: one conditional between the frontier
    pass and the dense pass, compiled for the chip."""
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    ints = lambda k: sds((k,), jnp.int32)  # noqa: E731
    g = Graph(n=v, n_real=v, src=ints(e), dst=ints(e), w=sds((e,), dtype),
              in_deg=ints(v), out_deg=ints(v), csr_row=ints(v + 1),
              csr_src=ints(e), csr_dst=ints(e), csr_w=sds((e,), dtype),
              nnz=sds((), jnp.int32))

    def round_prop(g, x, f):
        return jax.vmap(
            lambda xv, fv: ref.propagate_coo_frontier(g, sr, xv, fv))(x, f)

    lowered = jax.jit(round_prop).lower(g, sds((8, v), dtype),
                                        sds((8, v), jnp.bool_))
    assert "stablehlo.case" in lowered.as_text()
    lowered.compile()
