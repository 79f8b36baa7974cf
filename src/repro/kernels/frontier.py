"""Pallas TPU kernel: batched block-sparse semiring SpMV (frontier step).

One Pregel super-round over C in-flight queries is
``y[q, v] = add_{u -> v} mul(x[q, u], w(u, v))`` — we tile it as a
block-sparse dense-tile "matmul" over a semiring:

  grid = (num_dst_blocks, max_blocks_per_row)
  x tile   : (Q, B)  selected by scalar-prefetched ``src_ids[i, k]``
  adj tile : (B, B)  dense weight tile in VMEM
  y tile   : (Q, B)  accumulated across the k axis in VMEM

The scalar-prefetch indirection (``PrefetchScalarGridSpec``) is the TPU
idiom replacing Quegel's hash-partitioned message routing: the block index
list *is* the routing table, resolved at tile granularity instead of per
message.  A second scalar-prefetch operand carries the per-(dst_block,
slot) ACTIVITY bitmap (the frontier reduced over the query axis, plus
padding-slot validity): ``pl.when`` skips the combine and the accumulate
of dead tiles, making tile work proportional to the active frontier
(DESIGN.md §3).  B is a multiple of 128 (lane width); Q is padded to 8
(sublanes).

Semiring flavours (static `sr_name` at trace time):
  min_plus / max_plus : distance relaxation (saturating on int32)
  min_right/max_right : label propagation (tile != add_id gates the edge)
  sum_times           : numeric flow -- a true MXU matmul per tile
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.graph import BlockSparse
from repro.core.semiring import INF, Semiring


def _combine_tile(sr_name: str, xs, t, add_id):
    """(Q,B) x (B,B) -> (Q,B) partial combine for one adjacency tile."""
    if sr_name in ("min_plus", "max_plus"):
        s = xs[:, :, None] + t[None].astype(xs.dtype)
        if jnp.issubdtype(xs.dtype, jnp.integer):
            if sr_name == "min_plus":
                big = jnp.asarray(INF, xs.dtype)
                s = jnp.where((xs[:, :, None] >= big) | (t[None] >= big), add_id, s)
            else:
                neg = jnp.asarray(-INF, xs.dtype)
                s = jnp.where((xs[:, :, None] <= neg) | (t[None] <= neg), add_id, s)
        return jnp.min(s, 1) if sr_name == "min_plus" else jnp.max(s, 1)
    if sr_name in ("min_right", "max_right"):
        present = (t != add_id)[None]
        masked = jnp.where(present, xs[:, :, None], add_id)
        return jnp.min(masked, 1) if sr_name == "min_right" else jnp.max(masked, 1)
    if sr_name == "sum_times":
        return jax.lax.dot(xs, t.astype(xs.dtype), preferred_element_type=xs.dtype)
    raise ValueError(sr_name)


def _kernel(src_ids_ref, active_ref, x_ref, tiles_ref, *rest, sr_name: str,
            add_id, max_bpr: int):
    """One (dst_block, slot) grid cell.  ``active_ref`` is the second
    scalar-prefetch operand: a per-(i, k) activity flag, flattened to
    ``i * max_bpr + k`` (frontier-dead and padding tiles are skipped — both
    the combine and the accumulate).  The optional mask ref applies the
    per-lane frontier INSIDE the tile (the push-down replacing the old
    dense pre-mask of x)."""
    if len(rest) == 2:
        m_ref, o_ref = rest
    else:
        (o_ref,) = rest
        m_ref = None
    i, k = pl.program_id(0), pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.full(o_ref.shape, add_id, o_ref.dtype)

    @pl.when(active_ref[i * max_bpr + k] != 0)
    def _acc():
        xs = x_ref[...]
        if m_ref is not None:
            xs = jnp.where(m_ref[...] != 0, xs, jnp.asarray(add_id, xs.dtype))
        part = _combine_tile(sr_name, xs, tiles_ref[0, 0], jnp.asarray(add_id, xs.dtype))
        if sr_name in ("min_plus", "min_right"):
            o_ref[...] = jnp.minimum(o_ref[...], part)
        elif sr_name in ("max_plus", "max_right"):
            o_ref[...] = jnp.maximum(o_ref[...], part)
        else:
            o_ref[...] = o_ref[...] + part


def propagate_blocks(
    bs: BlockSparse,
    sr: Semiring,
    x: jnp.ndarray,
    mask=None,
    active=None,
    *,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Run the Pallas frontier kernel. x: (Q, V) -> (Q, V).

    Q is padded to a multiple of 8, V to num_dst_blocks * B.

    ``mask``   (Q, V) bool — per-lane frontier, applied per visited tile.
    ``active`` (nb, max_bpr) bool — per-tile activity flags, scalar-
               prefetched and gated with ``pl.when`` so dead tiles cost a
               flag read instead of a combine + accumulate.  None visits
               every tile (the dense baseline).
    ``interpret`` None (the default) compiles the kernel with Mosaic on a
               TPU and runs the Pallas interpreter on any other platform
               (CPU tests).  ``False`` forces the compiled kernel, for
               ahead-of-time lowering against a described TPU from a host
               without one; ``True`` on a TPU is refused, so the device can
               never silently run the interpreter.
    """
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        interpret = not on_tpu
    elif interpret and on_tpu:
        raise ValueError(
            "interpret=True on a TPU would run the Pallas interpreter on the "
            "device; leave interpret=None to compile the kernel"
        )
    if sr.name == "sum_times" and not jnp.issubdtype(x.dtype, jnp.floating):
        raise ValueError(
            f"sum_times on the Pallas backend needs a floating x, got "
            f"{x.dtype}: Mosaic has no integer matmul for the tile product; "
            "cast x and the tile table to float32, or use the coo backend"
        )
    return _propagate_blocks(bs, sr, x, mask, active, interpret=bool(interpret))


@functools.partial(jax.jit, static_argnames=("sr", "interpret"))
def _propagate_blocks(bs, sr, x, mask, active, *, interpret):
    q, v = x.shape
    b = bs.block
    nb, max_bpr = bs.num_dst_blocks, bs.max_bpr
    qp = max(8, ((q + 7) // 8) * 8)
    vp = nb * b
    xpad = jnp.pad(x, ((0, qp - q), (0, vp - v)), constant_values=sr.add_id)
    # Both scalar-prefetch tables go to SMEM flat: a 2-D (nb, max_bpr)
    # int32 table is padded to 128 words per row there, and two of them
    # overflow the 1 MiB of SMEM from nb = 2048 (a 512x512 terrain) on.
    ids = bs.src_ids.reshape(-1)
    if active is None:
        act = jnp.ones((nb * max_bpr,), jnp.int32)
    else:
        act = active.astype(jnp.int32).reshape(-1)

    grid = (nb, max_bpr)
    x_spec = pl.BlockSpec(
        (qp, b), lambda i, k, ids, act: (0, ids[i * max_bpr + k])
    )
    in_specs = [
        x_spec,
        pl.BlockSpec((1, 1, b, b), lambda i, k, ids, act: (i, k, 0, 0)),
    ]
    args = [xpad, bs.tiles.reshape(nb, max_bpr, b, b)]
    if mask is not None:
        mpad = jnp.pad(
            mask.astype(jnp.int32), ((0, qp - q), (0, vp - v)), constant_values=0
        )
        in_specs.append(x_spec)
        args.append(mpad)
    out = pl.pallas_call(
        functools.partial(_kernel, sr_name=sr.name, add_id=sr.add_id,
                          max_bpr=max_bpr),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((qp, b), lambda i, k, ids, act: (0, i)),
        ),
        out_shape=jax.ShapeDtypeStruct((qp, vp), x.dtype),
        interpret=interpret,
    )(ids, act, *args)
    return out[:q, :v]
