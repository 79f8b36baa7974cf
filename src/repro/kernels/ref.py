"""Pure-jnp oracles for the frontier-propagation kernels.

``propagate_coo`` is the reference semantics of one Pregel superstep with a
combiner (see core/semiring.py): edge-parallel message generation followed
by a segment reduction keyed by destination.  The Pallas kernel in
``frontier.py`` must match this bit-exactly on integer semirings and to
float tolerance on float ones.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap

from repro.core.graph import BlockSparse, Graph
from repro.core.semiring import INF, Semiring


def _saturating_add(x, w, big):
    """min-plus add that never wraps around on int32."""
    if jnp.issubdtype(x.dtype, jnp.integer):
        return jnp.where((x >= big) | (w >= big), big, x + w)
    return x + w


def apply_mul(sr: Semiring, x, w):
    big = jnp.asarray(INF, x.dtype) if sr.name in ("min_plus",) else None
    if sr.name == "min_plus":
        return _saturating_add(x, w.astype(x.dtype), big)
    if sr.name == "max_plus":
        if jnp.issubdtype(x.dtype, jnp.integer):
            neg = jnp.asarray(-INF, x.dtype)
            w_ = w.astype(x.dtype)
            return jnp.where((x <= neg) | (w_ <= neg), neg, x + w_)
        return x + w.astype(x.dtype)
    if sr.name in ("min_right", "max_right"):
        return x
    if sr.name == "sum_times":
        return x * w.astype(x.dtype)
    raise ValueError(sr.name)


def propagate_coo(graph: Graph, sr: Semiring, x: jnp.ndarray, frontier=None) -> jnp.ndarray:
    """One superstep: x (..., V) -> combined incoming messages (..., V).

    ``frontier`` (..., V) bool masks which sources emit; a masked source
    contributes the add-identity.  Leading axes are query/lane batch dims.
    """
    add_id = jnp.asarray(sr.add_id, x.dtype)
    if frontier is not None:
        x = jnp.where(frontier, x, _mask_fill(sr, x.dtype))

    def one(xv):
        msgs = apply_mul(sr, xv[graph.src], graph.w)
        out = sr.segment_combine(msgs, graph.dst, graph.n)
        return _clamp_empty(sr, out, add_id)

    flat = x.reshape((-1, x.shape[-1]))
    out = jax.vmap(one)(flat)
    return out.reshape(x.shape)


def _mask_fill(sr: Semiring, dtype):
    """What a masked source holds in the dense pass: the add-identity, or
    for a float min/max semiring the infinity that ``apply_mul`` keeps at
    or beyond the identity whatever the weight, so that ``_clamp_empty``
    turns its message back into exactly the identity."""
    if jnp.issubdtype(dtype, jnp.floating):
        if sr.name in ("min_plus", "min_right"):
            return jnp.asarray(jnp.inf, dtype)
        if sr.name in ("max_plus", "max_right"):
            return jnp.asarray(-jnp.inf, dtype)
    return jnp.asarray(sr.add_id, dtype)


def _clamp_empty(sr: Semiring, out, add_id):
    """Segment reductions fill empty segments with the dtype extreme; clamp
    back to the semiring identity (our finite INF sentinel)."""
    if sr.name in ("min_plus", "min_right"):
        return jnp.minimum(out, add_id)
    if sr.name in ("max_plus", "max_right"):
        return jnp.maximum(out, add_id)
    return out


# ------------------------------------------------- frontier-driven coo
# The semirings whose combine is a min or a max: a message's order and
# multiplicity cannot change the result, so reading only the frontier's
# out-arcs gives the dense pass's answer bit for bit.
SELECTIVE = ("min_plus", "min_right", "max_plus", "max_right")
# Arcs a frontier pass reads a trip, as a share of the view's padded
# arcs; the most trips it runs, past which the dense pass serves the call
# (on a v5e the dense pass costs about 130 trips); and the (row, vertex)
# pairs it holds, as a share of the vertices.
CHUNK_SHARE = 128
MAX_TRIPS = 64
PAIR_SHARE = 4


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def frontier_sizes(graph: Graph, rows: int, chunk=None):
    """``(K, T, P)`` of a frontier pass over ``graph`` with ``rows`` rows of
    x: K arcs a trip (``chunk``, else the view's padded arcs over
    ``CHUNK_SHARE``), at most T = ``MAX_TRIPS`` x K arcs in all, and at
    most P (row, vertex) pairs with arcs (V / ``PAIR_SHARE``; never more
    than T, as each pair has an arc).  None where the pass cannot run: no
    CSR view, or totals past int32."""
    if graph.csr_row is None:
        return None
    e, n = int(graph.csr_src.shape[0]), graph.n
    k = int(chunk) if chunk else _round_up(max(e // CHUNK_SHARE, 1), 128)
    t = MAX_TRIPS * k
    p = min(rows * n, t, _round_up(-(-n // PAIR_SHARE), 128))
    if rows * max(e, t) >= 2**31 or rows * n >= 2**31 - 1:
        return None
    return k, t, p


def _frontier_totals(graph: Graph, ff, sizes):
    """Per (row, vertex) out-degree on the frontier ``ff`` (rows, V), the
    arcs they lead out of, and whether the frontier pass takes them."""
    _, t, p = sizes
    deg = graph.csr_row[1:] - graph.csr_row[:-1]
    d = jnp.where(ff, deg, 0)
    total = d.sum()
    use = (total <= t) & ((d > 0).sum() <= p)
    return d, total, use


def frontier_plan(graph: Graph, sr: Semiring, shape, frontier, chunk=None):
    """What ``propagate_coo_frontier`` does with x of ``shape`` and this
    frontier, decided as it decides: (took the frontier pass, arcs read:
    the trips' arcs there, every arc for every row in the dense pass,
    held at the int32 limit)."""
    n = graph.n
    rows = math.prod(shape[:-1])
    dense = jnp.asarray(min(rows * int(graph.src.shape[0]), 2**31 - 1),
                        jnp.int32)
    sizes = (frontier_sizes(graph, rows, chunk)
             if frontier is not None and sr.name in SELECTIVE else None)
    if sizes is None:
        return jnp.asarray(False), dense
    ff = jnp.broadcast_to(frontier, shape).reshape((rows, n))
    _, total, use = _frontier_totals(graph, ff, sizes)
    k = sizes[0]
    return use, jnp.where(use, (total + k - 1) // k * k, dense)


def propagate_coo_frontier(graph: Graph, sr: Semiring, x: jnp.ndarray,
                           frontier=None, chunk=None) -> jnp.ndarray:
    """``propagate_coo``'s result, reading only the frontier's out-arcs
    where that is the cheaper pass.

    One decision for the whole call: with a frontier, a selective
    semiring, and the frontier's arcs (summed over every row of x) and
    (row, vertex) pairs within ``frontier_sizes``, the frontier pass;
    otherwise the dense ``propagate_coo``.  Under ``vmap`` (the engine's
    slot axis) a batching rule folds the mapped axis into the rows, so the
    decision and the pass span every slot and ``lax.cond`` stays a real
    branch.  The pass finds the arcs through ``csr_row``, so capacity
    padding is never read.
    """
    if (frontier is None or sr.name not in SELECTIVE
            or graph.csr_row is None):
        return propagate_coo(graph, sr, x, frontier)
    frontier = jnp.broadcast_to(frontier, x.shape)
    return _frontier_fn(sr, chunk)(x, frontier, graph)


@functools.lru_cache(maxsize=None)
def _frontier_fn(sr: Semiring, chunk):
    @custom_vmap
    def fn(x, frontier, graph):
        n = graph.n
        xf, ff = x.reshape((-1, n)), frontier.reshape((-1, n))
        sizes = frontier_sizes(graph, xf.shape[0], chunk)
        if sizes is None:
            return propagate_coo(graph, sr, x, frontier)
        d, total, use = _frontier_totals(graph, ff, sizes)
        out = jax.lax.cond(
            use,
            lambda: _frontier_pass(graph, sr, xf, d, total, sizes),
            lambda: propagate_coo(graph, sr, xf, ff),
        )
        return out.reshape(x.shape)

    @fn.def_vmap
    def rule(axis_size, in_batched, x, frontier, graph):
        xb, fb, gb = in_batched
        if any(jax.tree.leaves(gb)):
            raise NotImplementedError("frontier coo pass over a batched graph")
        if not xb:
            x = jnp.broadcast_to(x, (axis_size,) + x.shape)
        if not fb:
            frontier = jnp.broadcast_to(frontier, (axis_size,) + frontier.shape)
        return fn(x, frontier, graph), True

    return fn


def _gather_rows(idx, *cols):
    """``[c[idx] for c in cols]``: for 4-byte columns ONE gather of
    ``len(cols)`` values an index."""
    if any(jnp.dtype(c.dtype).itemsize != 4 for c in cols):
        return [c[idx] for c in cols]
    packed = jnp.stack(
        [jax.lax.bitcast_convert_type(c, jnp.int32) for c in cols], -1)
    got = packed[idx]
    return [jax.lax.bitcast_convert_type(got[..., i], c.dtype)
            for i, c in enumerate(cols)]


def _frontier_pass(graph: Graph, sr: Semiring, xf, d, total, sizes):
    """Reduce over the frontier's out-arcs only: (rows, V) -> (rows, V).

    The (row, vertex) pairs with arcs are compacted by one sort of their
    flat ids; a prefix sum of their degrees lays their arcs end to end,
    arc j of pair i being CSR arc ``csr_row[v_i] + j - start_i``; a
    running max over the marks at each ``start_i``, taken a trip at a
    time, names every packed position's pair.  The packed arcs are
    consumed K at a time in one ``while_loop`` of ceil(total / K) trips,
    each a gather of the pair's row, a gather of the arc's destination
    (and weight) and a scatter into a flat (rows * V + 1) output whose
    last entry takes the idle lanes.
    """
    k, t, p = sizes
    rows, n = xf.shape
    m = rows * n
    add_id = jnp.asarray(sr.add_id, xf.dtype)
    df = d.reshape(-1)
    ids = jnp.arange(m, dtype=jnp.int32)
    pick = jnp.sort(jnp.where(df > 0, ids, m))[:p]
    has = pick < m
    pick = jnp.minimum(pick, m - 1)
    row_start = jnp.broadcast_to(graph.csr_row[:-1], (rows, n)).reshape(-1)
    deg, first, xs = _gather_rows(pick, df, row_start, xf.reshape(-1))
    deg = jnp.where(has, deg, 0)
    start = jnp.cumsum(deg) - deg
    marks = jnp.zeros((t,), jnp.int32).at[jnp.where(deg > 0, start, t)].max(
        jnp.arange(p, dtype=jnp.int32), mode="drop")
    base = first - start  # arc id = base + packed position
    offset = pick - pick % n  # the row's first flat output index
    weighted = sr.name in ("min_plus", "max_plus")

    def trip(carry):
        out, lo, last = carry
        j = lo + jnp.arange(k, dtype=jnp.int32)
        live = j < total
        owner = jnp.maximum(
            jax.lax.cummax(jax.lax.dynamic_slice(marks, (lo,), (k,))), last)
        b, x_src, off = _gather_rows(owner, base, xs, offset)
        arc = jnp.where(live, b + j, 0)
        w = graph.csr_w[arc] if weighted else None
        msgs = apply_mul(sr, x_src, w)
        to = jnp.where(live, off + graph.csr_dst[arc], m)
        if sr.name in ("min_plus", "min_right"):
            out = out.at[to].min(msgs)
        else:
            out = out.at[to].max(msgs)
        return out, lo + k, owner[-1]

    zero = jnp.asarray(0, jnp.int32)
    out, _, _ = jax.lax.while_loop(
        lambda c: c[1] < total, trip,
        (jnp.full((m + 1,), add_id), zero, zero))
    return out[:m].reshape(rows, n)


def _tile_part(sr: Semiring, xs, t, add_id):
    """(q, b) x (b, b) -> (q, b) partial combine for one adjacency tile
    (the jnp mirror of the Pallas kernel's ``_combine_tile``)."""
    if sr.name in ("min_plus", "max_plus"):
        s = xs[:, :, None] + t[None].astype(xs.dtype)
        if jnp.issubdtype(xs.dtype, jnp.integer):
            if sr.name == "min_plus":
                big = jnp.asarray(INF, xs.dtype)
                s = jnp.where((xs[:, :, None] >= big) | (t[None] >= big), add_id, s)
            else:
                neg = jnp.asarray(-INF, xs.dtype)
                s = jnp.where((xs[:, :, None] <= neg) | (t[None] <= neg), add_id, s)
        return jnp.min(s, 1) if sr.name == "min_plus" else jnp.max(s, 1)
    if sr.name in ("min_right", "max_right"):
        present = t != sr.add_id
        masked = jnp.where(present[None], xs[:, :, None], add_id)
        return jnp.min(masked, 1) if sr.name == "min_right" else jnp.max(masked, 1)
    if sr.name == "sum_times":
        return xs @ t.astype(xs.dtype)
    raise ValueError(sr.name)


def propagate_blocks_ref(
    bs: BlockSparse, sr: Semiring, x: jnp.ndarray, mask=None, active=None
) -> jnp.ndarray:
    """jnp oracle operating on the *block-sparse* layout (same math the
    Pallas kernel performs), for layout-level validation.

    ``mask``   (q, V) bool: per-lane frontier, applied per visited tile
               (a masked source contributes the add-identity) — the
               push-down replacing ``ops.propagate``'s old dense pre-mask.
    ``active`` (nb, max_bpr) bool: per-tile activity; when given, dead
               tiles are short-circuited with ``lax.cond`` (a real skip
               when not under vmap; a select — still exact — under vmap).
    """
    q = x.shape[0]
    b = bs.block
    nb = bs.num_dst_blocks
    add_id = jnp.asarray(sr.add_id, x.dtype)
    vp = nb * b

    def pad(a, fill):
        if a.shape[-1] < vp:
            return jnp.pad(a, ((0, 0), (0, vp - a.shape[-1])), constant_values=fill)
        return a

    xb = pad(x, sr.add_id).reshape(q, nb, b)
    mb = None if mask is None else pad(mask, False).reshape(q, nb, b)

    def tile(i, k, acc):
        xs = xb[:, bs.src_ids[i, k]]  # (q, b)
        if mb is not None:
            xs = jnp.where(mb[:, bs.src_ids[i, k]], xs, add_id)
        return sr.add(acc, _tile_part(sr, xs, bs.tiles[i, k], add_id))

    init = jnp.full((q, b), add_id, x.dtype)
    if active is None:
        dst_block = lambda i: jax.lax.fori_loop(
            0, bs.max_bpr, lambda k, a: tile(i, k, a), init
        )
        out = jax.vmap(dst_block)(jnp.arange(nb))  # (nb, q, b)
    else:

        def row(_, i):
            def slot(k, a):
                return jax.lax.cond(
                    active[i, k], lambda a: tile(i, k, a), lambda a: a, a
                )

            return None, jax.lax.fori_loop(0, bs.max_bpr, slot, init)

        _, out = jax.lax.scan(row, None, jnp.arange(nb))  # (nb, q, b)
    return out.transpose(1, 0, 2).reshape(q, vp)[:, : x.shape[-1]]
