import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Pod-scale dry-run of the Quegel engine itself.

Lowers one BiBFS super-round — C concurrent queries, both propagation
directions, distance update, frontier mask, per-slot done flags — with
the vertex/edge axes sharded over the production mesh ('model' carries
the destination-block partition, 'data'×'pod' carries query slots), and
proves it compiles with per-device memory and collective bytes reported.

Abstract inputs (ShapeDtypeStruct): a Twitter-scale graph — |V| = 2^26
(67M), |E| = 2^31 (2.1B edges, the paper's Twitter has 1.96B) — never
allocated.

Usage: PYTHONPATH=src python -m repro.launch.dryrun_quegel [--multi-pod]
"""
import argparse
import json

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.semiring import INF
from repro.launch import roofline as RL
from repro.launch.mesh import make_production_mesh


def super_round(srcp, dstp, wp, valid, dist_s, dist_t, ff, fb, live, mesh, axis):
    """One BiBFS super-round over C slots, edge-partitioned by destination
    block along ``axis`` (the shard_map'd combine of core.distributed,
    inlined here over abstract inputs)."""
    n_parts = mesh.shape[axis]
    C, V = dist_s.shape
    block = V // n_parts

    def seg_min(x, seg, size):
        return jax.ops.segment_min(x, seg, num_segments=size)

    def body(x, srcp_, dstp_, wp_, valid_):
        i = jax.lax.axis_index(axis)
        xf = x[:, srcp_[0]]  # (C, Emax) gather of frontier values
        msgs = jnp.where(valid_[0][None], xf, INF)
        seg = dstp_[0] - i * block

        def one(m):
            return jnp.minimum(seg_min(m, seg, block), INF)

        y = jax.vmap(one)(msgs)
        return jax.lax.all_gather(y, axis, axis=1, tiled=True)

    slot_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def propagate(x, frontier):
        # two-level partition: query slots over 'data' (each group holds
        # C/|data| queries' full frontiers), edges over 'model'
        x = jnp.where(frontier, x, INF)
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(slot_axes, None), P(axis, None), P(axis, None),
                      P(axis, None), P(axis, None)),
            out_specs=P(slot_axes, None),
            check_vma=False,
        )(x, srcp, dstp, wp, valid)

    got_f = propagate(dist_s, ff)
    got_b = propagate(dist_t, fb)
    new_f = (got_f < INF) & (dist_s >= INF)
    new_b = (got_b < INF) & (dist_t >= INF)
    dist_s = jnp.where(new_f & live[:, None], got_f, dist_s)
    dist_t = jnp.where(new_b & live[:, None], got_b, dist_t)
    both = jnp.where((dist_s < INF) & (dist_t < INF), dist_s + dist_t, INF)
    best = both.min(axis=1)
    done = (best < INF) | (~new_f.any(axis=1)) | (~new_b.any(axis=1))
    return dist_s, dist_t, new_f, new_b, done & live


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--capacity", type=int, default=64)
    ap.add_argument("--log-v", type=int, default=26)
    ap.add_argument("--log-e", type=int, default=31)
    ap.add_argument("--out", default="runs/dryrun")
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    axis = "model"
    n_parts = mesh.shape[axis]
    C, V, E = args.capacity, 2 ** args.log_v, 2 ** args.log_e
    emax = E // n_parts
    i32 = jnp.int32

    edge = jax.ShapeDtypeStruct((n_parts, emax), i32)
    vb = jax.ShapeDtypeStruct((C, V), i32)
    fm = jax.ShapeDtypeStruct((C, V), jnp.bool_)
    lv = jax.ShapeDtypeStruct((C,), jnp.bool_)

    slot_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    e_sh = NamedSharding(mesh, P(axis, None))
    v_sh = NamedSharding(mesh, P(slot_axes, None))  # slots over data axes
    l_sh = NamedSharding(mesh, P(slot_axes))

    fn = lambda *a: super_round(*a, mesh=mesh, axis=axis)
    jitted = jax.jit(
        fn,
        in_shardings=(e_sh, e_sh, e_sh, e_sh, v_sh, v_sh, v_sh, v_sh, l_sh),
        out_shardings=(v_sh, v_sh, v_sh, v_sh, l_sh),
    )
    with mesh:
        lowered = jitted.lower(edge, edge, edge, edge, vb, vb, fm, fm, lv)
        compiled = lowered.compile()
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    coll = RL.collective_bytes(compiled.as_text())
    res = dict(
        arch="quegel-bibfs", shape=f"C{C}_V{V}_E{E}",
        mesh="pod2x16x16" if args.multi_pod else "pod16x16",
        status="compiled",
        flops=float(ca.get("flops", 0.0)),
        bytes=float(ca.get("bytes accessed", 0.0)),
        coll_bytes=float(coll["total"]),
        coll_detail=coll,
        memory=dict(
            temp_bytes=float(getattr(ma, "temp_size_in_bytes", 0) or 0),
            arg_bytes=float(getattr(ma, "argument_size_in_bytes", 0) or 0),
        ),
    )
    print(f"memory_analysis: {ma}")
    print(f"cost: flops/dev={res['flops']:.3e} bytes/dev={res['bytes']:.3e} "
          f"coll/dev={res['coll_bytes']:.3e}")
    pk = RL.peaks(RL.V5E)
    t_coll = res["coll_bytes"] / pk["ici_bw"]
    t_mem = res["bytes"] / pk["hbm_bw"]
    print(f"roofline: memory={t_mem*1e3:.1f}ms collective={t_coll*1e3:.1f}ms "
          f"per super-round (C={C} queries share ONE barrier)")
    os.makedirs(args.out, exist_ok=True)
    tag = "mp" if args.multi_pod else "sp"
    with open(os.path.join(args.out, f"quegel-bibfs_{tag}.json"), "w") as f:
        json.dump(res, f, indent=2, default=str)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
