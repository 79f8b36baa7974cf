import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry run: lower + compile every (arch x shape x mesh) cell.

512 placeholder host devices stand in for 2 pods x 256 chips.  Every cell
must `.lower().compile()` cleanly; the compiled artifact's
memory_analysis / cost_analysis plus the partitioned HLO's collective ops
feed the roofline table (EXPERIMENTS.md §Dry-run / §Roofline).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out runs/dryrun]
"""
import argparse
import functools
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, cell_is_supported, get_arch, input_specs, list_archs
from repro.launch import roofline as RL
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as T
from repro.models.common import param_spec, set_mesh
from repro.train.optimizer import OptConfig, adamw_init
from repro.train.train_step import make_train_step


def _path_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "idx", last)))


def params_shardings(mesh, tree, force_fsdp: bool = False):
    def one(path, leaf):
        return NamedSharding(
            mesh, param_spec(_path_name(path), leaf.shape, force_fsdp=force_fsdp))

    return jax.tree_util.tree_map_with_path(one, tree)


def _fits(dim: int, mesh, axes) -> bool:
    if axes is None:
        return False
    axes = (axes,) if isinstance(axes, str) else axes
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return dim % n == 0


def cache_shardings(mesh, tree, batch_axes):
    """Decode-cache sharding: batch over data axes; seq (kv caches) or
    state-heads over 'model'.

    Scan-stacked cache leaves (under the "blocks" key) carry a leading
    (n_super,) layer-stack dim that must stay unsharded — treating dim 1
    (the batch!) as the sequence dim silently dropped the seq sharding
    and decode caches stopped fitting HBM (§Perf iteration G2)."""

    def one(path, leaf):
        name = _path_name(path)
        top = str(getattr(path[0], "key", getattr(path[0], "idx", path[0])))
        off = 1 if top == "blocks" else 0  # layer-stack dim of scanned blocks
        nd = len(leaf.shape)
        spec = [None] * nd
        if nd > off:
            spec[off] = batch_axes if _fits(leaf.shape[off], mesh, batch_axes) else None
        msz = mesh.shape["model"]
        if name in ("k", "v", "ckv", "krope") and nd >= off + 2 and leaf.shape[off + 1] % msz == 0:
            spec[off + 1] = "model"  # sequence-sharded KV cache (flash-decode)
        elif name == "state" and nd >= off + 2 and leaf.shape[off + 1] % msz == 0:
            spec[off + 1] = "model"  # SSM state heads
        elif name in ("h",) and leaf.shape[-1] % msz == 0:
            spec[-1] = "model"
        elif name == "conv" and leaf.shape[-1] % msz == 0:
            spec[-1] = "model"
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(one, tree)


def batch_shardings(mesh, specs, batch_axes):
    out = {}
    for k, v in specs.items():
        spec = [None] * len(v.shape)
        spec[0] = batch_axes if _fits(v.shape[0], mesh, batch_axes) else None
        if spec[0] is None and len(v.shape) >= 2 and _fits(v.shape[1], mesh, ("model",)):
            spec[1] = "model"  # long-context single-seq: shard sequence
        out[k] = NamedSharding(mesh, P(*spec))
    return out


def pick_n_micro(cfg, shape_cfg, n_data: int) -> int:
    if shape_cfg.kind != "train":
        return 1
    per_dev = shape_cfg.global_batch // n_data
    # keep per-microbatch device tokens bounded for activation headroom;
    # cross-attention multiplies every token's activations by encoder_seq,
    # so enc-dec models microbatch much harder.
    budget = 4096 if cfg.cross_attention else 16384
    tokens = per_dev * shape_cfg.seq_len
    n_micro = 1
    while tokens // n_micro > budget and n_micro < per_dev:
        n_micro *= 2
    return n_micro


def _lower_one(cfg, sc, mesh, batch_axes, n_micro):
    """Build and lower the step function for one config variant."""
    key = jax.random.PRNGKey(0)
    params_abs = jax.eval_shape(lambda: T.init_params(cfg, key))
    p_sh = params_shardings(mesh, params_abs)
    specs = input_specs(cfg, sc)
    b_sh = batch_shardings(mesh, specs, batch_axes)
    if sc.kind == "train":
        opt_cfg = OptConfig()
        opt_abs = jax.eval_shape(lambda p: adamw_init(p, opt_cfg), params_abs)
        # moments always data-sharded (ZeRO-1) even when params are TP-only
        o_sh = dict(
            step=NamedSharding(mesh, P()),
            mu=params_shardings(mesh, opt_abs["mu"], force_fsdp=True),
            nu=params_shardings(mesh, opt_abs["nu"], force_fsdp=True),
        )
        step_fn = make_train_step(cfg, OptConfig(), n_micro=n_micro, as_fn=True)
        jitted = jax.jit(step_fn, in_shardings=(p_sh, o_sh, b_sh),
                         donate_argnums=(0, 1))
        return jitted.lower(params_abs, opt_abs, specs)
    if sc.kind == "prefill":
        jitted = jax.jit(lambda p, b: T.prefill(p, cfg, b), in_shardings=(p_sh, b_sh))
        return jitted.lower(params_abs, specs)
    cache_abs = jax.eval_shape(lambda: T.init_cache(cfg, sc.global_batch, sc.seq_len))
    c_sh = cache_shardings(mesh, cache_abs, batch_axes)
    bspec = batch_axes if _fits(sc.global_batch, mesh, batch_axes) else None
    tok_sh = NamedSharding(mesh, P(bspec, None))
    pos_sh = NamedSharding(mesh, P(bspec))
    jitted = jax.jit(
        lambda p, c, t, pos: T.serve_step(p, cfg, c, t, pos),
        in_shardings=(p_sh, c_sh, tok_sh, pos_sh),
        donate_argnums=(1,),
    )
    return jitted.lower(params_abs, cache_abs, specs["tokens"], specs["pos"])


def _cost_of(compiled, hlo):
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    coll = RL.collective_bytes(hlo)
    return dict(flops=float(ca.get("flops", 0.0)),
                bytes=float(ca.get("bytes accessed", 0.0)),
                coll=float(coll["total"]), coll_detail=coll)


def extrapolated_cost(cfg, sc, mesh, batch_axes):
    """XLA's cost_analysis counts scan/while bodies ONCE, so the full-model
    compile undercounts by ~n_layers.  Recover exact totals from two small
    *unrolled* lowers: cost(L=2*plen) - cost(L=plen) = one super-block;
    total = cost(plen) + delta * (n_layers/plen - 1).  Microbatch
    accumulation flops are invariant to n_micro (same total tokens), so the
    small lowers use n_micro=1."""
    import dataclasses as dc

    from repro.models.transformer import _plen

    plen = _plen(cfg)
    c1 = dc.replace(cfg, n_layers=plen, scan_layers=False)
    c2 = dc.replace(cfg, n_layers=2 * plen, scan_layers=False)
    costs = []
    for c in (c1, c2):
        lowered = _lower_one(c, sc, mesh, batch_axes, n_micro=1)
        comp = lowered.compile()
        costs.append(_cost_of(comp, comp.as_text()))
    n_blocks = cfg.n_layers / plen
    out = {}
    for k in ("flops", "bytes", "coll"):
        delta = costs[1][k] - costs[0][k]
        out[k] = costs[0][k] + delta * (n_blocks - 1)
    out["per_block"] = {k: costs[1][k] - costs[0][k] for k in ("flops", "bytes", "coll")}
    out["coll_detail"] = costs[1]["coll_detail"]
    return out


def lower_cell(arch: str, shape: str, multi_pod: bool = False, compile_: bool = True,
               verbose: bool = True, cfg_override=None):
    cfg = cfg_override or get_arch(arch)
    sc = SHAPES[shape]
    ok, reason = cell_is_supported(cfg, sc)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    if not ok:
        return dict(arch=arch, shape=shape, mesh=mesh_name, status="skipped", reason=reason)
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    set_mesh(mesh)
    # size-aware parallelism policy: small models replicate weights (pure
    # DP) — TP-sharding them buys nothing and costs activation all-reduces
    # (measured on whisper-base: 14x collective-bytes reduction; see
    # EXPERIMENTS.md §Perf).  Decided on the FULL config, not the reduced
    # extrapolation configs.
    from repro.models.common import set_fsdp, set_tp

    use_tp = cfg.param_count() >= 1.5e9
    set_tp(use_tp)
    # ZeRO policy: FSDP the parameters only when the TP shard doesn't fit
    # comfortably (> ~6 GB of 16 GB HBM); otherwise TP-only params with
    # data-sharded optimizer moments (ZeRO-1) — kills the per-microbatch
    # param re-gathers (§Perf iteration L1).
    tp_deg = mesh.shape["model"] if use_tp else 1
    set_fsdp(cfg.param_count() * 2 / tp_deg > 6e9)
    # batch axes: everything that is not TP; fall back to shorter axis
    # tuples until the global batch divides (e.g. decode_32k's B=128 on a
    # 256-way pure-DP mesh shards over 'data' only).
    if use_tp:
        cand = [("pod", "data"), ("data",)] if multi_pod else [("data",)]
    else:
        cand = (
            [("pod", "data", "model"), ("data", "model"), ("pod", "data"), ("data",)]
            if multi_pod
            else [("data", "model"), ("data",)]
        )
    batch_axes = cand[-1]
    for c in cand:
        if _fits(sc.global_batch, mesh, c):
            batch_axes = c
            break
    n_devices = mesh.size
    n_data = 1
    for a in batch_axes:
        n_data *= mesh.shape[a]
    n_micro = pick_n_micro(cfg, sc, n_data)

    with mesh:
        lowered = _lower_one(cfg, sc, mesh, batch_axes, n_micro)
        t_lower = time.time() - t0
        result = dict(arch=arch, shape=shape, mesh=mesh_name, status="lowered",
                      n_micro=n_micro, lower_s=round(t_lower, 1))
        if compile_:
            # 1) full compile: proves the cell builds + memory analysis
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            ma = compiled.memory_analysis()
            if multi_pod:
                # multi-pod pass proves the 'pod' axis shards; the roofline
                # table is single-pod only (spec) — skip cost extrapolation
                result.update(
                    status="compiled",
                    compile_s=round(t_compile, 1),
                    memory=dict(
                        temp_bytes=float(getattr(ma, "temp_size_in_bytes", 0) or 0),
                        arg_bytes=float(getattr(ma, "argument_size_in_bytes", 0) or 0),
                        out_bytes=float(getattr(ma, "output_size_in_bytes", 0) or 0),
                    ),
                )
                if verbose:
                    print(f"  memory_analysis: {ma}")
                return result
            # 2) cost extrapolation from two small unrolled lowers
            cost = extrapolated_cost(cfg, sc, mesh, batch_axes)
            rl = RL.Roofline(
                arch=arch, shape=shape, mesh=mesh_name,
                flops=cost["flops"], bytes_accessed=cost["bytes"],
                coll_bytes=cost["coll"], coll_detail=cost["coll_detail"],
                model_flops=RL.model_flops_per_device(cfg, sc, n_devices),
                peak_mem_bytes=float(getattr(ma, "temp_size_in_bytes", 0) or 0),
                device_kind=RL.V5E,
            )
            result.update(
                status="compiled",
                compile_s=round(t_compile, 1),
                roofline=rl.to_dict(),
                memory=dict(
                    temp_bytes=float(getattr(ma, "temp_size_in_bytes", 0) or 0),
                    arg_bytes=float(getattr(ma, "argument_size_in_bytes", 0) or 0),
                    out_bytes=float(getattr(ma, "output_size_in_bytes", 0) or 0),
                ),
            )
            if verbose:
                print(f"  memory_analysis: {ma}")
                print(f"  cost: flops/dev={rl.flops:.3e} bytes/dev={rl.bytes_accessed:.3e} "
                      f"coll/dev={rl.coll_bytes:.3e}")
                print(f"  roofline: compute={rl.t_compute*1e3:.2f}ms memory={rl.t_memory*1e3:.2f}ms "
                      f"collective={rl.t_collective*1e3:.2f}ms -> {rl.bottleneck}"
                      f" (useful={rl.useful_ratio:.2f}, frac={rl.roofline_fraction:.2f})")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--no-compile", action="store_true")
    ap.add_argument("--out", default="runs/dryrun")
    args = ap.parse_args()

    cells = []
    archs = list_archs() if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}_{shape}_{'mp' if mp else 'sp'}"
                print(f"[dryrun] {tag}")
                try:
                    res = lower_cell(arch, shape, multi_pod=mp, compile_=not args.no_compile)
                except Exception as e:
                    traceback.print_exc()
                    res = dict(arch=arch, shape=shape, mesh=mp, status="FAILED",
                               error=f"{type(e).__name__}: {e}")
                    failures += 1
                with open(os.path.join(args.out, tag + ".json"), "w") as f:
                    json.dump(res, f, indent=2, default=str)
                print(f"  -> {res['status']}")
                cells.append(res)
    print(f"[dryrun] {len(cells)} cells, {failures} failures")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
