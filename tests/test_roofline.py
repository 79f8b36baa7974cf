"""Roofline accounting: HLO collective parsing and term arithmetic; plus a
reduced-config dry-run smoke (the production dryrun machinery on an 8-device
subprocess mesh)."""
import os
import subprocess
import sys
import textwrap

import pytest

from repro.launch import roofline as RL


HLO = """
  %ag = f32[8,128]{1,0} all-gather(f32[8,8]{1,0} %x), replica_groups={}
  %ar = bf16[64]{0} all-reduce(bf16[64]{0} %y), to_apply=%add
  %rs = f32[4,4]{1,0} reduce-scatter(f32[4,64]{1,0} %z), dimensions={1}
  %aa = (s32[16]{0}, s32[16]{0}) all-to-all(s32[16]{0} %a, s32[16]{0} %b)
  %cp = u8[100]{0} collective-permute(u8[100]{0} %c)
  %dot = f32[8,8]{1,0} dot(f32[8,8] %p, f32[8,8] %q)
"""


def test_collective_bytes_parse():
    out = RL.collective_bytes(HLO)
    assert out["all-gather"] == 8 * 128 * 4
    assert out["all-reduce"] == 64 * 2
    assert out["reduce-scatter"] == 4 * 4 * 4
    assert out["all-to-all"] == 16 * 4 * 2
    assert out["collective-permute"] == 100
    assert out["count"] == 5
    assert out["total"] == sum(
        out[k] for k in ("all-gather", "all-reduce", "reduce-scatter",
                         "all-to-all", "collective-permute"))


def test_roofline_terms():
    r = RL.Roofline(
        arch="a", shape="s", mesh="m",
        flops=197e12, bytes_accessed=819e9, coll_bytes=200e9,
        coll_detail={}, model_flops=98.5e12, peak_mem_bytes=0,
        device_kind="TPU v5 lite",
    )
    assert abs(r.t_compute - 1.0) < 1e-9
    assert abs(r.t_memory - 1.0) < 1e-9
    assert abs(r.t_collective - 1.0) < 1e-9  # 1,600 Gbit/s = 200 GB/s
    assert r.useful_ratio == 0.5
    assert abs(r.roofline_fraction - 0.5) < 1e-9
    assert r.to_dict()["device_kind"] == "TPU v5 lite"


def test_roofline_unknown_device_is_an_error():
    with pytest.raises(ValueError, match="no published peaks"):
        RL.peaks("TPU v99")
    with pytest.raises(ValueError, match="no published peaks"):
        RL.Roofline(
            arch="a", shape="s", mesh="m", flops=1.0, bytes_accessed=1.0,
            coll_bytes=1.0, coll_detail={}, model_flops=1.0,
            peak_mem_bytes=0, device_kind="cpu",
        )


def test_model_flops_train_vs_decode():
    from repro.configs import SHAPES, get_arch

    cfg = get_arch("tinyllama-1.1b")
    tr = RL.model_flops_per_device(cfg, SHAPES["train_4k"], 256)
    de = RL.model_flops_per_device(cfg, SHAPES["decode_32k"], 256)
    n = cfg.active_param_count()
    assert abs(tr - 6 * n * 4096 * 256 / 256) / tr < 1e-6
    assert abs(de - 2 * n * 128 / 256) / de < 1e-6


DRYRUN_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses as dc
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import SHAPES, get_arch, reduced, input_specs
    from repro.launch import dryrun as DR
    from repro.launch.mesh import make_mesh
    from repro.models.common import set_mesh, set_tp
    from repro.launch import roofline as RL

    mesh = make_mesh((2, 4), ("data", "model"))
    set_mesh(mesh)
    sc = dc.replace(SHAPES["train_4k"], seq_len=64, global_batch=4)
    for arch in ("tinyllama-1.1b", "arctic-480b", "mamba2-780m"):
        cfg = dc.replace(reduced(get_arch(arch)), vocab=512)
        set_tp(True)
        lowered = DR._lower_one(cfg, sc, mesh, ("data",), n_micro=2)
        compiled = lowered.compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        assert float(ca.get("flops", 0)) > 0, arch
        coll = RL.collective_bytes(compiled.as_text())
        print(arch, "OK", coll["count"])
    print("DRYRUN_SMOKE_OK")
    """
)


def test_dryrun_machinery_reduced_mesh():
    """lower+compile+cost path works end-to-end on a small subprocess mesh
    (the production 512-device sweep uses the same code)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    # the forced host devices exist only on the cpu platform
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", DRYRUN_SCRIPT], capture_output=True,
                       text=True, env=env, timeout=540)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "DRYRUN_SMOKE_OK" in r.stdout
