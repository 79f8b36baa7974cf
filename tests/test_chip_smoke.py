"""``chip_smoke.py`` off the chip: its rehearsal runs every phase at a tiny
size on the CPU (one device, and four forced host devices for the mesh
path), it refuses to run without a TPU, and the compile-cache helper it
calls puts entries where it says."""
import json
import os
import subprocess
import sys

import pytest

from repro.launch import env as envmod

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run(args, tmp_path, **extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"), **extra)
    return subprocess.run([sys.executable, SMOKE, *args], capture_output=True,
                          text=True, env=env, timeout=300)


@pytest.mark.parametrize("chips,phases", [
    (1, ("device", "ppsp", "mutation", "pallas")),
    (4, ("device", "mesh")),
])
def test_chip_smoke_rehearsal(tmp_path, chips, phases):
    extra = {}
    if chips == 4:
        extra["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    r = _run(["--rehearse", "--chips", str(chips)], tmp_path, **extra)
    assert r.returncode == 0, r.stderr[-3000:]
    lines = r.stdout.strip().splitlines()
    assert [ln.split(":")[0] for ln in lines[:-1]] == list(phases)
    last = json.loads(lines[-1])
    assert last == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": chips}}
    if chips == 1:
        assert "recompiles=0" in lines[2] and "compiled_kernel=False" in lines[3]


def test_chip_smoke_fails_without_a_tpu(tmp_path):
    r = _run([], tmp_path)
    assert r.returncode != 0
    assert r.stdout == ""
    assert "no TPU" in r.stderr


@pytest.mark.parametrize("from_env", [True, False], ids=["env_var", "checkout"])
def test_compile_cache_lands_in_its_directory(tmp_path, from_env):
    """With JAX_COMPILATION_CACHE_DIR set JAX writes there; unset, the
    helper's fixed directory in the checkout, which git ignores."""
    want = str(tmp_path / "cc") if from_env else envmod.COMPILE_CACHE_DIR
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(ROOT, "src"))
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = want
    before = set(os.listdir(want)) if os.path.isdir(want) else set()
    # a constant unique to this run gives the program a fresh cache key
    script = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.env import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        f"jax.jit(lambda x: x * {abs(hash(str(tmp_path))) % 2**30} + 1)"
        "(jnp.arange(3)).block_until_ready()\n"
    )
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip().splitlines()[-1] == want
    assert set(os.listdir(want)) - before, "no new cache entry"
    if not from_env:
        with open(os.path.join(ROOT, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
