"""Runtime environment tuning for serving hosts (SNIPPETS.md 1/2 pattern).

The HomebrewNLP/olmax launch scripts bake two classes of host tuning into
``run.sh`` before the Python process starts: (1) ``LD_PRELOAD`` tcmalloc —
XLA's host allocator pressure under many small per-round transfers is
exactly the workload glibc malloc fragments on — and (2) XLA/JAX process
flags (``--xla_force_host_platform_device_count`` for SPMD-on-CPU,
quieting TF logging).  The platform is never pinned here: JAX picks the
accelerator when there is one.  Neither can be applied from
inside an already-initialized process: ``LD_PRELOAD`` is consumed by the
dynamic linker at exec time, and ``XLA_FLAGS`` is read when the backend
initializes.  So this module is detect-and-advise:

* ``detect()``  — what is active right now (and what is available),
* ``advise()``  — the recommended settings with active/inactive flags,
* ``shell_exports()`` — copy-pasteable ``export`` lines for a launcher,
* ``apply()``   — best-effort: set the env vars that are still unset in
  an environment dict BEFORE jax is imported (no-op for LD_PRELOAD),
* ``describe()`` — the one-line summary benchmarks print so every
  committed number says which tunings it ran under,
* ``enable_compile_cache()`` — JAX's persistent compilation cache, called
  by the entry points (``chip_smoke.py``, ``benchmarks/run.py``).

CLI::

    python -m repro.launch.env            # report + export lines
"""
from __future__ import annotations

import glob
import os
from typing import Optional

# Where distros put gperftools' tcmalloc (Debian/Ubuntu multiarch, generic
# /usr/lib, conda).  First existing match wins.
TCMALLOC_GLOBS = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc*.so*",
    "/usr/lib/aarch64-linux-gnu/libtcmalloc*.so*",
    "/usr/lib/libtcmalloc*.so*",
    "/usr/local/lib/libtcmalloc*.so*",
    "/opt/conda/lib/libtcmalloc*.so*",
)

# Matches SNIPPETS.md 1: silence the TF/XLA C++ log spew that otherwise
# dominates serving stdout.
TF_LOG_LEVEL = "4"

# The compile cache's home when JAX_COMPILATION_CACHE_DIR is unset: a fixed
# directory of the checkout (git-ignored).  JAX keys entries on the path,
# so it must not move between runs.
COMPILE_CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..", ".jax_cache"
))


def find_tcmalloc() -> Optional[str]:
    for pat in TCMALLOC_GLOBS:
        hits = sorted(glob.glob(pat))
        if hits:
            return hits[0]
    return None


def detect(env: Optional[dict] = None) -> dict:
    """What the current process environment actually has."""
    env = os.environ if env is None else env
    ld = env.get("LD_PRELOAD", "")
    xla = env.get("XLA_FLAGS", "")
    ndev = None
    for tok in xla.split():
        if tok.startswith("--xla_force_host_platform_device_count="):
            try:
                ndev = int(tok.split("=", 1)[1])
            except ValueError:
                pass
    return {
        "tcmalloc_path": find_tcmalloc(),
        "tcmalloc_active": "tcmalloc" in ld,
        "ld_preload": ld,
        "xla_flags": xla,
        "host_device_count": ndev,
        "jax_platforms": env.get("JAX_PLATFORMS", ""),
        "tf_log_level": env.get("TF_CPP_MIN_LOG_LEVEL", ""),
        "cpus": os.cpu_count() or 1,
    }


def advise(host_devices: Optional[int] = None,
           env: Optional[dict] = None) -> list[dict]:
    """Recommended settings as ``{var, value, active, reason}`` rows.
    ``active`` means the current environment already satisfies the row.
    tcmalloc is only advised when the library exists on this host."""
    d = detect(env)
    if host_devices is None:
        host_devices = max(1, min(8, d["cpus"]))
    rows = []
    if d["tcmalloc_path"]:
        rows.append({
            "var": "LD_PRELOAD",
            "value": d["tcmalloc_path"],
            "active": d["tcmalloc_active"],
            "reason": "tcmalloc beats glibc malloc under XLA's host-buffer "
                      "churn (SNIPPETS.md 1/2); must be set before exec",
        })
    rows.append({
        "var": "XLA_FLAGS",
        "value": f"--xla_force_host_platform_device_count={host_devices}",
        "active": d["host_device_count"] is not None,
        "reason": "expose N host devices so SPMD sharding (DESIGN.md §6) "
                  "has a mesh on CPU",
    })
    rows.append({
        "var": "TF_CPP_MIN_LOG_LEVEL",
        "value": TF_LOG_LEVEL,
        "active": d["tf_log_level"] == TF_LOG_LEVEL,
        "reason": "silence XLA C++ logging on the serving path",
    })
    return rows


def apply(env: Optional[dict] = None, *, host_devices: Optional[int] = None,
          overwrite: bool = False) -> dict:
    """Set the advisable env vars that can still take effect in-process —
    i.e. everything except ``LD_PRELOAD`` — into ``env`` (default
    ``os.environ``).  Only useful BEFORE jax initializes its backend;
    existing values are kept unless ``overwrite``.  Returns {var: value}
    actually written."""
    env = os.environ if env is None else env
    applied = {}
    for row in advise(host_devices=host_devices, env=env):
        var = row["var"]
        if var == "LD_PRELOAD":
            continue  # the dynamic linker already ran; advising only
        if var in env and not overwrite:
            continue
        env[var] = row["value"]
        applied[var] = row["value"]
    return applied


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Call from an entry point before the first compile, never at import.
    When ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it and no
    other directory is set here; otherwise the cache goes to
    ``COMPILE_CACHE_DIR``.  Every program is cached, however small or quick
    to compile: a round's entry points compile in well under JAX's default
    one-second floor, and a fresh machine would otherwise recompile them on
    every run.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def shell_exports(host_devices: Optional[int] = None) -> str:
    """Copy-pasteable launcher prelude (the run.sh pattern)."""
    return "\n".join(
        f"export {row['var']}={row['value']}"
        for row in advise(host_devices=host_devices)
    )


def describe(env: Optional[dict] = None) -> str:
    """One-line active-tunings summary for bench headers."""
    d = detect(env)
    parts = [
        f"cpus={d['cpus']}",
        "tcmalloc=" + ("on" if d["tcmalloc_active"] else
                       ("avail" if d["tcmalloc_path"] else "absent")),
        "host_devices=" + (str(d["host_device_count"])
                           if d["host_device_count"] is not None else "unset"),
        "platforms=" + (d["jax_platforms"] or "auto"),
    ]
    return " ".join(parts)


def main() -> int:
    d = detect()
    print("# runtime environment (detected)")
    for k, v in d.items():
        print(f"  {k}: {v!r}")
    print("# advised (— active, * not yet active)")
    for row in advise():
        mark = "—" if row["active"] else "*"
        print(f"  {mark} {row['var']}={row['value']}  # {row['reason']}")
    print("# launcher prelude")
    print(shell_exports())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
