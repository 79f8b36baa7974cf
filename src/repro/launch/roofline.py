"""Roofline accounting from compiled dry-run artifacts.

Three terms per (arch x shape x mesh), in seconds, from the peaks of the
chip the cell targets (``PEAKS``, keyed by ``device_kind``):

  compute    = HLO_FLOPs_per_device / peak FLOP/s   (bf16 per chip)
  memory     = HLO_bytes_per_device / HBM bytes/s
  collective = collective_bytes_per_device / ICI bytes/s

``cost_analysis`` of the SPMD-partitioned executable reports the
*per-device* program, so flops/bytes need no further division.
Collective bytes are parsed from the partitioned HLO: the summed result
sizes of all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute ops (result size ~= bytes crossing this device's
links for AG/AR; a mild overcount for RS — noted in EXPERIMENTS.md).
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Optional

# ``jax.devices()[0].device_kind`` of a TPU v5e chip.
V5E = "TPU v5 lite"

# Published per-chip peaks by device kind.  TPU v5e: Google Cloud
# documentation, "TPU v5e" — 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s,
# 1,600 Gbit/s of inter-chip interconnect.
PEAKS = {
    V5E: dict(flops=197e12, hbm_bw=819e9, ici_bw=1600e9 / 8),
}


def peaks(device_kind: str) -> dict:
    """``{flops, hbm_bw, ici_bw}`` (per second) for one chip; a device kind
    with no published peaks here is an error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}: add them "
            "to roofline.PEAKS with their source"
        ) from None


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*(?:\(([^)]*)\)|(\S+))\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"[-a-z]*\(",
)
_SHAPE_RE = re.compile(r"(pred|bf16|f16|f32|f64|s8|u8|s16|u16|s32|u32|s64|u64)\[([0-9,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per collective kind: summed result bytes in the per-device program."""
    out = {"all-gather": 0, "all-reduce": 0, "reduce-scatter": 0,
           "all-to-all": 0, "collective-permute": 0, "count": 0}
    for m in _COLL_RE.finditer(hlo_text):
        shape_str = m.group(1) or m.group(2)
        kind = m.group(3)
        out[kind] += _shape_bytes(shape_str)
        out["count"] += 1
    out["total"] = sum(out[k] for k in
                       ("all-gather", "all-reduce", "reduce-scatter",
                        "all-to-all", "collective-permute"))
    return out


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    flops: float  # per device
    bytes_accessed: float  # per device
    coll_bytes: float  # per device
    coll_detail: dict
    model_flops: float  # useful flops per device (6ND / 2ND)
    peak_mem_bytes: float  # from memory_analysis
    device_kind: str  # the chip whose PEAKS bound the terms

    def __post_init__(self):
        self.peaks = peaks(self.device_kind)

    @property
    def t_compute(self) -> float:
        return self.flops / self.peaks["flops"]

    @property
    def t_memory(self) -> float:
        return self.bytes_accessed / self.peaks["hbm_bw"]

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / self.peaks["ici_bw"]

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def bound_time(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_ratio(self) -> float:
        return self.model_flops / self.flops if self.flops else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Fraction of peak the *useful* model flops achieve at the bound."""
        if self.bound_time == 0:
            return 0.0
        return (self.model_flops / self.peaks["flops"]) / self.bound_time

    def to_dict(self) -> dict:
        return dict(
            arch=self.arch, shape=self.shape, mesh=self.mesh,
            flops=self.flops, bytes_accessed=self.bytes_accessed,
            coll_bytes=self.coll_bytes, coll_detail=self.coll_detail,
            model_flops=self.model_flops, peak_mem_bytes=self.peak_mem_bytes,
            device_kind=self.device_kind,
            t_compute=self.t_compute, t_memory=self.t_memory,
            t_collective=self.t_collective, bottleneck=self.bottleneck,
            useful_ratio=self.useful_ratio,
            roofline_fraction=self.roofline_fraction,
        )


def model_flops_per_device(cfg, shape_cfg, n_devices: int) -> float:
    """6·N_active·tokens for training, 2·N_active·tokens for decode."""
    n_active = cfg.active_param_count()
    if shape_cfg.kind == "train":
        tokens = shape_cfg.global_batch * shape_cfg.seq_len
        mult = 6.0
    elif shape_cfg.kind == "prefill":
        tokens = shape_cfg.global_batch * shape_cfg.seq_len
        mult = 2.0
    else:  # decode: one token per sequence
        tokens = shape_cfg.global_batch
        mult = 2.0
    return mult * n_active * tokens / n_devices


def build(arch: str, shape: str, mesh_name: str, cfg, shape_cfg, compiled,
          hlo_text: str, n_devices: int, device_kind: str) -> Roofline:
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    flops = float(ca.get("flops", 0.0))
    byt = float(ca.get("bytes accessed", 0.0))
    coll = collective_bytes(hlo_text)
    ma = compiled.memory_analysis()
    peak = 0.0
    for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                 "output_size_in_bytes", "generated_code_size_in_bytes"):
        peak += float(getattr(ma, attr, 0.0) or 0.0)
    return Roofline(
        arch=arch, shape=shape, mesh=mesh_name,
        flops=flops, bytes_accessed=byt,
        coll_bytes=float(coll["total"]), coll_detail=coll,
        model_flops=model_flops_per_device(cfg, shape_cfg, n_devices),
        peak_mem_bytes=peak, device_kind=device_kind,
    )
