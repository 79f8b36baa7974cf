"""Published chip peaks and the least bytes a frontier algorithm moves.

The peaks are a copy of ``repro.launch.roofline.PEAKS``, with its source.
``least_bytes`` counts only the work no frontier algorithm can skip, from
frontiers the benchmark's own reference works out, so the count is the
same whatever backend implements propagation.
"""
from __future__ import annotations

import numpy as np

# ``jax.devices()[0].device_kind`` of a TPU v5e chip.
V5E = "TPU v5 lite"

# Published per-chip peaks by device kind.  TPU v5e: Google Cloud
# documentation, "TPU v5e" -- 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s,
# 1,600 Gbit/s of inter-chip interconnect.
PEAKS = {
    V5E: dict(flops=197e12, hbm_bw=819e9, ici_bw=1600e9 / 8),
}

INDEX_BYTES = 4  # int32 src and dst ids of an arc


def peaks(device_kind: str) -> dict:
    """``{flops, hbm_bw, ici_bw}`` per second; a kind with no published
    peaks here is an error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}") from None


def least_bytes(frontiers, out_deg, *, value_bytes: int = 4,
                weight_bytes: int = 0) -> int:
    """Bytes that the supersteps with these frontiers cannot avoid.

    ``frontiers`` holds one array of vertex ids per superstep (and per
    direction, each with the out-degrees of the graph it walks).  Each arc
    leaving a frontier vertex costs its src and dst id, the source value it
    reads and, on a weighted graph, its weight; each frontier vertex costs
    one read and one write of its value.
    """
    out_deg = np.asarray(out_deg)
    arc = 2 * INDEX_BYTES + value_bytes + weight_bytes
    total = 0
    for f in frontiers:
        f = np.asarray(f, dtype=np.int64)
        total += arc * int(out_deg[f].sum()) + 2 * value_bytes * len(f)
    return total
