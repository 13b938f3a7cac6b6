"""Work one HOOI sweep must do, and the least time the chip could take.

Counted from the algorithm, not from the program's implementation, so it
reads the same whichever kernel builds Z. Per sweep, on the bottleneck
rank:

* operations: the paper's section 4.3 model, ``ttm_flops_max +
  svd_flops_max`` of ``repro.core.metrics.SchemeMetrics`` (TTM: 2 * E_max
  * K_hat per mode; oracle: Q_n = 4K queries * 2 * R_max * K_hat);
* bytes: per mode, one read of the rank's COO (int32 coordinates and a
  float32 value per element), one write of Z and one read of Z (R_max rows
  of K_hat float32 values).

``least_time_s`` divides each by its peak from ``peaks.json`` and takes the
larger; ``bound`` says which one binds.
"""

from __future__ import annotations

import json
import math
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")
VALUE_BYTES = 4  # float32 values and Z entries
COORD_BYTES = 4  # int32 coordinates


def peaks(device_kind: str, path: str = PEAKS_FILE) -> dict:
    """Peaks of one chip by ``device_kind``; an unknown kind is an error."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}")
    return table[device_kind]


def khat(core_dims, mode: int) -> int:
    return math.prod(int(k) for j, k in enumerate(core_dims) if j != mode)


def flops_per_sweep(core_dims, per_mode, lanczos_queries=None) -> int:
    """Section 4.3 operations on the bottleneck rank for one sweep.

    ``per_mode`` holds, per mode, objects with ``E_max`` and ``R_max``.
    """
    total = 0
    for n, m in enumerate(per_mode):
        kh = khat(core_dims, n)
        q = 4 * int(core_dims[n]) if lanczos_queries is None \
            else int(lanczos_queries[n])
        total += 2 * int(m.E_max) * kh + 2 * q * int(m.R_max) * kh
    return total


def bytes_per_sweep(core_dims, per_mode) -> int:
    """HBM bytes the sweep must move on the bottleneck rank."""
    N = len(core_dims)
    total = 0
    for n, m in enumerate(per_mode):
        coo = int(m.E_max) * (N * COORD_BYTES + VALUE_BYTES)
        z = int(m.R_max) * khat(core_dims, n) * VALUE_BYTES
        total += coo + 2 * z
    return total


def least_time_s(core_dims, per_mode, peak: dict) -> tuple[float, str]:
    """(least seconds per sweep, "flops" or "bytes": the bound that binds)."""
    t_f = flops_per_sweep(core_dims, per_mode) / float(peak["bf16_flops_per_s"])
    t_b = bytes_per_sweep(core_dims, per_mode) / float(peak["hbm_bytes_per_s"])
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")
