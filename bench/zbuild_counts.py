"""Work the Z build of one HOOI sweep must do, and the least time for it.

Counted from the algorithm, as ``bench/counts.py`` counts the sweep, so it
reads the same whichever kernel or XLA ops build Z. Per sweep, on the
bottleneck rank (``E_max`` elements and ``R_max`` rows per mode, from the
plan's ``SchemeMetrics.per_mode``), for each mode n with K̂_n the product
of the other modes' ranks:

* operations: ``2 * E_max * K̂_n`` (each of an element's K̂_n
  contributions multiplied out of its last Kronecker level and added into
  Z) plus the leading Kronecker products, ``E_max * (K_j1 + K_j1 K_j2 +
  ... )`` over the other modes but the last (the value times the first
  factor row, and so on);
* bytes: Z written once (``R_max * K̂_n`` float32), one read of the COO
  (int32 coordinates and a float32 value per element) and one factor row
  per element and other mode (``E_max * sum_j K_j`` float32).

``least_time_s`` divides each by its peak from ``peaks.json`` and takes the
larger; ``bound`` says which one binds.
"""

from __future__ import annotations

import math

from bench.counts import COORD_BYTES, VALUE_BYTES, khat


def _others(core_dims, mode: int) -> list[int]:
    return [int(k) for j, k in enumerate(core_dims) if j != mode]


def flops_per_sweep(core_dims, per_mode) -> int:
    """Z-build operations of one sweep on the bottleneck rank."""
    total = 0
    for n, m in enumerate(per_mode):
        lead = _others(core_dims, n)[:-1]
        kron = sum(math.prod(lead[:i + 1]) for i in range(len(lead)))
        total += int(m.E_max) * (2 * khat(core_dims, n) + kron)
    return total


def bytes_per_sweep(core_dims, per_mode) -> int:
    """Z-build HBM bytes of one sweep on the bottleneck rank."""
    N = len(core_dims)
    total = 0
    for n, m in enumerate(per_mode):
        E = int(m.E_max)
        total += (int(m.R_max) * khat(core_dims, n) * VALUE_BYTES
                  + E * (N * COORD_BYTES + VALUE_BYTES)
                  + E * sum(_others(core_dims, n)) * VALUE_BYTES)
    return total


def least_time_s(core_dims, per_mode, peak: dict) -> tuple[float, str]:
    """(least Z-build seconds per sweep, "flops" or "bytes")."""
    t_f = flops_per_sweep(core_dims, per_mode) / float(peak["bf16_flops_per_s"])
    t_b = bytes_per_sweep(core_dims, per_mode) / float(peak["hbm_bytes_per_s"])
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")
