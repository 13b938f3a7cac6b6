"""Jit'd dispatch wrappers around the Pallas kernels.

Public entry points used by the rest of the framework:

  * ``penultimate(coords, values, factors, mode, num_rows)`` — kernel-backed
    drop-in for repro.core.ttm.penultimate / penultimate_local.
  * ``window_fold_step(rows, factors, mode, num_rows)`` — the chunk step
    of the sorted-rows Z build through the windowed kernel.
  * ``oracle_pair(Z, x, y)`` — fused Lanczos oracle.

The wrappers (i) prepare kernel-friendly layouts (fold the N-2 leading
Kronecker levels into ``a``, sort elements by row id), (ii) enforce the VMEM
budget and fall back to the pure-jnp reference when a shape doesn't fit, and
(iii) select interpret mode automatically (interpret=True off-TPU, compiled
on TPU).
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core.ttm import ELEMENT_CHUNK

from . import ref
from .kron_segsum import (  # noqa: F401
    ROW_BLOCK, kron_segsum, kron_segsum_oracle, tile_geometry)
from .oracle_fused import oracle_pair as _oracle_pair_kernel
from .window_segsum import (WindowGeometry, chunk_pairs, mode_pairs,
                            panel_widths, window_geometry, window_segsum)

__all__ = ["penultimate", "penultimate_local", "penultimate_sorted",
           "penultimate_sorted_oracle", "oracle_pair", "kernel_fits_vmem",
           "windowed_fits_vmem", "windowed_geometry", "window_fold_step",
           "split_kron_dims", "vmem_budget_bytes"]

# default admission budget for one launch's VMEM footprint
# (``TileGeometry.vmem_bytes``), in bytes: a quarter of a v5e core's
# 128 MiB. Override per platform with REPRO_VMEM_BUDGET or the
# vmem_budget parameter on the gate.
_VMEM_BUDGET = 32 * 1024 * 1024


def vmem_budget_bytes() -> int:
    """The admission budget for resident kernel tiles, in bytes.

    ``REPRO_VMEM_BUDGET`` (bytes; parsed and validated by
    ``repro.envknobs``) overrides the default, so a TPU with less (or
    more) VMEM per core than a v5e can be served without a code change.
    """
    from repro import envknobs

    budget = envknobs.vmem_budget()
    return _VMEM_BUDGET if budget is None else budget


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def kernel_fits_vmem(num_rows: int, Ka: int, Kb: int,
                     block_e: int = 256, *, precision: str = "f32",
                     oracle_s: int = 0,
                     vmem_budget: int | None = None) -> bool:
    """Admission gate: does this launch's resident footprint fit the budget?

    Derives the footprint from the same ``tile_geometry`` the kernel uses
    (bf16 halves the C-block term; a fused oracle panel adds its X slab and
    accumulator), so the gate can never drift from the kernel's allocation.
    """
    geom = tile_geometry(num_rows, Ka, Kb, block_e,
                         itemsize=2 if precision == "bf16" else 4,
                         oracle_s=oracle_s)
    budget = vmem_budget_bytes() if vmem_budget is None else vmem_budget
    return geom.vmem_bytes <= budget


def windowed_geometry(Ka: int, Kb: int, *, precision: str = "f32",
                      vmem_budget: int | None = None
                      ) -> WindowGeometry | None:
    """The windowed kernel's launch for K̂ = Ka·Kb, or None where no launch
    fits the budget: all K̂ columns in one launch where that fits, else the
    widest column panel (``window_segsum.panel_widths``) that does. The
    footprint (``WindowGeometry.vmem_bytes``) depends on the columns a
    launch builds, not on the number of rows."""
    budget = vmem_budget_bytes() if vmem_budget is None else vmem_budget
    for panel in [None] + panel_widths(Ka, Kb):
        geom = window_geometry(Ka, Kb, precision, panel)
        if geom.vmem_bytes <= budget:
            return geom
    return None


def windowed_fits_vmem(Ka: int, Kb: int, *, precision: str = "f32",
                       vmem_budget: int | None = None) -> bool:
    """Admission gate of the windowed kernel: some launch of it, whole or
    in column panels, fits the budget (``windowed_geometry``)."""
    return windowed_geometry(Ka, Kb, precision=precision,
                             vmem_budget=vmem_budget) is not None


def split_kron_dims(core_dims: Sequence[int], mode: int) -> tuple[int, int]:
    """(Ka, Kb) that ``_split_ab`` will produce for these factor widths.

    Lets callers (the executor's step-key logic) evaluate the VMEM gate
    before any array exists: b takes the last non-mode factor's width, a
    takes the product of the rest.
    """
    other = [j for j in range(len(core_dims)) if j != mode]
    *lead, last = other
    Ka = 1
    for j in lead:
        Ka *= int(core_dims[j])
    return Ka, int(core_dims[last])


def _split_ab(
    coords: jnp.ndarray,
    values: jnp.ndarray,
    factors: Sequence[jnp.ndarray],
    mode: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fold modes j != mode into (a, b): a = val * kron(leading rows),
    b = rows of the last non-mode factor (the widest kron level stays in the
    kernel's hot loop)."""
    other = [j for j in range(len(factors)) if j != mode]
    *lead, last = other
    nnz = values.shape[0]
    a = values[:, None]
    for j in lead:
        rows = jnp.take(factors[j], coords[:, j], axis=0)
        # explicit width (not -1): must also trace for nnz == 0
        a = (a[:, :, None] * rows[:, None, :]).reshape(
            nnz, a.shape[1] * rows.shape[1])
    b = jnp.take(factors[last], coords[:, last], axis=0)
    return a, b


def penultimate_sorted(
    coords: jnp.ndarray,
    values: jnp.ndarray,
    local_rows: jnp.ndarray,
    factors: Sequence[jnp.ndarray],
    mode: int,
    num_local_rows: int,
    *,
    use_kernel: bool = True,
    interpret: bool | None = None,
    block_e: int = 256,
    precision: str = "f32",
) -> jnp.ndarray:
    """Z^p for *pre-sorted* dense local row ids — the partition.py contract.

    ``repro.distributed.partition`` emits each rank's elements already sorted
    by dense-renumbered local row id (padding elements carry value 0 and the
    last real row id), which is exactly the kernel's precondition — so the
    distributed mode step skips the runtime ``argsort`` that
    ``penultimate_local`` pays for arbitrary row orders. All branching here
    is on static shape information, so this is safe to call inside a
    shard_map-traced step: the kernel/fallback choice is baked into the
    trace (and must therefore be part of the compiled-step cache key).
    """
    a, b = _split_ab(coords, values, factors, mode)
    Ka, Kb = a.shape[1], b.shape[1]
    if not use_kernel or not kernel_fits_vmem(num_local_rows, Ka, Kb, block_e,
                                              precision=precision):
        return ref.kron_segsum_ref(local_rows, a, b, num_local_rows,
                                   precision=precision)
    interpret = _interpret_default() if interpret is None else interpret
    return kron_segsum(
        local_rows.astype(jnp.int32),
        a.astype(jnp.float32),
        b.astype(jnp.float32),
        num_local_rows,
        block_e=block_e,
        interpret=interpret,
        precision=precision,
    )


def window_fold_step(local_rows: jnp.ndarray, factors: Sequence[jnp.ndarray],
                     mode: int, num_rows: int, *, precision: str = "f32"):
    """The step of the sorted-rows Z build's ``fold_elements`` loop over
    the carry ``(z, start)``: ``step((z, start), coords, values, rows)``
    adds the chunk of elements from ``start`` through the windowed kernel,
    in the launches ``windowed_geometry`` gives (one, or one per column
    panel), and moves ``start`` past it. The (element block, row window)
    pairs of the whole mode are computed here, once, and each step slices its
    chunk's, so chunks must start on block boundaries: ``block_e`` divides
    ``core.ttm.ELEMENT_CHUNK``. The factor-row gathers and the leading
    Kronecker levels (``_split_ab``) stay in XLA; ``z`` is the kernel's
    aliased output."""
    Ka, Kb = split_kron_dims([int(f.shape[1]) for f in factors], mode)
    geom = windowed_geometry(Ka, Kb, precision=precision)
    block_e = geom.block_e
    assert ELEMENT_CHUNK % block_e == 0, (ELEMENT_CHUNK, block_e)
    local_rows = local_rows.astype(jnp.int32)
    pairs = (mode_pairs(local_rows, block_e, num_rows)
             if local_rows.shape[0] else None)

    def step(carry, coords, values, rows):
        z, start = carry
        if not rows.shape[0]:
            return carry
        a, b = _split_ab(coords, values, factors, mode)
        z = window_segsum(
            rows.astype(jnp.int32), a, b, z,
            chunk_pairs(pairs, start, rows.shape[0], block_e, num_rows),
            interpret=_interpret_default(), precision=precision,
            panel=geom.panel)
        return z, start + rows.shape[0]

    return step


def penultimate_sorted_oracle(
    coords: jnp.ndarray,
    values: jnp.ndarray,
    local_rows: jnp.ndarray,
    factors: Sequence[jnp.ndarray],
    mode: int,
    num_local_rows: int,
    X: jnp.ndarray,  # (K_hat, s) first oracle panel
    *,
    use_kernel: bool = True,
    interpret: bool | None = None,
    block_e: int = 256,
    precision: str = "f32",
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused Z^p build + first oracle product ``(Z^p, Z^p @ X)``.

    Same contract as ``penultimate_sorted``; the fused kernel contracts the
    VMEM-resident Z tile against the panel before it is ever written to HBM.
    The fallback computes the product from the reference Z — numerically the
    same pipeline, without the HBM saving.
    """
    a, b = _split_ab(coords, values, factors, mode)
    Ka, Kb = a.shape[1], b.shape[1]
    if not use_kernel or not kernel_fits_vmem(
            num_local_rows, Ka, Kb, block_e, precision=precision,
            oracle_s=int(X.shape[1])):
        return ref.kron_segsum_oracle_ref(local_rows, a, b, num_local_rows,
                                          X, precision=precision)
    interpret = _interpret_default() if interpret is None else interpret
    return kron_segsum_oracle(
        local_rows.astype(jnp.int32),
        a.astype(jnp.float32),
        b.astype(jnp.float32),
        num_local_rows,
        X.astype(jnp.float32),
        block_e=block_e,
        interpret=interpret,
        precision=precision,
    )


def penultimate_local(
    coords: jnp.ndarray,
    values: jnp.ndarray,
    local_rows: jnp.ndarray,
    factors: Sequence[jnp.ndarray],
    mode: int,
    num_local_rows: int,
    *,
    use_kernel: bool = True,
    interpret: bool | None = None,
    block_e: int = 256,
    precision: str = "f32",
) -> jnp.ndarray:
    """Kernel-backed local penultimate matrix Z^p (see core.ttm).

    Accepts rows in any order; sorts before handing to the kernel. Callers
    that can guarantee sorted dense ids should use ``penultimate_sorted``.
    """
    if not use_kernel or not kernel_fits_vmem(
            num_local_rows, *split_kron_dims([f.shape[1] for f in factors],
                                             mode), block_e,
            precision=precision):
        a, b = _split_ab(coords, values, factors, mode)
        return ref.kron_segsum_ref(local_rows, a, b, num_local_rows,
                                   precision=precision)
    order = jnp.argsort(local_rows)
    return penultimate_sorted(
        coords[order], values[order], local_rows[order], factors, mode,
        num_local_rows, use_kernel=True, interpret=interpret, block_e=block_e,
        precision=precision)


def penultimate(
    coords: jnp.ndarray,
    values: jnp.ndarray,
    factors: Sequence[jnp.ndarray],
    mode: int,
    num_rows: int,
    **kw,
) -> jnp.ndarray:
    """Global Z_(n) (single-rank): rows are the raw mode-n coordinates."""
    return penultimate_local(
        coords, values, coords[:, mode], factors, mode, num_rows, **kw
    )


def oracle_pair(
    Z: jnp.ndarray,
    x: jnp.ndarray,
    y: jnp.ndarray,
    *,
    use_kernel: bool = True,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    if not use_kernel:
        return ref.oracle_pair_ref(Z, x, y)
    interpret = _interpret_default() if interpret is None else interpret
    return _oracle_pair_kernel(Z, x, y, interpret=interpret)
