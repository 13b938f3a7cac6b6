"""Z-build stage: the §4.3 TTM hot spot, one implementation for every path.

Each HOOI mode step first materializes the (local) penultimate matrix
``Z = segment_sum(kron_contributions, rows)``. Two variants exist — the
pure-jnp reference and the Pallas ``kron_segsum`` kernel (the one-hot-matmul
reformulation, ``repro.kernels``) — and the choice is *static*: it is baked
into the trace, so executors must key compiled steps on it.

``resolve_kernel`` is the one gate: VMEM admission (``tile_geometry``) plus
the backend policy. ``use_kernel=None`` auto-engages the kernel on a real
TPU backend only (off-TPU it would run in interpret mode, far slower than
the reference) — unless the ``REPRO_FORCE_KERNEL=1`` environment variable is
set, which treats ``None`` as "kernel wherever it fits" so CI can run the
whole fast suite through the interpret-mode kernel path as a blocking job.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from repro import envknobs
from repro.core.ttm import fold_elements, kron_contributions
from repro.kernels import ops as kernel_ops

__all__ = ["build_local_z", "build_local_z_oracle", "resolve_kernel",
           "kernel_forced_by_env", "resolve_precision",
           "resolve_fused_zbuild", "PRECISIONS"]

PRECISIONS = envknobs.PRECISIONS  # historical re-export


def kernel_forced_by_env() -> bool:
    """True when ``REPRO_FORCE_KERNEL=1``: auto-resolution engages the
    (interpret-mode, off-TPU) kernel wherever the VMEM gate admits it.
    Parsing lives in ``repro.envknobs`` (malformed values raise)."""
    return envknobs.force_kernel()


def resolve_precision(precision: str | None) -> str:
    """Static Z-build precision for a mode step: ``"f32"`` or ``"bf16"``.

    ``None``/``"auto"`` honor ``REPRO_PRECISION`` (CI's bf16 leg; parsed and
    validated by ``repro.envknobs``); ``"auto"`` additionally consults the
    fitted ``CostModel`` — when calibration measured a materially faster
    bf16 TTM rate, auto picks bf16. The resolved value is static (baked
    into traces and compiled-step cache keys).
    """
    if precision in PRECISIONS:
        return precision
    if precision not in (None, "auto"):
        raise ValueError(f"unknown precision {precision!r} "
                         f"(expected one of {PRECISIONS + ('auto', None)})")
    env = envknobs.precision()
    if env is not None:
        return env
    if precision == "auto":
        from repro.core.calibrate import current_cost_model

        model = current_cost_model()
        bf16 = getattr(model, "ttm_flop_rate_bf16", None)
        f32 = model.ttm_flop_rate or model.flop_rate
        if bf16 and bf16 > 1.05 * f32:
            return "bf16"
    return "f32"


def resolve_fused_zbuild(fused_zbuild: bool | None) -> bool:
    """Static fused Z-build→first-oracle pipeline decision.

    ``None`` honors ``REPRO_FUSED_ZBUILD=1`` (CI leg; parsed by
    ``repro.envknobs``), else off. Like the kernel flag, the resolved value
    must be part of compiled-step keys.
    """
    if fused_zbuild is None:
        return envknobs.fused_zbuild()
    return bool(fused_zbuild)


def resolve_kernel(num_rows: int, core_dims: Sequence[int], mode: int,
                   use_kernel: bool | None, oracle_s: int = 0) -> bool:
    """Static kernel/reference decision for one mode step's Z build.

    ``True`` forces the kernel wherever the VMEM gate admits the shape
    (differential tests); ``False`` pins the jnp ``segment_sum`` reference;
    ``None`` is the auto policy described in the module docstring.
    ``oracle_s`` is the fused first-product panel width (0 = plain build),
    which the fused kernel's footprint includes. The resolved choice must
    be part of any compiled-step cache key.
    """
    if use_kernel is False:
        return False
    Ka, Kb = kernel_ops.split_kron_dims(core_dims, mode)
    fits = kernel_ops.kernel_fits_vmem(num_rows, Ka, Kb, oracle_s=oracle_s)
    if use_kernel is None:
        return fits and (jax.default_backend() == "tpu"
                         or kernel_forced_by_env())
    return fits


def build_local_z(
    coords: jnp.ndarray,
    values: jnp.ndarray,
    local_rows: jnp.ndarray,
    factors: Sequence[jnp.ndarray],
    mode: int,
    num_rows: int,
    *,
    use_kernel: bool = False,
    sorted_rows: bool = True,
    precision: str = "f32",
) -> jnp.ndarray:
    """The (local) penultimate matrix Z — (num_rows, K_hat).

    ``use_kernel`` routes through the Pallas ``kron_segsum`` kernel.
    ``sorted_rows=True`` asserts the partition.py contract (per-rank
    elements pre-sorted by dense local row id), skipping the runtime
    argsort; the single-process path passes ``sorted_rows=False`` since raw
    COO order is arbitrary. ``precision="bf16"`` rounds kron contributions
    to bf16 with f32 accumulation (kernel and reference implement the same
    contract). All flags are static (baked into the trace).
    """
    if use_kernel:
        fn = (kernel_ops.penultimate_sorted if sorted_rows
              else kernel_ops.penultimate_local)
        return fn(coords, values, local_rows, factors, mode, num_rows,
                  use_kernel=True, precision=precision)

    def chunk_z(acc, coords, values, rows):
        contribs = kron_contributions(coords, values, factors, mode)
        if precision == "bf16":
            contribs = contribs.astype(jnp.bfloat16).astype(jnp.float32)
        return acc + jax.ops.segment_sum(contribs, rows,
                                         num_segments=num_rows)

    khat = 1
    for j, f in enumerate(factors):
        if j != mode:
            khat *= int(f.shape[1])
    acc = jnp.zeros((num_rows, khat), jnp.result_type(values, *factors))
    return fold_elements(chunk_z, acc, coords, values, local_rows)


def build_local_z_oracle(
    coords: jnp.ndarray,
    values: jnp.ndarray,
    local_rows: jnp.ndarray,
    factors: Sequence[jnp.ndarray],
    mode: int,
    num_rows: int,
    X: jnp.ndarray,  # (K_hat, s) first oracle panel
    *,
    use_kernel: bool = False,
    sorted_rows: bool = True,
    precision: str = "f32",
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused pipeline stage: ``(Z, Z @ X)`` in one pass over the elements.

    On the kernel path the first oracle product is contracted against the
    VMEM-resident Z tile inside the same ``pallas_call`` (one HBM round-trip
    of Z saved per sweep·mode); the reference fallback computes the same
    product explicitly, keeping numerics identical across the gate.
    """
    if use_kernel and sorted_rows:
        return kernel_ops.penultimate_sorted_oracle(
            coords, values, local_rows, factors, mode, num_rows, X,
            use_kernel=True, precision=precision)
    Z = build_local_z(coords, values, local_rows, factors, mode, num_rows,
                      use_kernel=use_kernel, sorted_rows=sorted_rows,
                      precision=precision)
    return Z, Z @ X
