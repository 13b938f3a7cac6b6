"""HOOI (Higher-Order Orthogonal Iteration) — single-process entry point.

Implements the procedure of paper Fig 2 exactly:

    for each mode n:
        Z_(n)  <- TTM-chain skipping n, unfolded       (engine Z-build stage)
        F~_n   <- leading K_n left singular vectors    (engine oracle stage)
    core   <- T x_1 F~_1^T ... x_N F~_N^T              (once, at the end)

Since the engine refactor this module owns no sweep loop of its own:
``hooi`` is the **local-backend instantiation** of ``repro.engine`` — the
identity partition, no collectives — driving the same
``engine.sweep.run_hooi_sweeps`` loop and the same Z-build/oracle stages as
the distributed executor. The distributed runs differ only in placement and
comm backend, so this module remains the *oracle* the kernels and the
distributed paths are tested against by construction.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .coo import SparseTensor
from .ttm import core_from_factors

__all__ = ["Decomposition", "random_factors", "hosvd_init", "hooi_invocation",
           "hooi", "fit_score"]


@dataclasses.dataclass
class Decomposition:
    core: jnp.ndarray | None  # (K_1..K_N); None until finalized
    factors: list[jnp.ndarray]  # F_n: (L_n, K_n), orthonormal columns

    @property
    def core_dims(self) -> tuple[int, ...]:
        return tuple(int(f.shape[1]) for f in self.factors)


def random_factors(
    shape: Sequence[int], core_dims: Sequence[int], key: jax.Array
) -> list[jnp.ndarray]:
    """Random orthonormal factor matrices (paper: valid HOOI bootstrap)."""
    factors = []
    for n, (L, K) in enumerate(zip(shape, core_dims)):
        sub = jax.random.fold_in(key, n)
        g = jax.random.normal(sub, (L, K), jnp.float32)
        q, _ = jnp.linalg.qr(g)
        factors.append(q)
    return factors


def hosvd_init(t: SparseTensor, core_dims: Sequence[int]) -> list[jnp.ndarray]:
    """HOSVD bootstrap via dense unfoldings — small tensors / tests only."""
    dense = jnp.asarray(t.todense(), jnp.float32)
    factors = []
    for n, K in enumerate(core_dims):
        M = jnp.moveaxis(dense, n, 0).reshape(t.shape[n], -1)
        u, _, _ = jnp.linalg.svd(M, full_matrices=False)
        factors.append(u[:, :K])
    return factors


def hooi_invocation(
    t: SparseTensor,
    factors: list[jnp.ndarray],
    key: jax.Array,
    lanczos_iters: int | None = None,
    use_kernels: bool = False,
    use_fused_oracle: bool | None = None,
    precision: str | None = None,
    lanczos_block: int | None = None,
    fused_zbuild: bool | None = None,
    warm_start: str | None = None,
    objective=None,
) -> list[jnp.ndarray]:
    """One HOOI invocation: refine all factor matrices (no core update).

    Thin wrapper over the engine's local mode step (kept for direct
    callers; per-mode keys are derived as
    ``fold_in(key, n)``, the historical convention for this entry point).
    ``objective`` is an already-resolved ``engine.objective.Objective`` (or
    None for the standard Tucker behavior); this entry point does not apply
    ``prepare_tensor`` — callers own the view.
    """
    from repro.core.lanczos import effective_block_size
    from repro.core.sketch import sketch_block_size
    from repro.engine.steps import local_mode_step
    from repro.engine.oracle import (choose_warm_start, resolve_block_size,
                                     resolve_warm_start)
    from repro.engine.zbuild import resolve_fused_zbuild, resolve_precision

    coords = jnp.asarray(t.coords, jnp.int32)
    values = jnp.asarray(t.values, jnp.float32)
    prec = resolve_precision(precision)
    blk = resolve_block_size(lanczos_block)
    fz = resolve_fused_zbuild(fused_zbuild)
    warm = resolve_warm_start(warm_start)
    new_factors = list(factors)
    for n in range(t.ndim):
        k_n = int(new_factors[n].shape[1])
        khat = 1
        for j, f in enumerate(new_factors):
            if j != n:
                khat *= int(f.shape[1])
        s_eff = effective_block_size(k_n, t.shape[n], khat, blk)
        ws_n = choose_warm_start(warm, k_n, t.shape[n], khat, s_eff, fz)
        fz_n = fz and ws_n != "sketch"
        if ws_n == "sketch":
            s_eff = sketch_block_size(k_n, t.shape[n], khat, blk)
        niter = lanczos_iters
        if niter is not None and (fz_n or s_eff > 1 or ws_n == "sketch"):
            niter = -(-int(niter) // s_eff)  # vector budget -> block count
        new_factors[n] = local_mode_step(
            coords, values, new_factors, n, t.shape[n],
            jax.random.fold_in(key, n),
            niter=niter, use_kernel=use_kernels,
            use_fused_oracle=bool(use_fused_oracle), precision=prec,
            block_size=s_eff, fused_zbuild=fz_n, warm_start=ws_n,
            objective=objective,
        )
    return new_factors


def fit_score(t: SparseTensor, dec: Decomposition) -> float:
    """Fit = 1 - ||T - Z||_F / ||T||_F.

    With orthonormal factors and core = T x_n F_n^T (true after finalize),
    ||T - Z||^2 = ||T||^2 - ||G||^2 (classic identity), so no reconstruction
    is materialized.

    ``sum(values**2)`` equals ||T||^2 only for duplicate-free COO; tensors
    carrying duplicate coordinates (streaming value updates — see
    ``repro.streaming``) provide the true norm as ``_true_norm2`` and it
    takes precedence, keeping the identity exact.
    """
    true_norm2 = getattr(t, "_true_norm2", None)
    t_norm2 = float(true_norm2) if true_norm2 is not None \
        else float(np.sum(t.values**2))
    g_norm2 = float(jnp.sum(dec.core**2))
    err2 = max(t_norm2 - g_norm2, 0.0)
    return 1.0 - float(np.sqrt(err2) / (np.sqrt(t_norm2) + 1e-30))


def hooi(
    t: SparseTensor,
    core_dims: Sequence[int],
    n_invocations: int = 5,
    init: str = "random",
    seed: int = 0,
    lanczos_iters: int | None = None,
    use_kernels: bool = False,
    verbose: bool = False,
    use_fused_oracle: bool | None = None,
    precision: str | None = None,
    lanczos_block: int | None = None,
    fused_zbuild: bool | None = None,
    warm_start: str | None = None,
    objective=None,
    metrics_out: dict | None = None,
) -> tuple[Decomposition, list[float]]:
    """Full HOOI driver: bootstrap, invoke repeatedly, finalize core.

    The local-backend instantiation of the shared engine —
    ``dist_hooi(t, core_dims, 1, ...)`` runs the same loop, steps, and key
    schedule through the executor and produces the same fit trajectory.
    ``use_fused_oracle`` (None/False = off) routes the Lanczos oracle
    products through the Pallas ``oracle_pair`` kernel.

    Roofline knobs (each resolved through the same engine resolvers the
    distributed executor uses, so P=1 parity holds on every variant):
    ``precision`` — ``"f32"``/``"bf16"``/``"auto"``/None (None honors
    ``REPRO_PRECISION``); ``lanczos_block`` — s-step Lanczos panel width
    request (None honors ``REPRO_LANCZOS_BLOCK``); ``fused_zbuild`` — fuse
    the Z build with the first oracle panel product (None honors
    ``REPRO_FUSED_ZBUILD``); ``warm_start`` — ``"none"``/``"sketch"``/
    ``"auto"`` oracle warm start (None honors ``REPRO_WARM_START``;
    ``"sketch"`` seeds the block driver with the factor-sketched
    range-finder panel and halves the refinement budget, ``"none"``
    reproduces the historical trajectories bitwise).

    ``objective`` selects what the sweeps optimize (None honors
    ``REPRO_OBJECTIVE``, default standard Tucker; a name or an
    ``engine.objective.Objective`` instance otherwise). The objective's
    ``prepare_tensor`` view is applied here — completion drops its held-out
    entries before any device array is built. ``metrics_out`` (a dict)
    collects the objective's extra per-sweep stats (held-out RMSE).
    """
    from repro.core.lanczos import effective_block_size
    from repro.core.sketch import sketch_block_size
    from repro.engine.objective import resolve_objective
    from repro.engine.oracle import (choose_warm_start, resolve_block_size,
                                     resolve_warm_start)
    from repro.engine.steps import local_core_from_z, local_mode_step
    from repro.engine.sweep import run_hooi_sweeps
    from repro.engine.zbuild import resolve_fused_zbuild, resolve_precision

    obj = resolve_objective(objective)
    t = obj.prepare_tensor(t)

    key = jax.random.PRNGKey(seed)
    if init == "random":
        factors = random_factors(t.shape, core_dims, key)
    elif init == "hosvd":
        factors = hosvd_init(t, core_dims)
    else:
        raise ValueError(f"unknown init {init!r}")

    coords = jnp.asarray(t.coords, jnp.int32)
    values = jnp.asarray(t.values, jnp.float32)
    fused = bool(use_fused_oracle)
    prec = resolve_precision(precision)
    blk = resolve_block_size(lanczos_block)
    fz = resolve_fused_zbuild(fused_zbuild)
    warm = resolve_warm_start(warm_start)
    # the last mode's Z, kept for the sweep's core where it is built in f32
    # (the executor's rule: distributed.executor._keeps_z)
    keep_z = prec == "f32"
    kept: dict = {}

    def mode_step(n, facs, kk):
        k_n = int(facs[n].shape[1])
        khat = 1
        for j, f in enumerate(facs):
            if j != n:
                khat *= int(f.shape[1])
        s_eff = effective_block_size(k_n, t.shape[n], khat, blk)
        ws_n = choose_warm_start(warm, k_n, t.shape[n], khat, s_eff, fz)
        fz_n = fz and ws_n != "sketch"
        if ws_n == "sketch":
            s_eff = sketch_block_size(k_n, t.shape[n], khat, blk)
        niter = lanczos_iters
        if niter is not None and (fz_n or s_eff > 1 or ws_n == "sketch"):
            niter = -(-int(niter) // s_eff)
        out = local_mode_step(coords, values, facs, n, t.shape[n], kk,
                              niter=niter, use_kernel=use_kernels,
                              use_fused_oracle=fused, precision=prec,
                              block_size=s_eff, fused_zbuild=fz_n,
                              warm_start=ws_n, objective=obj,
                              return_z=keep_z and n == t.ndim - 1)
        if isinstance(out, tuple):
            out, kept["z"] = out
        return out

    # the rows the last mode's elements touch: the rows of the executor's
    # local Z at P = 1
    touched = np.unique(np.asarray(t.coords)[:, -1]) if keep_z else None

    def core_from_z(factor):
        return local_core_from_z(kept.pop("z"), touched, factor, t.ndim - 1,
                                 [int(f.shape[1]) for f in factors])

    def on_sweep(it, _seconds, fit):  # pragma: no cover
        if verbose:
            print(f"  HOOI invocation {it}: fit={fit:.4f}")

    return run_hooi_sweeps(coords, values, t, factors, key, n_invocations,
                           mode_step, on_sweep=on_sweep, objective=obj,
                           metrics_out=metrics_out,
                           core_from_z=core_from_z if keep_z else None)
