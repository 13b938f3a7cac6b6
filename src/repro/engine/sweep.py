"""The one HOOI sweep loop.

Both entry points — single-process ``repro.core.hooi.hooi`` and the
distributed ``HooiExecutor.run`` — drive this loop; they differ only in the
``mode_step`` callable they plug in (a local engine step vs. a cached
compiled ``shard_map`` step). That is the whole point of the engine: the
iteration structure, key derivation, fit accounting, and finalization exist
once, so single-process vs. distributed parity is structural.

Key derivation is the shared contract: the step for invocation ``it`` and
mode ``n`` receives ``sweep_key(key, it, N, n)``. Every backend therefore
draws identical Lanczos start/restart vectors for the same (seed, it, n),
which is what makes ``hooi(t, ...)`` and ``dist_hooi(t, ..., P=1)`` produce
the same fit trajectory.

Each sweep runs under the span ``hooi.sweep`` (``repro.tracing``), with the
children ``hooi.step`` (one per mode: the step's dispatch and whatever the
``mode_step`` callable does on the host), ``hooi.wait`` (blocking until
the factors are ready), ``hooi.core`` (the core and its finalization) and
``hooi.fit``.
"""

from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from repro.tracing import span

__all__ = ["sweep_key", "run_hooi_sweeps"]


def sweep_key(key: jax.Array, it: int, nmodes: int, mode: int) -> jax.Array:
    """Per-(invocation, mode) PRNG key — one convention for every backend."""
    return jax.random.fold_in(key, 1000 + it * nmodes + mode)


def run_hooi_sweeps(
    coords: jnp.ndarray,
    values: jnp.ndarray,
    t,
    factors: list,
    key: jax.Array,
    n_invocations: int,
    mode_step: Callable[[int, Sequence[jnp.ndarray], jax.Array], jnp.ndarray],
    on_sweep: Callable[[int, float, float], None] | None = None,
    objective=None,
    metrics_out: dict | None = None,
    core_from_z: Callable[[jnp.ndarray], jnp.ndarray] | None = None,
):
    """Run ``n_invocations`` HOOI sweeps, returning (Decomposition, fits).

    ``mode_step(n, factors, key) -> new factor`` must return the refined
    mode-n factor in *original* row order (distributed steps undo their row
    relabeling before returning). ``on_sweep(it, seconds, fit)`` observes
    each sweep's blocking wall time (its ``hooi.step`` and ``hooi.wait``
    spans) — the executor's calibration hook. The
    core is (re)finalized from the final factors, so ``n_invocations=0``
    still yields a valid decomposition of the bootstrap factors.

    ``objective`` (an ``engine.objective.Objective``) owns the per-sweep
    fit accounting; ``None`` runs the historical inline fit_score —
    ``TuckerObjective`` reproduces it bitwise, so both arms are the same
    trajectory. ``metrics_out`` collects the objective's extra per-sweep
    stats (e.g. completion's held-out RMSE).

    ``core_from_z(F_N) -> core``, where the last mode step keeps its Z (the
    executor's steps), gives each sweep's core from that Z and the factor
    the step returned: ``core_from_factors(..., from_z=core_from_z)``, the
    paper's ``G_(N) = U_Nᵀ Z_N``. Without it, and for ``n_invocations=0``,
    the core is folded from the elements.
    """
    from repro.core.hooi import Decomposition, fit_score
    from repro.core.ttm import core_from_factors

    N = t.ndim
    fits: list[float] = []
    core = None
    for it in range(n_invocations):
        with span("hooi.sweep", it=it):
            sweep_s = 0.0
            for n in range(N):
                with span("hooi.step", it=it, mode=n) as sp:
                    factors[n] = mode_step(n, factors,
                                           sweep_key(key, it, N, n))
                sweep_s += sp.seconds
            with span("hooi.wait", it=it) as sp:
                jax.block_until_ready(factors)
            sweep_s += sp.seconds
            with span("hooi.core", it=it):
                core = core_from_factors(coords, values, factors,
                                         from_z=core_from_z)
                if objective is not None:
                    core = objective.finalize_core(core, factors)
            with span("hooi.fit", it=it):
                if objective is None:
                    fit = fit_score(t, Decomposition(core=core,
                                                     factors=factors))
                else:
                    fit = objective.fit(t, core, factors)
                    if metrics_out is not None:
                        objective.sweep_metrics(metrics_out, t, core,
                                                factors)
            fits.append(fit)
            if on_sweep is not None:
                on_sweep(it, sweep_s, fit)
    if core is None:  # n_invocations == 0: finalize the initial factors
        with span("hooi.core"):
            core = core_from_factors(coords, values, factors)
            if objective is not None:
                core = objective.finalize_core(core, factors)
    return Decomposition(core=core, factors=factors), fits
