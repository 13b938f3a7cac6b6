"""Distributed HOOI — thin compatibility wrapper over ``HooiExecutor``.

The engine (mesh ownership, compiled-step cache, device-upload cache,
calibration sampling, and both collective paths) lives in
``repro.distributed.executor``; this module keeps the historical
``dist_hooi(...)`` entry point and re-exports so existing call sites work
unchanged. Repeated calls share a process-wide executor per (P, mesh), so
the second decomposition on a cached plan performs no new jit compilations
and no new host->device uploads — the device-side analogue of the plan
cache's host-side amortization.
"""

from __future__ import annotations

from typing import Sequence

from repro.core.coo import SparseTensor
from repro.core.distribution import Scheme
from repro.core.hooi import Decomposition
from repro.core.plan import PartitionPlan
from .executor import (  # noqa: F401 — historical re-exports
    DistHooiStats,
    HooiExecutor,
    comm_model,
    make_ranks_mesh,
    shared_executor,
)
from .partition import ModePartition, make_mode_partition  # noqa: F401

__all__ = ["dist_hooi", "make_ranks_mesh", "comm_model", "DistHooiStats",
           "HooiExecutor", "shared_executor"]


def dist_hooi(
    t: SparseTensor,
    core_dims: Sequence[int],
    P_ranks: int,
    scheme: str | Scheme | PartitionPlan = "lite",
    n_invocations: int = 3,
    path: str = "liteopt",
    seed: int = 0,
    mesh=None,
    plan_seed: int = 0,
    executor: HooiExecutor | None = None,
    use_kernel: bool | None = None,
    use_fused_oracle: bool | None = None,
    precision: str | None = None,
    lanczos_block: int | None = None,
    fused_zbuild: bool | None = None,
    warm_start: str | None = None,
    pad_geometric: bool = False,
    objective=None,
) -> tuple[Decomposition, DistHooiStats]:
    """Distributed HOOI: partition with ``scheme``, run on a 'ranks' mesh.

    ``scheme`` is the string sugar (any name ``repro.core.plan.plan``
    accepts, including ``"auto"``), a prebuilt ``Scheme``, or a full
    ``PartitionPlan``. String/Scheme forms go through the content-keyed plan
    cache, so repeated calls on the same tensor skip all host-side
    partitioning work. ``seed`` drives the factor initialization;
    ``plan_seed`` is threaded to randomized distribution schemes (medium's
    index permutations, coarse's block strategy) and participates in the
    plan cache key. ``executor`` overrides the shared per-(P, mesh) engine.

    ``path`` selects the comm-backend family (``"baseline"`` -> psum,
    ``"liteopt"`` -> boundary, ``"auto"`` -> per mode from the plan's
    analytic comm model; P=1 always runs the collective-free ``local``
    backend — the same engine instantiation as single-process ``hooi``).
    ``use_kernel`` picks the Z-build variant (None = a Pallas kernel on
    TPU when it fits VMEM, True = force kernel, False = jnp reference; see
    ``repro.engine.zbuild.resolve_zbuild``) and ``use_fused_oracle``
    (None/False = off) routes the Lanczos oracle products through the fused
    Pallas kernel. ``precision``/``lanczos_block``/``fused_zbuild`` are the
    roofline knobs (bf16 Z-build contributions, s-step Lanczos panels, the
    fused Z-build→first-oracle stage) and ``warm_start`` the sketched
    oracle warm start (``"none"``/``"sketch"``/``"auto"``; None honors
    ``REPRO_WARM_START`` — see ``docs/sketch.md``) — see
    ``HooiExecutor.run``; each ``None`` honors its ``REPRO_*`` environment
    override. ``pad_geometric``
    quantizes partition pads to powers of two (streaming shape stability;
    part of the plan-cache key — see ``repro.core.plan.plan``).
    ``objective`` selects what the sweeps optimize (None honors
    ``REPRO_OBJECTIVE``; a name or an ``engine.objective.Objective``) — see
    ``docs/objectives.md``.
    """
    ex = executor if executor is not None else shared_executor(P_ranks, mesh)
    if ex.P != P_ranks:
        raise ValueError(f"executor has P={ex.P}, asked for {P_ranks}")
    return ex.run(t, core_dims, scheme, n_invocations=n_invocations,
                  path=path, seed=seed, plan_seed=plan_seed,
                  use_kernel=use_kernel, use_fused_oracle=use_fused_oracle,
                  precision=precision, lanczos_block=lanczos_block,
                  fused_zbuild=fused_zbuild, warm_start=warm_start,
                  pad_geometric=pad_geometric, objective=objective)
