"""The layered HOOI engine: Z-build -> oracle -> comm backend.

One mode step = three explicit stages (paper §3's components made
structural, after the dense companion paper's one-schedule/many-
distributions framing):

* **Z-build** (``engine.zbuild``) — the penultimate matrix, Pallas
  ``kron_segsum`` kernel or jnp reference.
* **oracle** (``engine.oracle``) — the per-device Z products (plain or the
  fused Pallas ``oracle_pair`` kernel) feeding the repo's ONE Lanczos body
  (``repro.core.lanczos``).
* **comm backend** (``engine.comm``) — ``local`` (P=1, no collectives),
  ``psum`` (replicated row space, the paper baseline) or ``boundary``
  (sharded rows + O(P) boundary exchange), selected per mode from the
  plan's partition metrics.

``engine.steps`` composes the stages into mode steps; ``engine.sweep`` is
the single HOOI sweep loop both ``repro.core.hooi.hooi`` and
``repro.distributed.executor.HooiExecutor`` drive; ``engine.objective``
parameterizes *what* that loop optimizes (standard Tucker, masked
completion, nonnegative ADMM Tucker — see docs/objectives.md); ``engine.scheduler``
pipelines many tensors (or stream versions) through one executor,
overlapping host-side partitioning with device sweeps; ``engine.pool`` +
``engine.router`` serve many concurrent streams over several executors on
disjoint device slices, with priority admission and warm-start reroutes.
See docs/architecture.md and docs/scheduler.md.
"""

from .comm import (
    AXIS,
    COMM_BACKENDS,
    OracleSpace,
    make_comm_space,
    resolve_backend,
)
from .objective import (
    CompletionObjective,
    NNTuckerObjective,
    Objective,
    TuckerObjective,
    resolve_objective,
)
from .oracle import (
    choose_warm_start,
    count_z_passes,
    resolve_block_size,
    resolve_warm_start,
    solve_oracle,
    solve_oracle_block,
    z_products,
)
from .pool import ExecutorPool, PoolLane, PoolStats, device_slices
from .router import PoolSaturated, StreamRouter
from .scheduler import ScheduledResult, StreamScheduler
from .steps import (
    ARRAY_FIELDS,
    local_mode_step,
    make_core_step_fn,
    make_mode_step_fn,
    make_stochastic_step_fn,
    make_zbuild_step_fn,
)
from .sweep import run_hooi_sweeps, sweep_key
from .zbuild import (
    build_local_z,
    build_local_z_oracle,
    kernel_forced_by_env,
    resolve_fused_zbuild,
    resolve_kernel,
    resolve_zbuild,
    resolve_precision,
)

__all__ = [
    "AXIS",
    "COMM_BACKENDS",
    "OracleSpace",
    "make_comm_space",
    "resolve_backend",
    "Objective",
    "TuckerObjective",
    "CompletionObjective",
    "NNTuckerObjective",
    "resolve_objective",
    "solve_oracle",
    "solve_oracle_block",
    "count_z_passes",
    "resolve_block_size",
    "resolve_warm_start",
    "choose_warm_start",
    "z_products",
    "ExecutorPool",
    "PoolLane",
    "PoolStats",
    "device_slices",
    "PoolSaturated",
    "StreamRouter",
    "ScheduledResult",
    "StreamScheduler",
    "ARRAY_FIELDS",
    "local_mode_step",
    "make_core_step_fn",
    "make_mode_step_fn",
    "make_stochastic_step_fn",
    "make_zbuild_step_fn",
    "run_hooi_sweeps",
    "sweep_key",
    "build_local_z",
    "build_local_z_oracle",
    "kernel_forced_by_env",
    "resolve_kernel",
    "resolve_zbuild",
    "resolve_precision",
    "resolve_fused_zbuild",
]
