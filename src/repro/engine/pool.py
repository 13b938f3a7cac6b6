"""ExecutorPool: a serving tier of executors over disjoint device slices.

The paper's "MPI ranks" abstraction has so far only ever met one device
slice: ``StreamScheduler`` pipelines many tensors, but every sweep still
runs on the single attached ``HooiExecutor``. The serving regime the
ROADMAP targets (SGD_Tucker's many-concurrent-clients shape: lots of small
independent decomposition streams) needs the opposite — several executors
running *simultaneously*, each pinned to its own slice of the host's
devices, with streams routed across them.

This module is the resource layer of that tier:

* ``device_slices(n, P)`` cuts ``jax.devices()`` into ``n`` disjoint
  ``P``-device slices — executors never share a device, so their sweeps
  genuinely overlap instead of time-slicing one mesh.

* ``ExecutorPool`` owns ``n`` **lanes**. A lane is one ``HooiExecutor``
  (mesh pinned to its slice, its own compiled-step and upload caches) plus
  one ``StreamScheduler`` (its own producer pool and consumer thread) —
  the per-lane pipeline is exactly the single-executor pipeline, so every
  scheduler contract (submission order, refresh ladder, rerun = 0 new jit
  / 0 new uploads) holds per lane unchanged.

* ``PoolStats`` aggregates the per-stream accounting every run already
  lands in ``DistHooiStats`` (queue wait, prepare/sweep seconds, SLO
  hit/miss) across lanes, and carries the router-level admission counters
  when read through ``repro.engine.router.StreamRouter.stats()``.

Routing policy (priority classes, modeled cost, admission control,
backpressure, warm-start reroutes) lives above this layer in
``repro.engine.router`` — the pool itself is deliberately policy-free.
See docs/scheduler.md ("Pool & routing").
"""

from __future__ import annotations

import collections
import dataclasses
from typing import TYPE_CHECKING, Sequence

from repro.engine.scheduler import StreamScheduler

if TYPE_CHECKING:  # runtime import is deferred: executor imports repro.engine
    from repro.distributed.executor import HooiExecutor

__all__ = ["ExecutorPool", "PoolLane", "PoolStats", "device_slices"]


def device_slices(n_executors: int, P_ranks: int, devices=None) -> list:
    """Cut the device list into ``n_executors`` disjoint ``P_ranks``-slices.

    Raises when the host cannot supply ``n_executors * P_ranks`` devices —
    a pool whose executors silently shared devices would report overlap
    that the hardware never delivers.
    """
    import jax

    n, P = int(n_executors), int(P_ranks)
    if n < 1 or P < 1:
        raise ValueError(f"need n_executors >= 1 and P_ranks >= 1, "
                         f"got {n_executors} x {P_ranks}")
    devs = list(jax.devices() if devices is None else devices)
    need = n * P
    if len(devs) < need:
        raise ValueError(
            f"pool of {n} executors x P={P} needs {need} devices, have "
            f"{len(devs)} — set XLA_FLAGS=--xla_force_host_platform_"
            "device_count or shrink the pool")
    return [devs[i * P:(i + 1) * P] for i in range(n)]


@dataclasses.dataclass
class PoolLane:
    """One executor + its scheduler pipeline, pinned to a device slice."""

    index: int
    executor: HooiExecutor
    scheduler: StreamScheduler
    devices: tuple


@dataclasses.dataclass
class PoolStats:
    """Aggregate serving-tier accounting (lanes + router admission).

    Read via ``ExecutorPool.stats()`` (router fields zero) or
    ``StreamRouter.stats()`` (router fields filled in). Per-lane raw dicts
    are kept so dashboards can drill down without re-walking the pool.
    """

    n_lanes: int
    # ---- lane aggregates (summed StreamScheduler totals) ----
    submitted: int = 0
    completed: int = 0
    failed: int = 0
    host_s: float = 0.0
    run_s: float = 0.0
    queue_wait_s: float = 0.0
    slo_hit: int = 0
    slo_miss: int = 0
    decisions: dict = dataclasses.field(default_factory=dict)
    lane_stats: tuple = ()  # per-lane StreamScheduler.stats() dicts
    lane_executors: tuple = ()  # per-lane HooiExecutor.stats() snapshots
    # ---- router-level counters (admission/backpressure/affinity) ----
    rejected: int = 0  # submissions refused admission (PoolSaturated)
    rejected_by_priority: dict = dataclasses.field(default_factory=dict)
    rerouted: int = 0  # warm-start stream transfers between lanes
    backlog_s: tuple = ()  # modeled pending seconds per lane at read time

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class ExecutorPool:
    """``n_executors`` scheduler-fronted executors on disjoint device slices.

    Construction kwargs after ``core_dims`` are forwarded to every lane's
    ``StreamScheduler`` (scheme, path, n_invocations, drift_tol,
    pad_geometric, ...), so a pool is configured exactly like a single
    scheduler. Use as a context manager (or call ``close``) to stop every
    lane's worker threads.

    The pool is policy-free: ``lane(i).scheduler.submit`` is the raw
    per-lane entry point. Almost all callers want
    ``repro.engine.router.StreamRouter`` on top — it owns lane choice,
    admission control and backpressure.
    """

    def __init__(
        self,
        n_executors: int,
        P_ranks: int,
        core_dims: Sequence[int],
        *,
        devices=None,
        workers: int = 2,
        **scheduler_kw,
    ):
        from repro.distributed.executor import HooiExecutor, make_ranks_mesh

        self.P = int(P_ranks)
        self.core_dims = tuple(int(k) for k in core_dims)
        slices = device_slices(n_executors, P_ranks, devices)
        self.lanes: list[PoolLane] = []
        for i, sl in enumerate(slices):
            mesh = make_ranks_mesh(self.P, devices=sl)
            ex = HooiExecutor(self.P, mesh=mesh)
            sched = StreamScheduler(ex, self.core_dims, lane=i,
                                    workers=workers, **scheduler_kw)
            self.lanes.append(PoolLane(index=i, executor=ex,
                                       scheduler=sched, devices=tuple(sl)))

    # ------------------------------------------------------------ lifecycle
    def __enter__(self) -> "ExecutorPool":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def close(self) -> None:
        """Drain and stop every lane's worker threads (idempotent)."""
        for lane in self.lanes:
            lane.scheduler.close()

    # -------------------------------------------------------------- access
    @property
    def n_lanes(self) -> int:
        return len(self.lanes)

    def lane(self, i: int) -> PoolLane:
        return self.lanes[i]

    # ---------------------------------------------------------------- stats
    def stats(self) -> PoolStats:
        """Aggregated lane accounting (router counters zero at this layer)."""
        lane_stats = tuple(l.scheduler.stats() for l in self.lanes)
        lane_execs = tuple(l.executor.stats() for l in self.lanes)
        decisions: collections.Counter = collections.Counter()
        agg = {"submitted": 0, "completed": 0, "failed": 0,
               "host_s": 0.0, "run_s": 0.0, "queue_wait_s": 0.0,
               "slo_hit": 0, "slo_miss": 0}
        for ls in lane_stats:
            for k in agg:
                agg[k] += ls[k]
            decisions.update(ls["decisions"])
        return PoolStats(
            n_lanes=self.n_lanes,
            decisions=dict(decisions),
            lane_stats=lane_stats,
            lane_executors=lane_execs,
            **agg,
        )
