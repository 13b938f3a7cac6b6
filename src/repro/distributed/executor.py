"""HooiExecutor: mesh, caching and calibration over engine-built steps.

``dist_hooi`` used to be a monolith: every call re-jitted N shard_map mode
steps and re-uploaded every padded ``ModePartition`` array, so the
device-side distribution cost was paid on every run — the opposite of the
paper's amortization story. The executor makes reuse structural, and since
the engine refactor it owns *no math of its own*: every mode step is
composed by ``repro.engine`` (Z-build -> oracle -> comm backend; the same
stages single-process ``repro.core.hooi`` runs) and the sweep loop is the
shared ``engine.sweep.run_hooi_sweeps``. What the executor owns:

  * the ``ranks`` device mesh (built once per executor),

  * a **compiled-step cache**: jitted shard_map mode steps keyed on the
    static step signature ``(backend, zbuild-variant, oracle-variant, mode,
    R_pad, Lp, S_pad, P, K_n, niter)`` — two tensors whose partitions pad
    to the same shapes share one XLA compilation (jit re-specializes per
    concrete array shapes; the executor counts a compilation exactly when a
    (step, shapes) pair is first seen, which is jit's own cache-miss
    condition),

  * a **device-upload cache**: the per-mode device arrays for a plan, keyed
    weakly on ``PartitionPlan`` *identity* (the plan cache's same-object
    contract exists precisely so this works) — repeated runs, and
    interleaved runs on different cached tensors sharing one mesh
    (multi-tensor batching), skip all host->device transfer.

Every ``run`` also records measured per-sweep wall times next to the plan's
modeled flops/bytes; ``calibration_samples()`` feeds
``repro.core.calibrate.fit_cost_model`` so the analytic rates behind the
``auto`` selector can be fitted to the actual machine.

Comm backends (``repro.engine.comm``; unchanged math, selected per mode):
``local`` for P=1 (no collectives — structural parity with single-process
HOOI), ``psum`` for the paper-faithful ``baseline`` path, ``boundary`` for
the TPU-native ``liteopt`` path; ``path="auto"`` picks per mode from the
plan's analytic comm model.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import threading
import time
import weakref
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.coo import SparseTensor
from repro.core.distribution import Scheme
from repro.core.hooi import Decomposition, random_factors
from repro.core.lanczos import effective_block_size, lanczos_niter
from repro.core.plan import (
    PartitionPlan,
    last_plan_call_cache_hit,
    plan as build_plan,
    plan_cache_stats,
)
from repro.core.sketch import (DEFAULT_POWER_ITERS, sketch_block_size,
                               sketch_niter)
from repro.core.stochastic import (blend_factor, next_pow2, sample_batch,
                                   step_eta)
from repro.engine import (
    ARRAY_FIELDS,
    choose_warm_start,
    count_z_passes,
    make_mode_step_fn,
    make_stochastic_step_fn,
    make_zbuild_step_fn,
    resolve_backend,
    resolve_block_size,
    resolve_fused_zbuild,
    resolve_precision,
    resolve_warm_start,
    run_hooi_sweeps,
)
from repro.engine import zbuild as engine_zbuild
from repro.engine.objective import resolve_objective
from repro.kernels import ops as kernel_ops
from repro.kernels.window_segsum import pair_count, window_geometry
from repro.tracing import Tally, hlo_scopes, span
from .partition import comm_model, make_mode_partition  # noqa: F401 — re-export

__all__ = [
    "HooiExecutor",
    "shared_executor",
    "make_ranks_mesh",
    "DistHooiStats",
    "comm_model",
]

MAX_CALIBRATION_SAMPLES = 1024
MAX_COMPILED_STEPS = 256  # jitted shard_map executables held per executor
MAX_STOCH_UPLOADS = 32  # resident stochastic minibatches per executor

RUN_PATHS = ("baseline", "liteopt", "auto")


def make_ranks_mesh(P_ranks: int, devices=None):
    """1-D ``ranks`` mesh over ``devices`` (default: the first ``P_ranks``
    of ``jax.devices()``), one rank per device."""
    devs = jax.devices() if devices is None else list(devices)
    if len(devs) < P_ranks:
        raise ValueError(
            f"need {P_ranks} devices, have {len(devs)} — on the CPU set "
            "XLA_FLAGS=--xla_force_host_platform_device_count"
        )
    return jax.make_mesh((P_ranks,), ("ranks",),
                         axis_types=(jax.sharding.AxisType.Auto,),
                         devices=devs[:P_ranks])


# ------------------------------------------------------------------- stats
@dataclasses.dataclass
class DistHooiStats:
    fits: list
    comm: dict  # analytic per-mode comm model
    r_pad: dict
    e_pad: dict
    scheme: str = ""  # concrete scheme that ran (auto resolves to a candidate)
    selection: dict | None = None  # auto only: candidate -> modeled total_s
    partition_build_s: float = 0.0  # host-side plan construction this call
    plan_cache_hit: bool = False
    plan_cache: dict | None = None  # global plan-cache counters after this call
    # ---- executor counters, deltas for this call ----
    step_compilations: int = 0  # new XLA mode-step compilations this call
    step_cache_hits: int = 0  # mode-step invocations served from cache
    uploads: int = 0  # host->device arrays transferred this call
    upload_cache_hit: bool = False  # plan's device arrays were already resident
    executor: dict | None = None  # cumulative HooiExecutor.stats() snapshot
    # mode -> True if the Z build ran through a Pallas kernel
    z_kernel: dict | None = None
    # mode -> which Z build ran: "windowed" | "resident" | "reference"
    # (repro.engine.zbuild.ZBUILDS)
    z_build: dict | None = None
    # windowed modes -> (element block, row window) pairs the kernel worked
    # on per element block, over all ranks: 1.0 when no block crosses a
    # window boundary (kernels.window_segsum.pair_count)
    z_pairs_per_block: dict | None = None
    # mode -> comm backend the step ran ("local" | "psum" | "boundary")
    comm_backends: dict | None = None
    # True when the Lanczos oracle products ran the fused Pallas kernel
    fused_oracle: bool = False
    # ---- roofline knobs (resolved values that actually ran) ----
    precision: str = "f32"  # Z-build contribution precision ("f32" | "bf16")
    # mode -> effective Lanczos panel width (1 = the vector driver)
    lanczos_block: dict | None = None
    # True when the Z build and first oracle product ran as one fused stage
    fused_zbuild: bool = False
    # mode -> counted HBM passes over Z per sweep (engine.count_z_passes)
    z_passes: dict | None = None
    # ---- streaming scheduler annotations (repro.engine.scheduler) ----
    # how the scheduler refreshed the plan for this run:
    # "plan" (first sight) | "reuse" | "repartition" | "reselect"
    stream_decision: str | None = None
    # §4 imbalance drift that drove the decision (refresh_decision output)
    stream_drift: dict | None = None
    # host-side producer time (snapshot + decision + plan + upload staging)
    # that ran *off* the device hot path, overlapped with earlier sweeps
    prepare_s: float = 0.0
    # ---- serving-tier annotations (repro.engine.pool / .router) ----
    # submit -> sweep start, minus the prepare work (pure queueing delay)
    queue_wait_s: float = 0.0
    # consumer-stage sweep wall seconds for this run
    run_s: float = 0.0
    # caller's SLO budget on submit -> result latency, and whether the run
    # met it (None/None when no deadline was given)
    slo_deadline_s: float | None = None
    slo_met: bool | None = None
    # pool lane (executor index) that ran this decomposition
    lane: int | None = None
    # ---- objective annotations (repro.engine.objective) ----
    # which sweep objective ran ("tucker" | "completion" | "nn")
    objective: str = "tucker"
    # objective extra per-sweep stats, e.g. completion's held-out RMSE
    # trajectory under "holdout_rmse"; None when the objective emits none
    objective_metrics: dict | None = None
    # ---- sketch warm start / adaptive rank (repro.core.sketch) ----
    # mode -> resolved warm-start mode that ran ("none" | "sketch")
    warm_start: dict | None = None
    # mode -> last-sweep singular-value estimates (numpy); the tail drives
    # the streaming scheduler's adapt_rank policy
    mode_spectra: dict | None = None
    # scheduler-filled: [(stream_len, core_dims), ...] rank trajectory for
    # the stream this run belongs to (None outside adaptive-rank streams)
    rank_trajectory: list | None = None
    # ---- stochastic-refine rung (run_stochastic / core.stochastic) ----
    # sample fraction the minibatch drew at (None outside the rung)
    sample_fraction: float | None = None
    # sampled new-batch elements that entered the minibatch
    sample_nnz: int | None = None
    # replay-reservoir elements drawn from the refined prefix
    replay_nnz: int | None = None
    # effective blend step size eta this refine applied (post-decay)
    step_size: float | None = None
    # scheduler-filled: final fit minus the last *full* run's final fit —
    # the rung's observable fit error, bounded by the correction sweep
    fit_delta: float | None = None
    # ---- tracing (repro.tracing) ----
    # span name -> [count, host seconds] of every span this call closed
    # (hooi.run, hooi.plan, hooi.upload, hooi.sweep, hooi.step, ...)
    spans: dict | None = None
    # innermost open span -> XLA compilations this call (hooi.step: a new
    # step executable; hooi.core: the core recompiled)
    compiles: dict | None = None


@dataclasses.dataclass
class _PlanUpload:
    """Device-resident arrays for one plan (the upload cache's payload)."""

    dev_args: tuple  # per-mode 8-tuples of sharded jnp arrays
    row_perms: tuple  # per-mode (L,) jnp index arrays (relabel -> original)
    coords: jnp.ndarray  # full-tensor COO (core / fit evaluation)
    values: jnp.ndarray
    n_arrays: int
    # (mode, precision) -> windowed pairs per element block, counted once
    pairs_per_block: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass(frozen=True)
class _ModeSpec:
    """Static per-mode step parameters run() and profile_phases() share.

    Both must derive identical specs so a profiled step's shape signature
    counts as already-compiled for the subsequent run (and vice versa).
    """

    backend: str
    K_n: int
    niter: int  # block iterations when block_size > 1
    zbuild: str  # Z-build variant (repro.engine.zbuild.ZBUILDS)
    precision: str = "f32"
    block_size: int = 1  # effective (clamped) Lanczos panel width
    fused_zbuild: bool = False
    objective: str = "tucker"  # sweep objective the step runs under
    warm_start: str = "none"  # resolved oracle warm start ("none"|"sketch")

    @property
    def use_kernel(self) -> bool:
        return self.zbuild != "reference"


def _pairs_per_block(mp, core_dims: Sequence[int], precision: str) -> float:
    """Windowed-kernel pairs per element block over every rank's elements
    of one mode partition (host count from the sorted local rows)."""
    Ka, Kb = kernel_ops.split_kron_dims(core_dims, mp.mode)
    block_e = window_geometry(Ka, Kb, precision).block_e
    pairs = sum(pair_count(mp.local_rows[p], block_e) for p in range(mp.P))
    return pairs / (mp.P * -(-mp.E_pad // block_e))


def _traced_call(run):
    """Run an executor entry point as one call: under the span ``hooi.run``
    (``call`` = the executor's run count before it) with a ``Tally`` whose
    spans and compilations land on the returned ``DistHooiStats``."""

    @functools.wraps(run)
    def traced(self, *args, **kwargs):
        with Tally() as tally, span("hooi.run", call=self._stats["runs"]):
            dec, stats = run(self, *args, **kwargs)
        stats.spans, stats.compiles = tally.spans, tally.compiles
        return dec, stats

    return traced


# ---------------------------------------------------------------- executor
class HooiExecutor:
    """Runs distributed HOOI sweeps on one ``ranks`` mesh, caching both the
    compiled mode steps and the per-plan device uploads across runs.

    One executor per mesh; ``shared_executor(P)`` hands out a process-wide
    instance so independent ``dist_hooi`` calls amortize automatically.
    """

    def __init__(self, P_ranks: int, mesh=None):
        self.P = int(P_ranks)
        self.mesh = mesh if mesh is not None else make_ranks_mesh(self.P)
        self._lock = threading.RLock()
        self._steps: dict[tuple, object] = {}  # static sig -> jitted callable
        # (static sig, arg shapes) -> abstract arguments (op_scopes lowers
        # the step again from them)
        self._seen_shapes: dict[tuple, tuple] = {}
        self._uploads: "weakref.WeakKeyDictionary[PartitionPlan, _PlanUpload]" \
            = weakref.WeakKeyDictionary()
        # an auto plan is a dataclasses.replace copy of its winning
        # candidate, sharing the same parts tuple: dedupe uploads on the
        # parts' identity so the arrays go to device once. While an upload
        # is alive, some plan in _uploads holds its parts, so id() is stable.
        self._uploads_by_parts: "weakref.WeakValueDictionary[int, _PlanUpload]" \
            = weakref.WeakValueDictionary()
        # stochastic-refine minibatch device arrays, LRU-keyed on
        # (fingerprint, objective token, fraction, seed, covered, replay) —
        # everything the deterministic sampler's output is a pure function
        # of, so a rerun on the same snapshot re-uses the resident arrays
        # (the rung's 0-new-uploads contract) while a new append (new
        # fingerprint/covered) uploads its own minibatch
        self._stoch_uploads: "collections.OrderedDict[tuple, tuple]" \
            = collections.OrderedDict()
        # calibration records; bounded so a long-lived shared executor does
        # not grow without limit (recent sweeps are the relevant ones anyway)
        self._samples: "collections.deque[dict]" = collections.deque(
            maxlen=MAX_CALIBRATION_SAMPLES)
        self._stats = {
            "runs": 0,
            "step_compilations": 0,
            "step_cache_hits": 0,
            "uploads": 0,
            "upload_cache_hits": 0,
        }

    # ------------------------------------------------------------ kernels
    def resolve_zbuild(self, mp, core_dims: Sequence[int],
                       use_kernel: bool | None, oracle_s: int = 0) -> str:
        """Static Z-build variant of one mode step (delegates to the
        engine's shared gate — see ``repro.engine.zbuild.resolve_zbuild``;
        partitions hold their elements sorted by local row)."""
        return engine_zbuild.resolve_zbuild(mp.R_pad, core_dims, mp.mode,
                                            use_kernel, oracle_s)

    # ------------------------------------------------------------ planning
    def _check_plan(self, pl: PartitionPlan, t: SparseTensor,
                    core_dims: Sequence[int], path: str,
                    objective: str = "tucker") -> None:
        """Refuse a plan that does not describe (t, core_dims, path,
        objective) — the upload cache is keyed on plan identity, so a
        mismatched plan would silently run (and time) the wrong device
        arrays or score the wrong objective's cost."""
        if pl.P != self.P:
            raise ValueError(
                f"plan built for P={pl.P}, executor has P={self.P}")
        if pl.objective != objective:
            raise ValueError(
                f"plan was built for objective={pl.objective!r}, asked to "
                f"run {objective!r} — its view, metrics and cost describe "
                "a different training tensor; build a matching plan")
        if pl.fingerprint is not None \
                and pl.fingerprint != t.fingerprint():
            raise ValueError(
                f"plan was built for tensor {pl.fingerprint[:12]}…, "
                f"got {t.fingerprint()[:12]}…")
        if tuple(pl.core_dims) != tuple(int(k) for k in core_dims):
            raise ValueError(
                f"plan modeled core_dims={pl.core_dims}, asked to run "
                f"{tuple(core_dims)} — comm/calibration stats would "
                "mix models; build a plan with matching core_dims")
        if path != "auto" and pl.cost.path not in (path, "auto"):
            raise ValueError(
                f"plan costed for path={pl.cost.path!r}, running "
                f"{path!r}")

    def _mode_specs(self, pl: PartitionPlan, core_dims: Sequence[int],
                    path: str, use_kernel: bool | None,
                    precision: str = "f32", block_size: int = 1,
                    fused_zbuild: bool = False,
                    objective: str = "tucker",
                    warm_start: str = "none") -> list[_ModeSpec]:
        """Per-mode static step parameters for a plan.

        * ``backend``: from the plan's partition metrics (``path="auto"``
          compares the analytic per-mode comm models; P=1 is ``local``).
        * ``niter``: the shared Lanczos iteration count, clamped by the
          *true* row count and the effective K_hat — the same numbers the
          local engine path derives, so P=1 trajectories coincide. Counts
          *block* iterations when the mode runs the block driver.
        * ``zbuild``: the VMEM-gated Z-build variant, evaluated on the
          actual factor widths ``min(L_n, K_n)`` (``random_factors``'
          reduced QR clamps K > L), not the raw request.
        * ``precision``/``block_size``/``fused_zbuild``: the *resolved*
          roofline knobs; ``block_size`` is clamped per mode to the
          operator's rank cap via ``effective_block_size``.
        * ``warm_start``: the resolved warm-start mode (``"auto"`` settles
          per mode via ``choose_warm_start`` on the same static geometry
          the local engine path sees, so P=1 parity holds). A sketch mode
          runs the reduced ``sketch_niter`` budget and structurally
          forgoes the fused first product (the panel depends on Z).
        """
        parts = pl.parts
        eff = tuple(min(int(k), int(mp.L))
                    for k, mp in zip(core_dims, parts))
        # a plan costed with path="auto" already chose per-mode backends
        # under the (possibly per-backend-calibrated) cost model — honor
        # that choice instead of re-deriving it from raw bytes
        recorded = None
        if path == "auto" and pl.cost.path == "auto" and self.P > 1 \
                and len(pl.cost.mode_backends) == len(parts):
            recorded = pl.cost.mode_backends
        specs = []
        for n, mp in enumerate(parts):
            K_n = int(core_dims[n])
            khat = int(np.prod([eff[j] for j in range(len(eff)) if j != n]))
            if recorded is not None:
                backend = resolve_backend(recorded[n], self.P)
            else:
                backend = resolve_backend(
                    path, self.P, pl.comm(n) if path == "auto" else None)
            s_eff = effective_block_size(K_n, int(mp.L), khat, block_size)
            ws = choose_warm_start(warm_start, K_n, int(mp.L), khat, s_eff,
                                   fused_zbuild)
            fz_n = fused_zbuild and ws != "sketch"
            if ws == "sketch":
                s_eff = sketch_block_size(K_n, int(mp.L), khat, block_size)
                niter = sketch_niter(K_n, int(mp.L), khat, s_eff)
            else:
                niter = lanczos_niter(K_n, int(mp.L), khat,
                                      s_eff if (fz_n or s_eff > 1) else 1)
            specs.append(_ModeSpec(
                backend=backend,
                K_n=K_n,
                niter=niter,
                zbuild=self.resolve_zbuild(
                    mp, eff, use_kernel, s_eff if fz_n else 0),
                precision=precision,
                block_size=s_eff,
                fused_zbuild=fz_n,
                objective=objective,
                warm_start=ws,
            ))
        return specs

    # ------------------------------------------------------------- caches
    def _step_key(self, mp, path: str, K_n: int, niter: int,
                  use_kernel: bool = False, use_fused: bool = False,
                  precision: str = "f32", block_size: int = 1,
                  fused_zbuild: bool = False,
                  objective: str = "tucker",
                  warm_start: str = "none") -> tuple:
        # the static signature of one mode step: everything baked into the
        # trace besides array shapes (which jit itself specializes on) —
        # the comm backend (or historical path alias), the Z-build variant
        # (Pallas kernel vs jnp reference), the oracle-product variant, the
        # roofline knobs (precision, Lanczos panel width, fused Z-build),
        # the objective, and the warm-start mode: distinct variants never
        # alias each other's compiled steps, so the rerun contract holds
        # per (objective, warm_start) variant.
        return (path, "kern" if use_kernel else "ref",
                "fused" if use_fused else "plain", mp.mode, mp.R_pad,
                mp.Lp, mp.S_pad, self.P, K_n, niter,
                precision, int(block_size),
                "fz" if fused_zbuild else "zb", objective, warm_start)

    def _get_step(self, mp, path: str, K_n: int, use_kernel: bool = False,
                  niter: int | None = None, use_fused: bool = False,
                  precision: str = "f32", block_size: int = 1,
                  fused_zbuild: bool = False, objective: str = "tucker",
                  warm_start: str = "none"):
        niter = 2 * K_n if niter is None else int(niter)
        skey = self._step_key(mp, path, K_n, niter, use_kernel, use_fused,
                              precision, block_size, fused_zbuild, objective,
                              warm_start)
        with self._lock:
            step = self._steps.get(skey)
            if step is not None:
                # LRU touch: hot steps survive the executable bound
                self._steps[skey] = self._steps.pop(skey)
            else:
                ms = dict(mode=mp.mode, R_pad=mp.R_pad, Lp=mp.Lp,
                          S_pad=mp.S_pad, P=mp.P, use_kernel=use_kernel,
                          use_fused=use_fused, precision=precision,
                          block_size=int(block_size),
                          fused_zbuild=fused_zbuild,
                          warm_start=warm_start)
                if path == "zbuild":
                    fn = make_zbuild_step_fn(ms, use_kernel,
                                             precision=precision)
                    smap = jax.shard_map(
                        fn, mesh=self.mesh, check_vma=False,
                        in_specs=(P("ranks"),) * 3 + (P(),),
                        out_specs=P("ranks"),
                    )
                else:
                    backend = resolve_backend(path, self.P)
                    fn = make_mode_step_fn(ms, backend, K_n, niter)
                    smap = jax.shard_map(
                        fn, mesh=self.mesh, check_vma=False,
                        in_specs=(P("ranks"),) * 8 + (P(), P()),
                        out_specs=(P("ranks"), P()),
                    )
                step = jax.jit(smap)
                self._steps[skey] = step
                while len(self._steps) > MAX_COMPILED_STEPS:
                    old = next(iter(self._steps))
                    del self._steps[old]
                    # a re-created callable gets a fresh jit cache: its
                    # compilations must be counted again
                    self._seen_shapes = {
                        s: v for s, v in self._seen_shapes.items()
                        if s[0] != old}
        return skey, step

    def _note_shapes(self, skey, args: tuple, tally: dict) -> None:
        # jit compiles exactly when it first sees a shape signature for this
        # callable; mirror that condition to count compilations faithfully.
        # ``args`` are the step's arguments; ``tally`` is the per-run
        # ledger: concurrent runs on one shared executor must not read each
        # other's work out of the cumulative counters.
        sig = (skey, tuple(a.shape for a in jax.tree.leaves(args)))
        with self._lock:
            if sig in self._seen_shapes:
                self._stats["step_cache_hits"] += 1
                tally["step_cache_hits"] += 1
            else:
                self._seen_shapes[sig] = jax.tree.map(_abstract, args)
                self._stats["step_compilations"] += 1
                tally["step_compilations"] += 1

    def _call_step(self, skey, step, dev_args, factors, key, tally: dict):
        args = (*dev_args, factors, key)
        self._note_shapes(skey, args, tally)
        return step(*args)

    def _get_upload(self, pl: PartitionPlan, t: SparseTensor,
                    tally: dict) -> _PlanUpload:
        with self._lock:
            up = self._uploads.get(pl)
            if up is None:
                up = self._uploads_by_parts.get(id(pl.parts))
                if up is not None:  # plan copy sharing resident arrays
                    self._uploads[pl] = up
            if up is not None:
                self._stats["upload_cache_hits"] += 1
                tally["upload_cache_hits"] += 1
                return up
        # positional layout pinned by the engine's step functions; each
        # rank's slice of the leading axis goes to that rank's device
        ranks = NamedSharding(self.mesh, P("ranks"))
        dev_args = tuple(
            tuple(jax.device_put(getattr(mp, f), ranks) for f in ARRAY_FIELDS)
            for mp in pl.parts)
        row_perms = tuple(jnp.asarray(mp.row_perm) for mp in pl.parts)
        up = _PlanUpload(
            dev_args=dev_args,
            row_perms=row_perms,
            coords=jnp.asarray(t.coords, jnp.int32),
            values=jnp.asarray(t.values, jnp.float32),
            n_arrays=(len(ARRAY_FIELDS) + 1) * len(pl.parts) + 2,
        )
        with self._lock:
            won = self._uploads.setdefault(pl, up)
            if won is up:
                self._uploads_by_parts[id(pl.parts)] = up
            # the setdefault loser still paid a (discarded) transfer: count
            # its arrays as uploads either way so stats reflect real traffic
            self._stats["uploads"] += up.n_arrays
            tally["uploads"] += up.n_arrays
        return won

    # ------------------------------------------------------------ staging
    def stage_upload(self, pl: PartitionPlan, t: SparseTensor) -> dict:
        """Move a plan's device arrays host->device *now*, off the hot path.

        Safe to call from a producer thread (device puts are thread-safe;
        no computation is dispatched): the streaming scheduler stages
        uploads for tensor k+1 while the consumer thread sweeps tensor k,
        so the subsequent ``run`` on the same plan finds everything
        resident and its own upload tally is 0. Idempotent — a plan whose
        arrays are already resident transfers nothing.
        """
        tally = {"step_compilations": 0, "step_cache_hits": 0,
                 "uploads": 0, "upload_cache_hits": 0}
        with span("hooi.upload"):
            self._get_upload(pl, t, tally)
        return {"uploads": tally["uploads"],
                "already_resident": tally["upload_cache_hits"] > 0}

    def prepare(
        self,
        t: SparseTensor,
        core_dims: Sequence[int],
        scheme: str | Scheme | PartitionPlan = "auto",
        *,
        path: str = "liteopt",
        plan_seed: int = 0,
        pad_geometric: bool = False,
        objective=None,
        metrics=None,
    ) -> tuple[PartitionPlan, dict]:
        """Host-side half of a run: build/fetch the plan and stage uploads.

        This is the submission API the streaming scheduler drives from its
        producer pool — everything here is host work (numpy partitioning +
        device puts), no compilation and no sweep. Returns the plan and the
        staging report; a following ``run(t, core_dims, plan)`` is then a
        pure device hot path. ``objective`` shapes the staged view
        (completion partitions and uploads only its training entries) and
        stamps the plan; pass the same objective to the following ``run``.
        ``metrics`` (prebuilt-``Scheme`` only) supplies incrementally
        maintained ``SchemeMetrics``, skipping the O(nnz) recompute — the
        scheduler's repartition path hands its ``MetricsExtender`` output
        here.
        """
        assert path in RUN_PATHS
        obj = resolve_objective(objective)
        t = obj.prepare_tensor(t)
        if isinstance(scheme, PartitionPlan):
            pl = scheme
            self._check_plan(pl, t, core_dims, path, obj.name)
        else:
            pl = build_plan(t, scheme, self.P, core_dims=tuple(core_dims),
                            path=path, seed=plan_seed,
                            pad_geometric=pad_geometric, objective=obj,
                            metrics=metrics)
        return pl, self.stage_upload(pl, t)

    # ------------------------------------------------------------ observe
    def shard_devices(self, pl: PartitionPlan) -> dict:
        """mode -> devices holding ranks 0..P-1's element arrays of ``pl``
        (empty when the plan's arrays are not resident)."""
        with self._lock:
            up = self._uploads.get(pl)
        if up is None:
            return {}
        return {n: [s.device for s in sorted(
                    args[0].addressable_shards,
                    key=lambda s: s.index[0].start or 0)]
                for n, args in enumerate(up.dev_args)}

    def stats(self) -> dict:
        """Cumulative counters + cache occupancy."""
        with self._lock:
            return dict(self._stats, cached_steps=len(self._steps),
                        cached_plans=len(self._uploads))

    def op_scopes(self) -> dict[str, str]:
        """``{"<module>/<instruction>": scope}`` for every step executable
        this executor has run, ``scope`` one of ``repro.tracing.SCOPES``.

        A device op in a profiler trace is named by its executable's module
        (``jit_hooi_step_m0_local``) and its HLO instruction; this maps it
        to the ``zbuild``/``oracle``/``comm`` scope it was traced under
        (``repro.tracing.hlo_scopes``). Each cached step is lowered again
        from the arguments noted when it first ran and compiled, which jit
        serves from its cache. Call it after the work it describes, never
        inside a measured window.
        """
        with self._lock:
            todo = [(self._steps[sig[0]], args)
                    for sig, args in self._seen_shapes.items()
                    if sig[0] in self._steps]
        out: dict[str, str] = {}
        for step, args in todo:
            out.update(hlo_scopes(step.lower(*args).compile().as_text()))
        return out

    def calibration_samples(self) -> list[dict]:
        """Measured sweeps (flops/bytes/seconds) for ``fit_cost_model``."""
        with self._lock:
            return [dict(s) for s in self._samples]

    def profile_phases(
        self,
        t: SparseTensor,
        core_dims: Sequence[int],
        scheme: str | Scheme | PartitionPlan = "lite",
        *,
        path: str = "liteopt",
        plan_seed: int = 0,
        use_kernel: bool | None = None,
        use_fused_oracle: bool | None = None,
        precision: str | None = None,
        lanczos_block: int | None = None,
        fused_zbuild: bool | None = None,
        warm_start: str | None = None,
        repeats: int = 3,
        seed: int = 0,
        objective=None,
    ) -> dict:
        """Measure per-phase sweep times: TTM (Z build) vs Lanczos/SVD.

        Runs the Z-build-only step (``zbuild`` — same kernel/fallback choice
        as a real sweep) and the full mode step per mode, compiled first and
        then timed over ``repeats`` warm calls. Appends two calibration
        samples — a pure-TTM one (``svd_flops=0, comm_bytes=0``) and a full
        sweep — so ``fit_cost_model`` gets a full-rank per-phase design even
        from a single plan. Returns per-mode and total timings.

        ``precision`` labels the appended samples, so ``fit_cost_model``
        can fit a separate bf16 TTM rate for the ``auto`` precision policy.
        """
        assert path in RUN_PATHS
        tally = {"step_compilations": 0, "step_cache_hits": 0,
                 "uploads": 0, "upload_cache_hits": 0}
        obj = resolve_objective(objective)
        t = obj.prepare_tensor(t)
        if isinstance(scheme, PartitionPlan):
            pl = scheme
            self._check_plan(pl, t, core_dims, path, obj.name)
        else:
            pl = build_plan(t, scheme, self.P, core_dims=tuple(core_dims),
                            path=path, seed=plan_seed, objective=obj)
        N = t.ndim
        parts = pl.parts
        prec = resolve_precision(precision)
        blk = resolve_block_size(lanczos_block)
        fz = resolve_fused_zbuild(fused_zbuild)
        warm = resolve_warm_start(warm_start)
        specs = self._mode_specs(pl, core_dims, path, use_kernel,
                                 precision=prec, block_size=blk,
                                 fused_zbuild=fz, objective=obj.name,
                                 warm_start=warm)
        up = self._get_upload(pl, t, tally)
        key = jax.random.PRNGKey(seed)
        factors = random_factors(t.shape, core_dims, key)
        z_kernel = {n: specs[n].use_kernel for n in range(N)}

        def _timed(fn, *args):
            out = fn(*args)  # compile + warm
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            for _ in range(repeats):
                out = fn(*args)
            jax.block_until_ready(out)
            return (time.perf_counter() - t0) / repeats

        per_mode = {}
        ttm_s = full_s = 0.0
        for n in range(N):
            sp = specs[n]
            zkey, zstep = self._get_step(parts[n], "zbuild", sp.K_n,
                                         use_kernel=sp.use_kernel,
                                         precision=sp.precision)
            skey, step = self._get_step(parts[n], sp.backend, sp.K_n,
                                        use_kernel=sp.use_kernel,
                                        niter=sp.niter,
                                        use_fused=bool(use_fused_oracle),
                                        precision=sp.precision,
                                        block_size=sp.block_size,
                                        fused_zbuild=sp.fused_zbuild,
                                        objective=sp.objective,
                                        warm_start=sp.warm_start)
            kk = jax.random.fold_in(key, 7000 + n)
            # register the shape signatures exactly like a run() would, so a
            # later run() on these shapes sees them as already-compiled (the
            # 0-new-compilations reuse contract) and its first sweep is not
            # mis-flagged cold
            self._note_shapes(zkey, (*up.dev_args[n][:3], factors), tally)
            self._note_shapes(skey, (*up.dev_args[n], factors, kk), tally)
            tz = _timed(zstep, *up.dev_args[n][:3], factors)
            tf = _timed(step, *up.dev_args[n], factors, kk)
            per_mode[n] = {"ttm_s": tz, "full_s": tf,
                           "svd_s": max(tf - tz, 0.0)}
            ttm_s += tz
            full_s += tf
        m = pl.metrics
        backend_label = _backend_label(specs)
        with self._lock:
            self._samples.append({
                "critical_path_flops": m.ttm_flops_max,
                "ttm_flops": m.ttm_flops_max, "svd_flops": 0,
                "comm_bytes": 0.0, "seconds": ttm_s, "warm": True,
                "P": self.P, "path": path, "scheme": pl.name,
                "phase": "ttm", "kernel": all(z_kernel.values()),
                "comm_backend": backend_label, "precision": prec,
            })
            self._samples.append({
                "critical_path_flops": m.critical_path_flops,
                "ttm_flops": m.ttm_flops_max,
                "svd_flops": m.svd_flops_max,
                "comm_bytes": _run_comm_bytes(pl, specs),
                "seconds": full_s,
                "warm": True, "P": self.P, "path": path, "scheme": pl.name,
                "phase": "sweep", "kernel": all(z_kernel.values()),
                "comm_backend": backend_label, "precision": prec,
            })
        return {"ttm_s": ttm_s, "full_s": full_s,
                "svd_s": max(full_s - ttm_s, 0.0),
                "per_mode": per_mode, "z_kernel": z_kernel}

    # ---------------------------------------------------------------- run
    @_traced_call
    def run(
        self,
        t: SparseTensor,
        core_dims: Sequence[int],
        scheme: str | Scheme | PartitionPlan = "lite",
        *,
        n_invocations: int = 3,
        path: str = "liteopt",
        seed: int = 0,
        plan_seed: int = 0,
        use_kernel: bool | None = None,
        use_fused_oracle: bool | None = None,
        precision: str | None = None,
        lanczos_block: int | None = None,
        fused_zbuild: bool | None = None,
        warm_start: str | None = None,
        init_factors: Sequence[jnp.ndarray] | None = None,
        pad_geometric: bool = False,
        objective=None,
    ) -> tuple[Decomposition, DistHooiStats]:
        """One distributed HOOI decomposition on this executor's mesh.

        ``scheme`` is the string sugar (any name ``repro.core.plan.plan``
        accepts, including ``"auto"``), a prebuilt ``Scheme``, or a full
        ``PartitionPlan``. String/Scheme forms go through the content-keyed
        plan cache with ``plan_seed`` threaded to randomized schemes; a
        cached plan additionally reuses this executor's device uploads and
        compiled steps.

        ``path`` selects the comm-backend family: ``"baseline"`` (psum),
        ``"liteopt"`` (boundary) or ``"auto"`` (per mode from the plan's
        analytic comm model); P=1 always resolves to the collective-free
        ``local`` backend. ``use_kernel`` selects the Z-build variant per
        mode step (see ``repro.engine.zbuild.resolve_zbuild``);
        ``use_fused_oracle`` (None/False = off) routes the Lanczos oracle
        products through the fused Pallas kernel.

        Roofline knobs (resolved through the same engine resolvers
        single-process ``hooi`` uses, so P=1 parity holds per variant):
        ``precision`` — ``"f32"``/``"bf16"``/``"auto"``/None (None honors
        ``REPRO_PRECISION``); ``lanczos_block`` — requested s-step Lanczos
        panel width, clamped per mode (None honors
        ``REPRO_LANCZOS_BLOCK``); ``fused_zbuild`` — fuse the Z build with
        the first oracle panel product (None honors ``REPRO_FUSED_ZBUILD``).
        Every knob is part of the compiled-step cache key.

        ``pad_geometric`` must match how the tensor was prepared: it is
        part of the plan-cache key, so a ``prepare(..., pad_geometric=
        True)`` followed by a string/Scheme ``run`` with the default would
        silently build (and upload, and compile) a second tight-pad plan.

        ``warm_start`` — ``"none"``/``"sketch"``/``"auto"``/None (None
        honors ``REPRO_WARM_START``): seed the oracle's block driver with
        the factor-sketched range-finder panel under the reduced
        ``sketch_niter`` budget; ``"none"`` reproduces the historical
        trajectories bitwise. ``init_factors`` (default None = the
        seed-keyed ``random_factors``) carries previous factors into this
        run — the streaming scheduler hands the prior decomposition here so
        the sketch warm start persists across runs and across the
        ``reselect`` rung; widths are coerced to ``core_dims`` (truncate /
        orthonormal-complete) when adaptive rank changed them.
        """
        assert path in RUN_PATHS
        # per-run ledger: deltas must be this run's own work, not whatever
        # a concurrent run on the shared executor did meanwhile
        tally = {"step_compilations": 0, "step_cache_hits": 0,
                 "uploads": 0, "upload_cache_hits": 0}
        obj = resolve_objective(objective)
        t = obj.prepare_tensor(t)
        with span("hooi.plan") as sp:
            if isinstance(scheme, PartitionPlan):
                pl = scheme
                self._check_plan(pl, t, core_dims, path, obj.name)
                cache_hit = False
            else:
                pl = build_plan(t, scheme, self.P,
                                core_dims=tuple(core_dims), path=path,
                                seed=plan_seed, pad_geometric=pad_geometric,
                                objective=obj)
                # thread-local outcome: differencing the global miss
                # counter misreports hits when a concurrent submitter
                # builds a plan in the same window (the pool's producer
                # threads routinely do)
                cache_hit = last_plan_call_cache_hit()
        partition_build_s = sp.seconds

        N = t.ndim
        key = jax.random.PRNGKey(seed)
        if init_factors is None:
            factors = random_factors(t.shape, core_dims, key)
        else:
            factors = _coerce_factors(init_factors, t.shape, core_dims, key)
        parts = pl.parts
        comm = {n: pl.comm(n) for n in range(N)}

        fused = bool(use_fused_oracle)
        prec = resolve_precision(precision)
        blk = resolve_block_size(lanczos_block)
        fz = resolve_fused_zbuild(fused_zbuild)
        warm = resolve_warm_start(warm_start)
        specs = self._mode_specs(pl, core_dims, path, use_kernel,
                                 precision=prec, block_size=blk,
                                 fused_zbuild=fz, objective=obj.name,
                                 warm_start=warm)
        z_kernel = {n: specs[n].use_kernel for n in range(N)}
        steps = [self._get_step(parts[n], specs[n].backend, specs[n].K_n,
                                use_kernel=specs[n].use_kernel,
                                niter=specs[n].niter, use_fused=fused,
                                precision=specs[n].precision,
                                block_size=specs[n].block_size,
                                fused_zbuild=specs[n].fused_zbuild,
                                objective=specs[n].objective,
                                warm_start=specs[n].warm_start)
                 for n in range(N)]
        with span("hooi.upload"):
            up = self._get_upload(pl, t, tally)
        eff = [min(int(k), int(mp.L)) for k, mp in zip(core_dims, parts)]
        z_pairs = {}
        for n in range(N):
            if specs[n].zbuild == "windowed":
                pkey = (n, prec)
                if pkey not in up.pairs_per_block:
                    up.pairs_per_block[pkey] = _pairs_per_block(
                        parts[n], eff, prec)
                z_pairs[n] = up.pairs_per_block[pkey]
        backend_label = _backend_label(specs)
        run_bytes = _run_comm_bytes(pl, specs)

        spectra: dict = {}

        def mode_step(n, facs, kk):
            skey, step = steps[n]
            F_new, sv = self._call_step(skey, step, up.dev_args[n],
                                        facs, kk, tally)
            # last-sweep spectrum estimate per mode (overwritten each
            # sweep) — the adaptive-rank policy reads its tail
            spectra[n] = sv
            # F_new rows are in relabelled space; restore original order,
            # then let the objective post-process the full-row factor —
            # the exact update the local engine path applies, so P=1
            # parity covers every objective
            return obj.refine_factor(jnp.asarray(F_new)[up.row_perms[n]],
                                     jnp.asarray(sv))

        sweep_state = {"compiles": tally["step_compilations"]}

        def on_sweep(it, sweep_s, _fit):
            with self._lock:
                self._samples.append({
                    "critical_path_flops": pl.metrics.critical_path_flops,
                    # per-phase split (bottleneck-rank flops): lets
                    # fit_cost_model separate the TTM and Lanczos/SVD rates
                    "ttm_flops": pl.metrics.ttm_flops_max,
                    "svd_flops": pl.metrics.svd_flops_max,
                    "comm_bytes": run_bytes,
                    "seconds": sweep_s,
                    # sweeps that paid jit time measure XLA, not the machine
                    "warm": tally["step_compilations"]
                    == sweep_state["compiles"],
                    "P": self.P,
                    "path": path,
                    "scheme": pl.name,
                    # True when every mode's Z build ran the Pallas kernel —
                    # rates fitted from kernel sweeps are kernel-speed rates
                    "kernel": all(z_kernel.values()),
                    "comm_backend": backend_label,
                    "precision": prec,
                })
            sweep_state["compiles"] = tally["step_compilations"]

        objective_metrics: dict = {}
        dec, fits = run_hooi_sweeps(up.coords, up.values, t, factors, key,
                                    n_invocations, mode_step,
                                    on_sweep=on_sweep, objective=obj,
                                    metrics_out=objective_metrics)

        with self._lock:
            self._stats["runs"] += 1
        stats = DistHooiStats(
            fits=fits, comm=comm,
            r_pad={n: parts[n].R_pad for n in range(N)},
            e_pad={n: parts[n].E_pad for n in range(N)},
            scheme=pl.name,
            selection=pl.candidates,
            partition_build_s=partition_build_s,
            plan_cache_hit=cache_hit,
            plan_cache=plan_cache_stats(),
            step_compilations=tally["step_compilations"],
            step_cache_hits=tally["step_cache_hits"],
            uploads=tally["uploads"],
            upload_cache_hit=tally["upload_cache_hits"] > 0,
            executor=self.stats(),
            z_kernel=z_kernel,
            z_build={n: specs[n].zbuild for n in range(N)},
            z_pairs_per_block=z_pairs or None,
            comm_backends={n: specs[n].backend for n in range(N)},
            fused_oracle=fused,
            precision=prec,
            lanczos_block={n: specs[n].block_size for n in range(N)},
            fused_zbuild=fz,
            z_passes={n: count_z_passes(
                specs[n].niter, specs[n].fused_zbuild,
                warm_start=specs[n].warm_start,
                power_iters=DEFAULT_POWER_ITERS
                if specs[n].warm_start == "sketch" else 0)
                for n in range(N)},
            objective=obj.name,
            objective_metrics=objective_metrics or None,
            warm_start={n: specs[n].warm_start for n in range(N)},
            mode_spectra={n: np.asarray(v) for n, v in spectra.items()}
            or None,
        )
        return dec, stats

    # ----------------------------------------------------- stochastic rung
    def _get_stoch_step(self, mode: int, num_rows: int, K_n: int, niter: int,
                        block_size: int, use_kernel: bool, precision: str,
                        objective: str, sample_fraction: float,
                        sample_seed: int):
        """Jitted minibatch step, cached in the same LRU as the shard_map
        steps. The key carries the sample fraction and seed (the ISSUE's
        rerun discipline: a rerun of the same sampled refine is 0 new jit,
        a different sampling policy never aliases a compiled step) plus
        every static trace parameter; the padded minibatch shape is jit's
        own specialization axis, counted by ``_note_shapes`` exactly like
        the distributed steps."""
        skey = ("stoch", int(mode), int(num_rows), int(K_n), int(niter),
                int(block_size), precision,
                "kern" if use_kernel else "ref", objective,
                float(sample_fraction), int(sample_seed))
        with self._lock:
            step = self._steps.get(skey)
            if step is not None:
                self._steps[skey] = self._steps.pop(skey)
            else:
                step = jax.jit(make_stochastic_step_fn(
                    int(mode), int(num_rows), int(K_n), int(niter),
                    int(block_size), use_kernel=use_kernel,
                    precision=precision))
                self._steps[skey] = step
                while len(self._steps) > MAX_COMPILED_STEPS:
                    old = next(iter(self._steps))
                    del self._steps[old]
                    self._seen_shapes = {
                        s: v for s, v in self._seen_shapes.items()
                        if s[0] != old}
        return skey, step

    def _get_stoch_upload(self, t: SparseTensor, obj, sb,
                          covered_nnz: int, sample_fraction: float,
                          sample_seed: int, replay_nnz: int,
                          tally: dict) -> tuple:
        """Device arrays for one stochastic refine: the padded minibatch
        plus the full-snapshot COO (fit/core accounting). The full arrays
        are zero-padded to the next power of two as well — coordinate-0 /
        value-0 rows contribute nothing to the elementwise core build, and
        the pow2 shape keeps the jitted full-pass core computation
        (``_get_stoch_core``) compiled across many appends. Keyed on
        everything ``sample_batch``'s output is a pure function of, so a
        rerun of the same refine transfers nothing."""
        ukey = (t.fingerprint(), obj.cache_token(), float(sample_fraction),
                int(sample_seed), int(covered_nnz), int(replay_nnz))
        with self._lock:
            up = self._stoch_uploads.get(ukey)
            if up is not None:
                self._stoch_uploads.move_to_end(ukey)
                self._stats["upload_cache_hits"] += 1
                tally["upload_cache_hits"] += 1
                return up
        pad = next_pow2(int(t.nnz)) - int(t.nnz)
        full_coords = np.pad(np.asarray(t.coords), ((0, pad), (0, 0)))
        full_values = np.pad(np.asarray(t.values), (0, pad))
        up = (jnp.asarray(sb.coords, jnp.int32),
              jnp.asarray(sb.values, jnp.float32),
              jnp.asarray(full_coords, jnp.int32),
              jnp.asarray(full_values, jnp.float32))
        with self._lock:
            won = self._stoch_uploads.setdefault(ukey, up)
            self._stoch_uploads.move_to_end(ukey)
            while len(self._stoch_uploads) > MAX_STOCH_UPLOADS:
                self._stoch_uploads.popitem(last=False)
            self._stats["uploads"] += len(up)
            tally["uploads"] += len(up)
        return won

    def _get_stoch_core(self):
        """Jitted full-pass core build (``core_from_factors``) for the
        stochastic rung's final fit accounting. One O(nnz) device pass per
        refine instead of the sweep loop's eager per-sweep build; the pow2
        padding of the full upload keeps its compiled shape stable across
        appends, so steady-state refines replay it with zero tracing."""
        skey = ("stochcore",)
        with self._lock:
            fn = self._steps.get(skey)
            if fn is not None:
                self._steps[skey] = self._steps.pop(skey)
            else:
                from repro.core.ttm import core_from_factors

                fn = jax.jit(core_from_factors)
                self._steps[skey] = fn
                while len(self._steps) > MAX_COMPILED_STEPS:
                    old = next(iter(self._steps))
                    del self._steps[old]
                    self._seen_shapes = {
                        s: v for s, v in self._seen_shapes.items()
                        if s[0] != old}
        return skey, fn

    @_traced_call
    def run_stochastic(
        self,
        t: SparseTensor,
        core_dims: Sequence[int],
        pl: PartitionPlan,
        *,
        init_factors: Sequence[jnp.ndarray],
        covered_nnz: int,
        sample_fraction: float,
        sample_seed: int = 0,
        replay_nnz: int = 1024,
        step_size: float = 0.5,
        step_decay: float = 0.5,
        step_index: int = 0,
        n_invocations: int = 1,
        seed: int = 0,
        use_kernel: bool | None = None,
        precision: str | None = None,
        objective=None,
    ) -> tuple[Decomposition, DistHooiStats]:
        """One stochastic-refine pass: update carried factors from a
        deterministic minibatch of the appended elements (plus a replay
        reservoir of the refined prefix) instead of a full sweep.

        ``pl`` is the stream's *adopted* plan — it stays untouched (its
        partitions describe the pre-append prefix; the whole point of the
        rung is not rebuilding them) and contributes its identity checks
        (P, objective, core_dims) and modeled cost only. The fingerprint is
        deliberately *not* checked against ``t``: the snapshot has grown
        past the plan by construction.

        Device work is O(minibatch): each mode runs the jitted
        single-device ``make_stochastic_step_fn`` (sampled Z-build through
        the same kernel/reference seam, sketch-seeded from the carried
        factor), the returned basis is Procrustes-blended into the carried
        factor at ``eta = step_size / (1 + step_decay * step_index)``
        (``core.stochastic``), and the objective's ``refine_factor`` runs
        after the blend — the same post-oracle discipline as the full path.
        The only O(nnz) device work is the final core/fit accounting: one
        jitted pass over the pow2-padded full snapshot per refine
        (``_get_stoch_core``), where a full sweep pays an O(nnz) Z-build
        per mode per invocation.

        ``init_factors`` is required: the rung refines carried factors;
        there is nothing to refine on a cold stream (the scheduler routes
        first sight to ``"plan"``).
        """
        tally = {"step_compilations": 0, "step_cache_hits": 0,
                 "uploads": 0, "upload_cache_hits": 0}
        obj = resolve_objective(objective)
        t = obj.prepare_tensor(t)
        if pl.P != self.P:
            raise ValueError(
                f"plan built for P={pl.P}, executor has P={self.P}")
        if pl.objective != obj.name:
            raise ValueError(
                f"plan was built for objective={pl.objective!r}, asked to "
                f"refine under {obj.name!r}")
        if tuple(pl.core_dims) != tuple(int(k) for k in core_dims):
            raise ValueError(
                f"plan modeled core_dims={pl.core_dims}, asked to refine "
                f"{tuple(core_dims)}")
        if init_factors is None:
            raise ValueError("stochastic refine needs carried factors "
                             "(init_factors) — a cold stream takes the "
                             "full plan path")

        N = t.ndim
        key = jax.random.PRNGKey(seed)
        factors = _coerce_factors(init_factors, t.shape, core_dims, key)
        sb = sample_batch(np.asarray(t.coords), np.asarray(t.values),
                          covered_nnz, sample_fraction, sample_seed,
                          replay_nnz=replay_nnz)
        up = self._get_stoch_upload(t, obj, sb, covered_nnz,
                                    sample_fraction, sample_seed,
                                    replay_nnz, tally)
        sb_coords, sb_values, full_coords, full_values = up

        prec = resolve_precision(precision)
        eff = tuple(min(int(k), int(L))
                    for k, L in zip(core_dims, t.shape))
        eta = step_eta(step_size, step_decay, step_index)
        steps = []
        z_kernel = {}
        lanczos_block = {}
        for n in range(N):
            L = int(t.shape[n])
            K_n = int(eff[n])
            khat = int(np.prod([eff[j] for j in range(N) if j != n]))
            s_eff = sketch_block_size(K_n, L, khat, 1)
            niter = sketch_niter(K_n, L, khat, s_eff)
            kern = engine_zbuild.resolve_kernel(L, eff, n, use_kernel)
            z_kernel[n] = kern
            lanczos_block[n] = s_eff
            steps.append(self._get_stoch_step(
                n, L, K_n, niter, s_eff, kern, prec, obj.name,
                sample_fraction, sample_seed))

        spectra: dict = {}

        def mode_step(n, facs, kk):
            skey, step = steps[n]
            self._note_shapes(skey, (sb_coords, sb_values, facs, kk), tally)
            left, sv = step(sb_coords, sb_values, facs, kk)
            spectra[n] = sv
            blended = blend_factor(facs[n], left, eta)
            return obj.refine_factor(blended, jnp.asarray(sv))

        # the sweep loop runs over the MINIBATCH: its per-sweep core/fit
        # accounting is then O(minibatch) like the steps themselves. The
        # true core and fit are computed once afterwards from the padded
        # full snapshot via the jitted full-pass builder — one O(nnz)
        # device pass per refine, against a full sweep's one per mode
        # per invocation.
        dec, fits = run_hooi_sweeps(sb_coords, sb_values, t, factors,
                                    key, n_invocations, mode_step,
                                    objective=obj)
        ckey, core_fn = self._get_stoch_core()
        self._note_shapes(ckey, (full_coords, full_values, dec.factors),
                          tally)
        core = obj.finalize_core(
            core_fn(full_coords, full_values, dec.factors), dec.factors)
        dec = Decomposition(core=core, factors=dec.factors)
        fits = fits[:-1] + [obj.fit(t, core, dec.factors)]
        objective_metrics: dict = {}
        obj.sweep_metrics(objective_metrics, t, core, dec.factors)
        with self._lock:
            self._stats["runs"] += 1
        stats = DistHooiStats(
            fits=fits, comm={},
            r_pad={}, e_pad={},
            scheme=pl.name,
            step_compilations=tally["step_compilations"],
            step_cache_hits=tally["step_cache_hits"],
            uploads=tally["uploads"],
            upload_cache_hit=tally["upload_cache_hits"] > 0,
            executor=self.stats(),
            z_kernel=z_kernel,
            z_build={n: "resident" if k else "reference"
                     for n, k in z_kernel.items()},
            comm_backends={n: "local" for n in range(N)},
            precision=prec,
            lanczos_block=lanczos_block,
            objective=obj.name,
            objective_metrics=objective_metrics or None,
            warm_start={n: "sketch" for n in range(N)},
            mode_spectra={n: np.asarray(v) for n, v in spectra.items()}
            or None,
            sample_fraction=float(sample_fraction),
            sample_nnz=int(sb.sample_nnz),
            replay_nnz=int(sb.replay_nnz),
            step_size=float(eta),
        )
        return dec, stats


def _abstract(a) -> jax.ShapeDtypeStruct:
    """A step argument as jit saw it: shape, dtype, and its sharding when
    the array is committed to devices (an uncommitted one has none)."""
    return jax.ShapeDtypeStruct(
        a.shape, a.dtype, weak_type=a.aval.weak_type,
        sharding=a.sharding if getattr(a, "committed", True) else None)


def _coerce_factors(factors, shape: Sequence[int],
                    core_dims: Sequence[int],
                    key: jax.Array) -> list[jnp.ndarray]:
    """Fit carried-over factors to this run's (shape, core_dims).

    The streaming scheduler hands the previous run's factors back as
    ``init_factors`` so the sketch warm start seeds from real structure.
    When the adaptive-rank policy changed a mode's ``K_n`` the carried
    factor is truncated (shrink) or completed with an orthonormalized
    random complement (grow) — deterministic per (key, mode), mirroring
    ``random_factors``' key discipline.
    """
    out = []
    for n, (L, K) in enumerate(zip(shape, core_dims)):
        F = jnp.asarray(factors[n], jnp.float32)
        if int(F.shape[0]) != int(L):
            raise ValueError(
                f"init_factors[{n}] has {F.shape[0]} rows, tensor mode has "
                f"{L} — factors carry across runs on the same mode sizes")
        K = min(int(K), int(L))  # random_factors' reduced-QR clamp
        if int(F.shape[1]) > K:
            F = F[:, :K]
        elif int(F.shape[1]) < K:
            extra = jax.random.normal(
                jax.random.fold_in(key, 4100 + n),
                (int(L), K - int(F.shape[1])), jnp.float32)
            F, _ = jnp.linalg.qr(jnp.concatenate([F, extra], axis=1))
        out.append(F)
    return out


def _backend_label(specs: Sequence[_ModeSpec]) -> str:
    """One calibration label per run: the uniform backend or 'mixed'."""
    names = {sp.backend for sp in specs}
    return names.pop() if len(names) == 1 else "mixed"


def _run_comm_bytes(pl: PartitionPlan, specs: Sequence[_ModeSpec]) -> float:
    """Modeled comm bytes for the backends that actually run.

    A plan may legally run under a different backend family than it was
    costed for (auto-costed plan under an explicit path, and vice versa);
    calibration samples must pair measured seconds with the bytes of the
    *executed* backends, not ``pl.cost.comm_bytes``, or fitted per-backend
    bandwidths would be biased by the mismatch.
    """
    from repro.engine.comm import backend_comm_bytes

    total = pl.metrics.fm_volume * 4.0
    for n, sp in enumerate(specs):
        total += backend_comm_bytes(sp.backend, pl.comm(n))
    return total


# ------------------------------------------------------- shared executors
_SHARED: dict[int, HooiExecutor] = {}  # default-mesh executors, keyed by P
# caller-provided meshes: content-keyed (jax Mesh equality/hash compare
# devices + axis names, so fresh-but-equal meshes share one executor) and
# LRU-bounded — an executor pins its mesh and compiled steps, and the old
# per-call dist_hooi never retained any of that
_SHARED_BY_MESH: dict[object, HooiExecutor] = {}
MAX_SHARED_MESH_EXECUTORS = 8
_SHARED_LOCK = threading.Lock()


def shared_executor(P_ranks: int, mesh=None) -> HooiExecutor:
    """Process-wide executor for (P, mesh) — what ``dist_hooi`` runs on.

    Sharing the executor is what makes repeated ``dist_hooi`` calls (and
    interleaved calls on different cached tensors — multi-tensor batching)
    skip jit and host->device transfer without any caller-side plumbing.
    """
    P_ranks = int(P_ranks)
    with _SHARED_LOCK:
        if mesh is None:
            ex = _SHARED.get(P_ranks)
            if ex is None:
                ex = HooiExecutor(P_ranks)
                _SHARED[P_ranks] = ex
            return ex
        ex = _SHARED_BY_MESH.get(mesh)
        if ex is not None and ex.P == P_ranks:
            # LRU touch: hot meshes survive the bound
            _SHARED_BY_MESH[mesh] = _SHARED_BY_MESH.pop(mesh)
            return ex
        ex = HooiExecutor(P_ranks, mesh=mesh)
        _SHARED_BY_MESH[mesh] = ex
        while len(_SHARED_BY_MESH) > MAX_SHARED_MESH_EXECUTORS:
            _SHARED_BY_MESH.pop(next(iter(_SHARED_BY_MESH)))
        return ex
