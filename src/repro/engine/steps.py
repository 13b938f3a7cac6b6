"""Mode steps composed from the three engine stages.

A HOOI mode step is exactly: **Z-build** (``engine.zbuild``) -> **oracle**
(``engine.oracle``: per-device Z products + the one shared Lanczos body) ->
**comm backend** (``engine.comm``: how the products cross the mesh). This
module is the only place the stages meet. Each stage runs under the
``jax.named_scope`` of its name (``zbuild``, ``oracle``; ``comm`` nested
where the collectives run), and each step function carries a stable name
for its executable (``hooi_step_m<mode>_<backend>``):

* ``make_mode_step_fn`` — the function ``HooiExecutor`` wraps in
  ``shard_map``/``jit`` (one per static step signature). Its positional
  layout (8 sharded per-device arrays, then replicated factors + key) is
  part of the executor's upload-cache contract.
* ``make_zbuild_step_fn`` — the Z-build-only probe for per-phase
  calibration.
* ``make_core_step_fn`` — the core from the last mode's Z (paper Fig. 2,
  last step: ``G₍N₎ = U_Nᵀ Z_N``), through that step's ``OracleSpace``
  under the scope ``core``; the last mode step keeps its Z for it
  (``ms["keep_z"]``).
* ``local_mode_step`` — the same composition with the identity partition
  and the ``local`` backend semantics, no ``shard_map``: this is what
  ``repro.core.hooi`` runs, making the single-process reference the P=1
  instantiation of the engine rather than a second implementation.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core.lanczos import (block_start_panel, gk_block_bidiag,
                                lanczos_bidiag, lanczos_niter,
                                svd_from_bidiag)
from repro.core.sketch import (DEFAULT_POWER_ITERS, power_refine,
                               seeded_start_panel, sketch_block_size,
                               sketch_niter)
from repro.core.ttm import fold
from .comm import AXIS, make_comm_space
from .oracle import solve_oracle, solve_oracle_block, z_products
from .zbuild import build_local_z, build_local_z_oracle

__all__ = ["make_mode_step_fn", "make_zbuild_step_fn", "make_core_step_fn",
           "local_mode_step", "local_core_step", "local_core_from_z",
           "make_stochastic_step_fn", "ARRAY_FIELDS"]

# the per-device ModePartition arrays a distributed step consumes, in the
# positional order the step functions (and the executor's uploads) use
ARRAY_FIELDS = ("coords", "values", "local_rows", "row_gid", "row_owned",
                "bnd_slot", "own_bnd_slot", "own_bnd_off")


def f32_matmuls(fn):
    """Run (and trace) ``fn`` with f32 matmuls at full f32 precision.

    The TPU's default contracts f32 operands in one bf16 pass (~3 decimal
    digits), which would leave the Lanczos factors visibly non-orthonormal;
    on the CPU this changes nothing. Dots that pin their own precision
    (the Pallas kernels) keep it.
    """

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("float32"):
            return fn(*args, **kwargs)

    return wrapped


def _named(fn, name: str):
    """Give a step function the stable name its executable carries
    (``jit_<name>`` in the compiled module and in a trace)."""
    fn.__name__ = fn.__qualname__ = name
    return fn


def make_zbuild_step_fn(ms: dict, use_kernel: bool, precision: str = "f32"):
    """TTM-only step: just the local Z build (per-phase calibration probe)."""

    @f32_matmuls
    def fn(coords, values, local_rows, factors):
        # shard_map keeps a leading size-1 'ranks' axis on sharded operands
        coords, values, local_rows = (
            x[0] for x in (coords, values, local_rows))
        with jax.named_scope("zbuild"):
            Z = build_local_z(coords, values, local_rows, factors,
                              ms["mode"], ms["R_pad"], use_kernel=use_kernel,
                              precision=precision)
        return Z[None]

    return _named(fn, f"hooi_zbuild_m{ms['mode']}")


def make_mode_step_fn(ms: dict, backend: str, K_n: int, niter: int):
    """One distributed mode step for ``shard_map`` over the 'ranks' axis.

    ``ms`` is the static partition signature (mode, R_pad, Lp, S_pad, P,
    use_kernel, use_fused, precision, block_size, fused_zbuild, warm_start,
    keep_z);
    ``backend`` one of ``engine.comm``'s names. All of these are baked into
    the trace — the executor keys its compiled-step cache on them. ``niter``
    counts block iterations when ``block_size > 1``.

    ``warm_start="sketch"`` replaces the key-derived start panel with the
    factor-seeded range-finder sketch: each device recovers the original
    row id of every local Z row from its coords, contracts ``Z_pᵀ`` against
    the gathered rows of the incoming factor (partial sums psum to the
    exact global ``Zᵀ F``), orthonormalizes, and power-iterates through the
    comm space — so the block driver refines an already-good subspace under
    the reduced ``sketch_niter`` budget. The sketch panel depends on Z, so
    it cannot be served by the fused build's pre-Z first product —
    ``fused_zbuild`` is structurally off for sketch modes (the spec builder
    normalizes it; asserted here).

    ``keep_z`` (the last mode's step) returns the device's Z as a third
    output, ``(1, R_pad, K_hat)``, for ``make_core_step_fn``.
    """
    precision = ms.get("precision", "f32")
    block_size = int(ms.get("block_size", 1))
    fused_zbuild = bool(ms.get("fused_zbuild", False))
    warm_start = ms.get("warm_start", "none")
    keep_z = bool(ms.get("keep_z", False))
    assert not (fused_zbuild and warm_start == "sketch"), \
        "sketch warm start excludes the fused first product (spec builder)"

    @f32_matmuls
    def fn(coords, values, local_rows, row_gid, row_owned, bnd_slot,
           own_bnd_slot, own_bnd_off, factors, key):
        (coords, values, local_rows, row_gid, row_owned, bnd_slot,
         own_bnd_slot, own_bnd_off) = (
            x[0] for x in (coords, values, local_rows, row_gid, row_owned,
                           bnd_slot, own_bnd_slot, own_bnd_off))
        arrs = dict(row_gid=row_gid, row_owned=row_owned, bnd_slot=bnd_slot,
                    own_bnd_slot=own_bnd_slot, own_bnd_off=own_bnd_off)
        use_kernel = ms.get("use_kernel", False)
        first_panel = first_product = None
        with jax.named_scope("zbuild"):
            if fused_zbuild:
                Khat = 1
                for j, f in enumerate(factors):
                    if j != ms["mode"]:
                        Khat *= int(f.shape[1])
                first_panel = block_start_panel(key, Khat, block_size)
                Z, ZV1 = build_local_z_oracle(
                    coords, values, local_rows, factors, ms["mode"],
                    ms["R_pad"], first_panel, use_kernel=use_kernel,
                    precision=precision)
            else:
                Z = build_local_z(coords, values, local_rows, factors,
                                  ms["mode"], ms["R_pad"],
                                  use_kernel=use_kernel, precision=precision)
        with jax.named_scope("oracle"):
            zmv, zrmv = z_products(Z, fused=ms.get("use_fused", False))
            space = make_comm_space(backend, ms, arrs, zmv, zrmv)
            if warm_start == "sketch":
                # original row id per local Z row, recovered from the
                # element coords (padding elements carry coord 0 and land on
                # the last real row's slot, where max() keeps the real id;
                # element-free rows stay 0 — their Z row is zero, so the
                # gathered factor row contributes nothing either way)
                F_n = factors[ms["mode"]]
                orig = jnp.zeros((ms["R_pad"],), jnp.int32).at[
                    local_rows].max(coords[:, ms["mode"]])
                w = min(block_size, int(F_n.shape[1]))
                seed = Z.T @ F_n.at[orig].get(
                    mode="fill", fill_value=0.0)[:, :w]
                if backend != "local":
                    with jax.named_scope("comm"):
                        seed = jax.lax.psum(seed, AXIS)
                first_panel = seeded_start_panel(seed, key, Z.shape[1],
                                                 block_size)
                first_panel = power_refine(space.matvec, space.rmatvec,
                                           first_panel, DEFAULT_POWER_ITERS)
            if warm_start == "sketch" or fused_zbuild or block_size > 1:
                if fused_zbuild:
                    first_product = space.wrap_matvec_out(ZV1)
                left, S = solve_oracle_block(
                    space.matvec, space.rmatvec, space.dim_u, Z.shape[1],
                    K_n, niter, block_size, key, axis=space.axis,
                    first_panel=first_panel, first_product=first_product)
            else:
                left, S = solve_oracle(space.matvec, space.rmatvec,
                                       space.dim_u, Z.shape[1], K_n, niter,
                                       key, axis=space.axis)
            if keep_z:
                return space.finalize(left), S, Z[None]
            return space.finalize(left), S

    return _named(fn, f"hooi_step_m{ms['mode']}_{backend}")


def make_core_step_fn(ms: dict, backend: str, core_dims: Sequence[int]):
    """The core from the Z a mode step kept: ``G₍n₎ = U_nᵀ Z_n`` folded to
    ``core_dims`` (paper Fig. 2, last step), for ``shard_map`` over the
    'ranks' axis like the mode step of ``ms``.

    ``fn(Z, row_gid, row_owned, bnd_slot, own_bnd_slot, own_bnd_off,
    row_perm, factor)``: the device's Z from ``make_mode_step_fn``
    (``keep_z``), the step's five row arrays, ``row_perm`` (original row ->
    relabelled row) and the mode's factor in original row order, the one
    the sweep holds after the step (an objective's ``refine_factor``
    included). The factor is laid out over the relabelled rows and taken
    through the step's own ``OracleSpace``: ``rmatvec`` of the K-wide
    panel, summed over ranks as the oracle's products are, so no rank
    folds the elements and no ``(elements, ∏K)`` temporary exists. Runs
    under the scope ``core``.
    """
    mode = ms["mode"]
    dims = tuple(int(k) for k in core_dims)

    @f32_matmuls
    def fn(Z, row_gid, row_owned, bnd_slot, own_bnd_slot, own_bnd_off,
           row_perm, factor):
        (Z, row_gid, row_owned, bnd_slot, own_bnd_slot, own_bnd_off) = (
            x[0] for x in (Z, row_gid, row_owned, bnd_slot, own_bnd_slot,
                           own_bnd_off))
        arrs = dict(row_gid=row_gid, row_owned=row_owned, bnd_slot=bnd_slot,
                    own_bnd_slot=own_bnd_slot, own_bnd_off=own_bnd_off)
        with jax.named_scope("core"):
            _, zrmv = z_products(Z)
            space = make_comm_space(backend, ms, arrs, None, zrmv)
            rows = jnp.zeros((ms["P"] * ms["Lp"], factor.shape[1]),
                             factor.dtype).at[row_perm].set(factor)
            gz = space.rmatvec(space.place_rows(rows))  # Z_nᵀ U_n
            return fold(gz.T, mode, dims)

    return _named(fn, f"hooi_core_m{mode}_{backend}")


def make_stochastic_step_fn(mode: int, num_rows: int, K_n: int, niter: int,
                            block_size: int, use_kernel: bool = False,
                            precision: str = "f32"):
    """One minibatch mode step for the stochastic-refine rung.

    Same Z-build → oracle composition as ``local_mode_step``'s sketch path
    — the sampled elements go through the identical ``build_local_z``
    kernel/reference seam, and the carried factor seeds the range-finder
    panel so the solve *refines* the adopted subspace instead of
    rediscovering it — but shaped for ``jax.jit`` with everything static
    closed over. No ``shard_map``: a minibatch is a few thousand elements,
    far below the scale where sharding over host devices pays for its
    collectives, so the rung's device work is a single-device O(batch)
    step by design (matching ``extend_scheme``'s O(batch) host work).

    ``fn(coords, values, factors, key) -> (left, S)``: ``coords`` are the
    sampled elements' *original* coordinates zero-padded to a power of two
    (padding rows carry coord 0 / value 0, contributing nothing to the
    scatter-add Z build), ``factors`` the full carried factors, and the
    returned ``left`` an orthonormal (num_rows, K_n) basis the caller
    blends into the carried factor (``core.stochastic.blend_factor``) and
    hands to ``Objective.refine_factor`` — outside the trace, matching the
    distributed step's refine-after-finalize discipline.
    """

    @f32_matmuls
    def fn(coords, values, factors, key):
        with jax.named_scope("zbuild"):
            Z = build_local_z(coords, values, coords[:, mode], factors, mode,
                              num_rows, use_kernel=use_kernel,
                              sorted_rows=False, precision=precision)
        with jax.named_scope("oracle"):
            matvec, rmatvec = z_products(Z)
            Khat = int(Z.shape[1])
            seed = Z.T @ factors[mode][:, :min(int(block_size), K_n)]
            first_panel = seeded_start_panel(seed, key, Khat, block_size)
            first_panel = power_refine(matvec, rmatvec, first_panel,
                                       DEFAULT_POWER_ITERS)
            U, B = gk_block_bidiag(matvec, rmatvec, num_rows, Khat, niter,
                                   block_size, key, axis=None,
                                   first_panel=first_panel)
            return svd_from_bidiag(U, B, K_n, key, axis=None)

    return _named(fn, f"hooi_stoch_step_m{mode}")


@f32_matmuls
def local_mode_step(
    coords: jnp.ndarray,
    values: jnp.ndarray,
    factors: Sequence[jnp.ndarray],
    mode: int,
    num_rows: int,
    key: jax.Array,
    *,
    k: int | None = None,
    niter: int | None = None,
    use_kernel: bool = False,
    use_fused_oracle: bool = False,
    precision: str = "f32",
    block_size: int = 1,
    fused_zbuild: bool = False,
    warm_start: str = "none",
    objective=None,
    return_z: bool = False,
) -> jnp.ndarray:
    """One single-process mode step (identity partition, local backend).

    Returns the refined factor (num_rows, k); with ``return_z``, ``(factor,
    Z)`` for ``local_core_from_z``. The Z build runs under the
    named scope ``zbuild`` and the solve under ``oracle``, as in the
    distributed steps.

    ``block_size``/``fused_zbuild`` route through the same block driver and
    fused build stage the distributed steps use, with the identity
    partition — so ``hooi`` and ``dist_hooi(P=1)`` stay trajectory-identical
    on every variant. ``block_size`` here is the *effective* (pre-clamped)
    panel width; callers resolve requests via ``effective_block_size``.

    ``objective`` (an ``engine.objective.Objective``) post-processes the
    oracle solve via ``refine_factor(left, S)`` — identity for the standard
    objective, ADMM projection for NN. The distributed path applies the
    same refine after its row-perm restore, so P=1 parity covers every
    objective.

    ``warm_start="sketch"`` routes through the block driver with the
    factor-seeded range-finder panel (``core.sketch``) and — when ``niter``
    is not given — the reduced ``sketch_niter`` refinement budget. The
    current factor seeds the sketch, so the warm start carries across
    sweeps for free. Sketch excludes ``fused_zbuild`` (the panel depends on
    Z, which the fused first product must precede).
    """
    k = int(factors[mode].shape[1]) if k is None else int(k)
    Khat = 1
    for j, f in enumerate(factors):
        if j != mode:
            Khat *= int(f.shape[1])
    block_size = int(block_size)
    if warm_start == "sketch":
        fused_zbuild = False
        # the seeded panel must span the whole previous subspace (idempotent
        # for callers that already widened via sketch_block_size)
        block_size = sketch_block_size(k, num_rows, Khat, block_size)
    blockish = fused_zbuild or block_size > 1 or warm_start == "sketch"
    first_panel = first_product = None
    with jax.named_scope("zbuild"):
        if fused_zbuild:
            first_panel = block_start_panel(key, Khat, block_size)
            Z, first_product = build_local_z_oracle(
                coords, values, coords[:, mode], factors, mode, num_rows,
                first_panel, use_kernel=use_kernel, sorted_rows=False,
                precision=precision)
        else:
            Z = build_local_z(coords, values, coords[:, mode], factors, mode,
                              num_rows, use_kernel=use_kernel,
                              sorted_rows=False, precision=precision)
    if niter is None:
        niter = (sketch_niter(k, num_rows, Khat, block_size)
                 if warm_start == "sketch"
                 else lanczos_niter(k, num_rows, Khat,
                                    block_size if blockish else 1))
    with jax.named_scope("oracle"):
        matvec, rmatvec = z_products(Z, fused=use_fused_oracle)
        if warm_start == "sketch":
            seed = Z.T @ factors[mode][:, :min(block_size, k)]
            first_panel = seeded_start_panel(seed, key, Khat, block_size)
            first_panel = power_refine(matvec, rmatvec, first_panel,
                                       DEFAULT_POWER_ITERS)
        if blockish:
            U, B = gk_block_bidiag(matvec, rmatvec, num_rows, Khat, niter,
                                   block_size, key, axis=None,
                                   first_panel=first_panel,
                                   first_product=first_product)
            left, S = svd_from_bidiag(U, B, k, key, axis=None)
        else:
            res = lanczos_bidiag(matvec, rmatvec, num_rows, Khat, k,
                                 niter=niter, key=key)
            left, S = res.left_vectors, res.singular_values
    if objective is not None:
        left = objective.refine_factor(left, S)
    return (left, Z) if return_z else left


@functools.lru_cache(maxsize=64)
def local_core_step(mode: int, R_pad: int, Lp: int, core_dims: tuple):
    """``make_core_step_fn`` of the ``local`` backend, jitted: the one
    executable that gives the core at P = 1, in ``HooiExecutor.run`` (a
    one-device ``shard_map`` is the identity, so none is wrapped around it)
    and in single-process ``hooi`` (``local_core_from_z``), so that both
    paths compute it alike, to the bit."""
    ms = dict(mode=mode, R_pad=R_pad, Lp=Lp, S_pad=1, P=1)
    return jax.jit(make_core_step_fn(ms, "local", core_dims))


def local_core_from_z(Z: jnp.ndarray, touched, factor: jnp.ndarray,
                      mode: int, core_dims: Sequence[int]) -> jnp.ndarray:
    """Single-process ``hooi``'s core from the last mode's Z: the identity
    partition's ``local_core_step`` on the rows the mode's elements touch
    (``touched``, sorted ascending; the rows the executor's local Z holds
    at P = 1). The local backend reads ``row_gid`` alone of the step's
    row arrays; the others are given at their P = 1 shapes."""
    R, L = len(touched), int(factor.shape[0])
    rows = jnp.asarray(touched, jnp.int32)
    step = local_core_step(mode, R, L, tuple(int(k) for k in core_dims))
    return step(Z[rows][None], rows[None], jnp.ones((1, R), bool),
                jnp.ones((1, R), jnp.int32), jnp.ones((1, 1), jnp.int32),
                jnp.full((1, 1), L, jnp.int32),
                jnp.arange(L, dtype=jnp.int32), factor)
