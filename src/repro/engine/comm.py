"""Comm backends: how a mode step's oracle answers cross the device mesh.

The paper's framing (shared with the dense companion paper,
arXiv:1707.05594) is that ONE compute schedule runs under different data
distributions — only the placement and the collectives change. This module
makes that the literal architecture: a backend wraps the per-device Z
products (``engine.oracle.z_products``) into the global oracle the shared
Lanczos body consumes, and owns nothing else.

Three backends, selected per mode from the plan's partition metrics
(``resolve_backend``):

* ``local`` — P = 1: no collectives at all. The single-process HOOI in
  ``repro.core.hooi`` is this backend applied to the identity partition,
  and ``dist_hooi(P=1)`` resolves here too — single-process/distributed
  parity is a property of the architecture, not a differential test.

* ``psum`` — the paper's framework mapped 1:1 onto SPMD (the historical
  ``baseline`` path): the oracle answer lives replicated in the full padded
  row space L_sent = P*Lp, aggregated with a ``psum`` over the full row
  vector (the all-reduce analogue of the MPI owner reduction). Comm per
  query: O(L) per device; the u-space is replicated (``axis=None``).

* ``boundary`` — the beyond-paper TPU-native path (the historical
  ``liteopt``): rows are relabelled so each device owns a contiguous block;
  the oracle answer is produced *sharded* and the only cross-device traffic
  is the tiny boundary vector of split-slice rows — size R_sum - L <= P for
  Lite (Theorem 6.1.2). Comm per query: O(S_pad) ~ O(P); the u-space is
  sharded (``axis="ranks"``), cutting reorthogonalization memory and FLOPs
  by P.

All backends assume they run inside ``shard_map`` over the ``"ranks"`` axis
(``local`` merely never issues a collective, so its 1-device mesh is
degenerate by construction). Collectives and the boundary exchange run
under the named scope ``comm``, nested in the stage that issues them.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

__all__ = ["OracleSpace", "make_comm_space", "resolve_backend",
           "cheaper_backend", "backend_comm_bytes", "COMM_BACKENDS",
           "BACKEND_BYTES_KEY", "AXIS"]

AXIS = "ranks"  # the one mesh axis every distributed step runs over

COMM_BACKENDS = ("local", "psum", "boundary")

# historical path names -> backend families (P=1 always resolves to local)
PATH_BACKENDS = {"baseline": "psum", "liteopt": "boundary"}

# which comm_model entry a backend's collectives move — the single source
# of truth for plan costing (repro.core.plan) and calibration accounting
# (repro.distributed.executor)
BACKEND_BYTES_KEY = {"psum": "baseline_bytes", "boundary": "liteopt_bytes"}


def backend_comm_bytes(backend: str, comm: dict) -> float:
    """Collective bytes one mode moves under ``backend`` (local: none)."""
    if backend == "local":
        return 0.0
    return float(comm[BACKEND_BYTES_KEY[backend]])


def cheaper_backend(comm: dict, model) -> str:
    """The modeled-cheaper of psum/boundary for one mode's comm model.

    THE auto selection rule — plan costing, run-time backend resolution,
    and calibration accounting all call this one function, so calibrated
    per-backend bandwidths shift every consumer together.
    """
    return ("psum"
            if model.comm_seconds(comm["baseline_bytes"], "psum")
            < model.comm_seconds(comm["liteopt_bytes"], "boundary")
            else "boundary")


@dataclasses.dataclass
class OracleSpace:
    """What a comm backend hands the shared Lanczos body.

    All closures are panel-polymorphic: ``x`` may be ``(K_hat,)`` or a
    ``(K_hat, s)`` panel (block Lanczos), and u-space values broadcast the
    same way. ``wrap_matvec_out`` is the backend's placement step alone —
    ``matvec = wrap_matvec_out ∘ zmv`` — exposed so a fused Z-build stage
    that already holds the local product ``Z_local @ V_1`` can lift it into
    the global oracle space without a second pass over Z. ``place_rows``
    takes a value of every relabelled row, ``(P·Lp, ...)`` on every
    device, to this device's u-space, so that ``rmatvec ∘ place_rows``
    gives ``Zᵀ U`` of a whole factor (the core from the last mode's Z).
    """

    matvec: Callable  # x (K_hat,)|(K_hat, s) -> u-space vector/panel
    rmatvec: Callable  # u (dim_u,)|(dim_u, s) -> (K_hat, ...) replicated
    dim_u: int  # per-device u-space dimension
    axis: str | None  # mesh axis the u-space is sharded over (None: replicated)
    finalize: Callable  # left vectors (dim_u, k) -> per-device factor shard
    wrap_matvec_out: Callable = None  # local Z product -> u-space placement
    place_rows: Callable = None  # (P·Lp, ...) replicated -> u-space


def resolve_backend(path: str, P: int, comm: dict | None = None) -> str:
    """Backend for one mode step, from the plan's partition metrics.

    ``path`` is ``"baseline"``/``"liteopt"`` (forced family), ``"auto"``
    (pick the cheaper of psum/boundary from the mode's analytic comm model
    ``comm``), or already a backend name. P = 1 always resolves to
    ``local`` — no collectives exist worth modeling.
    """
    if P == 1:
        return "local"
    if path in COMM_BACKENDS:
        return path
    if path == "auto":
        if comm is None:
            return "boundary"
        from repro.core.calibrate import current_cost_model

        return cheaper_backend(comm, current_cost_model())
    try:
        return PATH_BACKENDS[path]
    except KeyError:
        raise ValueError(f"unknown path/backend {path!r}") from None


def _local_space(ms: dict, arrs: dict, zmv, zrmv) -> OracleSpace:
    Lp = ms["Lp"]
    row_gid = arrs["row_gid"]

    def wrap(local):
        # P = 1: every real row is owned; padding rows carry the
        # out-of-range gid sentinel and drop out of the scatter
        return jnp.zeros((Lp,) + local.shape[1:], local.dtype).at[
            row_gid].add(local, mode="drop")

    def rmatvec(u):
        return zrmv(u.at[row_gid].get(mode="fill", fill_value=0.0))

    return OracleSpace(lambda x: wrap(zmv(x)), rmatvec, Lp, None,
                       lambda left: left, wrap, lambda rows: rows)


def _psum_space(ms: dict, arrs: dict, zmv, zrmv) -> OracleSpace:
    Lp = ms["Lp"]
    L_sent = ms["P"] * Lp
    row_gid = arrs["row_gid"]
    p = jax.lax.axis_index(AXIS)

    def wrap(local):  # (R_pad, ...) local Z product -> replicated row space
        out = jnp.zeros((L_sent,) + local.shape[1:], local.dtype).at[
            row_gid].add(local, mode="drop")
        with jax.named_scope("comm"):
            return jax.lax.psum(out, AXIS)

    def rmatvec(u):
        y_loc = u.at[row_gid].get(mode="fill", fill_value=0.0)
        part = zrmv(y_loc)
        with jax.named_scope("comm"):
            return jax.lax.psum(part, AXIS)

    def finalize(left):  # (L_sent, k) replicated -> (Lp, k) shard
        return jax.lax.dynamic_slice_in_dim(left, p * Lp, Lp, 0)

    return OracleSpace(lambda x: wrap(zmv(x)), rmatvec, L_sent, None,
                       finalize, wrap, lambda rows: rows)


def _boundary_space(ms: dict, arrs: dict, zmv, zrmv) -> OracleSpace:
    Lp, S_pad = ms["Lp"], ms["S_pad"]
    row_gid, row_owned = arrs["row_gid"], arrs["row_owned"]
    bnd_slot = arrs["bnd_slot"]
    own_bnd_slot, own_bnd_off = arrs["own_bnd_slot"], arrs["own_bnd_off"]
    p = jax.lax.axis_index(AXIS)
    off = row_gid - p * Lp  # owned rows: in [0, Lp); foreign/pad: out of range

    def _bmask(ref):  # row_owned broadcast against vector or panel values
        return row_owned if ref.ndim == 1 else row_owned[:, None]

    def wrap(local):  # (R_pad, ...) local Z product -> owned row shard
        owned_contrib = jnp.where(_bmask(local), local, 0.0)
        shard = jnp.zeros((Lp,) + local.shape[1:], local.dtype).at[
            jnp.where(row_owned, off, Lp)
        ].add(owned_contrib, mode="drop")
        with jax.named_scope("comm"):
            # boundary rows -> tiny global slot vector (size S_pad ~ O(P))
            bvec = jnp.zeros((S_pad,) + local.shape[1:], local.dtype).at[
                bnd_slot].add(local, mode="drop")
            # owned/pad rows have slot S_pad -> dropped
            bvec = jax.lax.psum(bvec, AXIS)
            add = bvec.at[own_bnd_slot].get(mode="fill", fill_value=0.0)
            shard = shard.at[own_bnd_off].add(add, mode="drop")
        return shard  # (Lp, ...) sharded over ranks

    def rmatvec(u_shard):
        with jax.named_scope("comm"):
            # owners publish boundary-row values into the tiny slot vector
            vals = u_shard.at[own_bnd_off].get(mode="fill", fill_value=0.0)
            ybnd = jnp.zeros((S_pad,) + u_shard.shape[1:],
                             u_shard.dtype).at[own_bnd_slot].set(
                                 vals, mode="drop")
            ybnd = jax.lax.psum(ybnd, AXIS)
            y_for = ybnd.at[bnd_slot].get(mode="fill", fill_value=0.0)
        y_own = u_shard.at[off].get(mode="fill", fill_value=0.0)
        y_loc = jnp.where(_bmask(y_own), y_own, y_for)
        part = zrmv(y_loc)
        with jax.named_scope("comm"):
            return jax.lax.psum(part, AXIS)

    def place_rows(rows):  # every relabelled row -> this device's shard
        return jax.lax.dynamic_slice_in_dim(rows, p * Lp, Lp, 0)

    return OracleSpace(lambda x: wrap(zmv(x)), rmatvec, Lp, AXIS,
                       lambda left: left, wrap, place_rows)


_SPACES = {
    "local": _local_space,
    "psum": _psum_space,
    "boundary": _boundary_space,
}


def make_comm_space(backend: str, ms: dict, arrs: dict, zmv, zrmv
                    ) -> OracleSpace:
    """Wrap per-device Z products into the global oracle for ``backend``."""
    if backend == "local" and ms["P"] != 1:
        raise ValueError("local comm backend requires P == 1")
    try:
        make = _SPACES[backend]
    except KeyError:
        raise ValueError(f"unknown comm backend {backend!r}") from None
    return make(ms, arrs, zmv, zrmv)
