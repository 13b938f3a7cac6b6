"""Matrix-free Lanczos (Golub–Kahan) bidiagonalization for the SVD step.

The paper's framework performs the SVD of the penultimate matrix Z_(n)
(L_n x K_hat_n) through an *oracle model*: the method only ever asks for the
two products  x_out = Z @ x_in  and  y_out = y_in @ Z.  This file implements
the driver; callers supply the oracle as a pair of closures, which is what
lets the distributed runtime answer queries with local matmuls + collectives
(paper §3 'SVD Component').

This is the repo's ONE Lanczos implementation. ``gk_bidiag`` is the single
GK body; the u-space (left/row space) may be *sharded* over a named mesh
axis, in which case every u-space inner product and the breakdown-restart
key go through that axis (``axis="ranks"`` is what the distributed boundary
backend passes from inside ``shard_map``). With ``axis=None`` the body
reduces to the classic replicated driver. ``svd_from_bidiag`` owns the
shared small-SVD + rank-deficiency completion postlude, space-aware the
same way.

Per the paper (§7.1, following SLEPc), we run ``2*K`` bidiagonalization
iterations for K requested singular vectors, i.e. ``Q_n = 4*K`` oracle
queries. Full (two-pass CGS) reorthogonalization keeps float32 stable.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

__all__ = ["LanczosResult", "lanczos_bidiag", "svd_via_lanczos",
           "gk_bidiag", "gk_block_bidiag", "svd_from_bidiag",
           "lanczos_niter", "effective_block_size", "block_start_panel"]

_EPS = 1e-30


class LanczosResult(NamedTuple):
    left_vectors: jnp.ndarray  # (nrows, k) leading left singular vectors
    singular_values: jnp.ndarray  # (k,)
    n_queries: int  # oracle queries consumed (Q_n in the paper)


def lanczos_niter(k: int, nrows: int, ncols: int, block_size: int = 1) -> int:
    """The paper/SLEPc iteration count, clamped to the operator's rank cap.

    Shared by the local driver and the distributed mode steps so both sides
    of the engine issue the same number of oracle queries (a precondition
    for their trajectories to coincide at P=1).

    With ``block_size = s > 1`` the count is in *block* iterations: each
    iteration services ``s`` Krylov directions per oracle pass, so the
    vector-iteration budget shrinks to ``ceil(base / s)`` blocks (the last
    block may overshoot the rank cap; breakdown restarts absorb the tail).
    """
    base = int(min(2 * k, nrows, ncols))
    if block_size <= 1:
        return base
    s = min(int(block_size), max(base, 1))
    return -(-base // s)


def effective_block_size(
    k: int, nrows: int, ncols: int, block_size: int
) -> int:
    """Clamp a requested panel width to the operator's vector-iteration
    budget, so a tail panel never exceeds the Krylov directions available
    (``s <= min(2k, nrows, ncols) <= ncols`` keeps the start panel
    column-independent)."""
    base = lanczos_niter(k, nrows, ncols)
    return max(1, min(int(block_size), base))


def block_start_panel(key: jax.Array, ncols: int, block_size: int) -> jnp.ndarray:
    """Deterministic orthonormal start panel V_1 (ncols, s).

    Derived from ``fold_in(key, 3)`` — the same stream the vector driver
    uses for v0 — so the fused Z-build stage and the block driver agree on
    the first panel without communicating.
    """
    g = jax.random.normal(
        jax.random.fold_in(key, 3), (ncols, block_size), jnp.float32
    )
    q, _ = jnp.linalg.qr(g)
    return q


def _space_reduce(axis: str | None) -> Callable[[jnp.ndarray], jnp.ndarray]:
    if axis is None:
        return lambda x: x

    def reduce(x):  # u-space inner products across the mesh
        with jax.named_scope("comm"):
            return jax.lax.psum(x, axis)

    return reduce


def gk_bidiag(
    matvec: Callable[[jnp.ndarray], jnp.ndarray],
    rmatvec: Callable[[jnp.ndarray], jnp.ndarray],
    dim_u: int,
    ncols: int,
    niter: int,
    key: jax.Array,
    axis: str | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The GK bidiagonalization body — the repo's one Lanczos sweep.

    ``dim_u`` is the (per-device, when ``axis`` is set) left-space dimension.
    With ``axis`` given, u-space inner products are ``psum`` over that mesh
    axis and each device draws distinct breakdown-restart directions (the
    concatenation over devices is the global restart vector). The v-space
    (K_hat) is always replicated. Returns ``(U, B)`` with ``B`` upper
    bidiagonal: ``Z V = U B``.
    """
    _ps = _space_reduce(axis)
    dtype = jnp.float32
    V = jnp.zeros((ncols, niter), dtype)  # right Lanczos vectors
    U = jnp.zeros((dim_u, niter), dtype)  # left Lanczos vectors
    alphas = jnp.zeros((niter,), dtype)
    betas = jnp.zeros((niter,), dtype)  # betas[i] couples step i -> i+1

    ku = jax.random.fold_in(key, 17)
    if axis is not None:  # per-device distinct restart directions
        ku = jax.random.fold_in(ku, jax.lax.axis_index(axis))
    kv = jax.random.fold_in(key, 29)
    r_u = jax.random.normal(ku, (dim_u, niter), dtype)  # breakdown restarts
    r_v = jax.random.normal(kv, (ncols, niter), dtype)

    v0 = jax.random.normal(jax.random.fold_in(key, 3), (ncols,), dtype)
    v0 = v0 / (jnp.linalg.norm(v0) + _EPS)

    def u_reorth(u, basis):
        # CGS2 ("twice is enough"); zero columns of the preallocated basis
        # contribute nothing, so a full static-shaped matmul is safe
        for _ in range(2):
            u = u - basis @ _ps(basis.T @ u)
        return u

    def v_reorth(w, basis):
        for _ in range(2):
            w = w - basis @ (basis.T @ w)
        return w

    def body(i, carry):
        U, V, alphas, betas, v, u_prev, beta_prev, scale = carry
        V = V.at[:, i].set(v)
        u = matvec(v) - beta_prev * u_prev
        u = u_reorth(u, U)
        alpha = jnp.sqrt(_ps(jnp.sum(u * u)))
        scale = jnp.maximum(scale, alpha)
        # Lucky breakdown: restart with a fresh direction, record alpha = 0
        # so the restart never mixes into the computed singular vectors.
        ok = alpha > 1e-6 * scale
        u_new = u_reorth(r_u[:, i], U)
        u_new = u_new / (jnp.sqrt(_ps(jnp.sum(u_new * u_new))) + _EPS)
        u = jnp.where(ok, u / (alpha + _EPS), u_new)
        alpha = jnp.where(ok, alpha, 0.0)
        U = U.at[:, i].set(u)
        alphas = alphas.at[i].set(alpha)

        w = rmatvec(u) - alpha * v
        w = v_reorth(w, V)
        beta = jnp.linalg.norm(w)
        scale = jnp.maximum(scale, beta)
        ok_b = beta > 1e-6 * scale
        v_new = v_reorth(r_v[:, i], V)
        v_new = v_new / (jnp.linalg.norm(v_new) + _EPS)
        v = jnp.where(ok_b, w / (beta + _EPS), v_new)
        beta = jnp.where(ok_b, beta, 0.0)
        betas = betas.at[i].set(beta)
        return (U, V, alphas, betas, v, u, beta, scale)

    carry = (U, V, alphas, betas, v0, jnp.zeros((dim_u,), dtype),
             jnp.array(0.0, dtype), jnp.array(_EPS, dtype))
    U, V, alphas, betas, *_ = jax.lax.fori_loop(0, niter, body, carry)

    # Z V = U B with B *upper* bidiagonal: alphas on the diagonal, betas on
    # the superdiagonal (Z v_{i+1} = beta_i u_i + alpha_{i+1} u_{i+1}).
    B = jnp.diag(alphas) + jnp.diag(betas[:-1], k=1)
    return U, B


def gk_block_bidiag(
    matvec: Callable[[jnp.ndarray], jnp.ndarray],
    rmatvec: Callable[[jnp.ndarray], jnp.ndarray],
    dim_u: int,
    ncols: int,
    niter: int,
    block_size: int,
    key: jax.Array,
    axis: str | None = None,
    first_panel: jnp.ndarray | None = None,
    first_product: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Block (s-step) GK bidiagonalization: ``Z V = U B`` with B banded.

    ``niter`` counts *block* iterations; matvec/rmatvec consume and produce
    ``(., s)`` panels, so each oracle pass over Z services ``s`` Krylov
    directions. The returned ``U`` is ``(dim_u, niter*s)`` and ``B`` is the
    block upper bidiagonal ``(niter*s, niter*s)`` matrix with the panel-QR
    triangular factors ``A_i`` on the diagonal blocks and ``B_{i-1}^T`` on
    the superdiagonal blocks — ``svd_from_bidiag`` consumes it unchanged.

    ``first_panel``/``first_product`` let an upstream stage hand over the
    start panel ``V_1`` (any orthonormal ``(ncols, s)`` panel, replicated
    across devices) and optionally the already-computed product
    ``Z @ V_1``. Two producers use the seam: the fused Z-build stage passes
    exactly ``block_start_panel(key, ncols, block_size)`` (the default, so
    resumed and cold drivers walk the same Krylov space), and the sketched
    warm start (``core/sketch.py``) passes a randomized range-finder panel
    seeded by the previous factors, so the driver only *refines* an
    already-good subspace. Space-awareness matches ``gk_bidiag``: with
    ``axis`` set, the u-space is sharded and all u inner products psum over
    the mesh axis.
    """
    _ps = _space_reduce(axis)
    dtype = jnp.float32
    s = int(block_size)
    m = int(niter)
    total = m * s

    ku = jax.random.fold_in(key, 17)
    if axis is not None:  # per-device distinct restart directions
        ku = jax.random.fold_in(ku, jax.lax.axis_index(axis))
    kv = jax.random.fold_in(key, 29)
    r_u = jax.random.normal(ku, (dim_u, total), dtype)  # breakdown restarts
    r_v = jax.random.normal(kv, (ncols, total), dtype)

    if first_panel is None:
        first_panel = block_start_panel(key, ncols, s)

    U = jnp.zeros((dim_u, total), dtype)
    V = jnp.zeros((ncols, total), dtype)
    B = jnp.zeros((total, total), dtype)

    def panel_reorth(W, basis, reduce_fn):
        # CGS2 against the full preallocated basis; zero columns are inert
        for _ in range(2):
            W = W - basis @ reduce_fn(basis.T @ W)
        return W

    def panel_qr(W, basis, restarts, reduce_fn, scale):
        """Column-MGS QR of the panel with per-column breakdown restarts.

        Restart columns get a fresh direction orthogonal to ``basis`` and
        the panel built so far, with a zero diagonal R entry so they never
        mix into the computed singular vectors (same contract as the vector
        driver's lucky-breakdown handling).
        """
        cols = []
        R = jnp.zeros((s, s), dtype)
        for j in range(s):
            w = W[:, j]
            for _pass in range(2):  # MGS twice within the panel
                for jj in range(j):
                    r = reduce_fn(jnp.sum(cols[jj] * w))
                    w = w - r * cols[jj]
                    R = R.at[jj, j].add(r)
            nrm = jnp.sqrt(reduce_fn(jnp.sum(w * w)))
            scale = jnp.maximum(scale, nrm)
            ok = nrm > 1e-6 * scale
            c = restarts[:, j]
            for _pass in range(2):
                c = c - basis @ reduce_fn(basis.T @ c)
                for jj in range(j):
                    c = c - reduce_fn(jnp.sum(cols[jj] * c)) * cols[jj]
            c = c / (jnp.sqrt(reduce_fn(jnp.sum(c * c))) + _EPS)
            q = jnp.where(ok, w / (nrm + _EPS), c)
            R = R.at[j, j].set(jnp.where(ok, nrm, 0.0))
            cols.append(q)
        return jnp.stack(cols, axis=1), R, scale

    _id = lambda x: x  # noqa: E731 — v-space is replicated
    Vi = first_panel
    Uprev = jnp.zeros((dim_u, s), dtype)
    Bprev = jnp.zeros((s, s), dtype)
    scale = jnp.array(_EPS, dtype)
    for i in range(m):
        V = jax.lax.dynamic_update_slice(V, Vi, (0, i * s))
        # Z V_i = U_{i-1} B_{i-1}^T + U_i A_i
        ZV = first_product if (i == 0 and first_product is not None) \
            else matvec(Vi)
        W = ZV - Uprev @ Bprev.T
        W = panel_reorth(W, U, _ps)
        Ui, Ai, scale = panel_qr(W, U, r_u[:, i * s:(i + 1) * s], _ps, scale)
        U = jax.lax.dynamic_update_slice(U, Ui, (0, i * s))
        B = jax.lax.dynamic_update_slice(B, Ai, (i * s, i * s))

        # Z^T U_i = V_i A_i^T + V_{i+1} B_i
        G = rmatvec(Ui) - Vi @ Ai.T
        G = panel_reorth(G, V, _id)
        Vn, Bi, scale = panel_qr(G, V, r_v[:, i * s:(i + 1) * s], _id, scale)
        if i + 1 < m:
            B = jax.lax.dynamic_update_slice(B, Bi.T, (i * s, (i + 1) * s))
        Uprev, Bprev, Vi = Ui, Bi, Vn
    return U, B


def _complete_columns(
    left: jnp.ndarray, m: int, key: jax.Array, axis: str | None
) -> jnp.ndarray:
    """Append ``m`` orthonormal columns to ``left`` (rank-deficient edge).

    Column-by-column CGS2 with space-aware inner products, so the completed
    basis is globally orthonormal even when the rows are sharded.
    """
    _ps = _space_reduce(axis)
    key = jax.random.fold_in(key, 1)
    if axis is not None:
        key = jax.random.fold_in(key, jax.lax.axis_index(axis))
    extra = jax.random.normal(key, (left.shape[0], m), left.dtype)
    basis = left
    for j in range(m):
        c = extra[:, j]
        for _ in range(2):
            c = c - basis @ _ps(basis.T @ c)
        c = c / (jnp.sqrt(_ps(jnp.sum(c * c))) + _EPS)
        basis = jnp.concatenate([basis, c[:, None]], axis=1)
    return basis


def svd_from_bidiag(
    U: jnp.ndarray,
    B: jnp.ndarray,
    k: int,
    key: jax.Array,
    axis: str | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Left singular vectors from the GK output: SVD of the small bidiagonal
    matrix, projected through U, completed to ``k`` orthonormal columns when
    the iteration count could not reach ``k`` (rank-deficient operators)."""
    P, S, _ = jnp.linalg.svd(B, full_matrices=False)
    niter = int(B.shape[0])
    kk = min(k, niter)
    left = U @ P[:, :kk]
    if kk < k:
        left = _complete_columns(left, k - kk, key, axis)
        S = jnp.concatenate([S[:kk], jnp.zeros((k - kk,), S.dtype)])
    return left, S[:k]


@partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _lanczos_impl(matvec, rmatvec, nrows, ncols, niter, key):
    """Jitted replicated instantiation of the shared body."""
    return gk_bidiag(matvec, rmatvec, nrows, ncols, niter, key, axis=None)


def lanczos_bidiag(
    matvec: Callable[[jnp.ndarray], jnp.ndarray],
    rmatvec: Callable[[jnp.ndarray], jnp.ndarray],
    nrows: int,
    ncols: int,
    k: int,
    niter: int | None = None,
    key: jax.Array | None = None,
) -> LanczosResult:
    """Leading-k left singular vectors of the oracle matrix Z.

    matvec : x (ncols,) -> Z @ x (nrows,)
    rmatvec: u (nrows,) -> Z.T @ u (ncols,)
    """
    if key is None:
        key = jax.random.PRNGKey(0)
    if niter is None:
        niter = lanczos_niter(k, nrows, ncols)
    else:
        niter = int(min(niter, nrows, ncols))
        niter = max(niter, min(k, nrows, ncols))
    U, B = _lanczos_impl(matvec, rmatvec, nrows, ncols, niter, key)
    left, S = svd_from_bidiag(U, B, k, key, axis=None)
    return LanczosResult(left, S, n_queries=2 * niter)


def svd_via_lanczos(Z: jnp.ndarray, k: int, key: jax.Array | None = None,
                    niter: int | None = None) -> LanczosResult:
    """Convenience wrapper: explicit (single-rank) Z."""
    return lanczos_bidiag(
        lambda x: Z @ x,
        lambda u: Z.T @ u,
        Z.shape[0],
        Z.shape[1],
        k,
        niter=niter,
        key=key,
    )
