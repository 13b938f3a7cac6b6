"""Plain float64 reference for the last sweep of a sparse HOOI decomposition.

Independent of the program: numpy and scipy on the host, and JAX only to
draw the documented Lanczos start vectors. A decomposition of ``n`` sweeps
is checked through every mode step of its last sweep. The step of mode
``m`` takes the factors of modes ``0..m-1`` as that sweep left them (the
answer's own) and those of modes ``m+1..N-1`` as the sweep found them
(``before``: the same call's factors after ``n - 1`` sweeps). From those
inputs, per mode:

* ``Z_m`` is rebuilt in float64 from the host's COO in the original row
  order (``Z[i, (a, b, ..)] = sum_e v_e U_j[i_j, a] U_k[i_k, b] ..`` over
  the other modes in increasing order, the later one varying fastest);
* Golub-Kahan-Lanczos runs on it in float64 from the start vector the
  program's step draws (``normal(fold_in(k, 3))`` with ``k =
  fold_in(PRNGKey(seed), 1000 + sweep * N + mode)``) for ``min(2K, rows,
  cols)`` steps with full reorthogonalization; its top-K Ritz vectors,
  turned to the answer's basis (orthogonal Procrustes), are ``U_ref``;
* ``gap``: the answer's projection of ``Z_m`` against ``U_ref^T Z_m``,
  relative in the Frobenius norm. For the modes before the last the
  projection is ``U_m^T Z_m`` with the answer's factor; for the last mode
  it is the answer's core, so the core is checked as well. The gap weighs
  each direction of the subspace by the energy ``Z_m`` has in it;
* ``bf16_gap``: the same gap of the control's step, the reference step
  from the same inputs one precision below the float32 the configurations
  state: each contribution to ``Z_m`` rounded to bfloat16 and summed in
  float32, and every Lanczos product taking bfloat16 operands with float32
  sums, as a matrix unit at bfloat16 precision does. It is the root mean
  square over four draws whose Lanczos roundings lie on shifted grids
  (``CONTROL_SCALES``): on an ill-conditioned step a single draw's gap is
  one rounding's projection on one direction and can fall near zero;
* ``gap / bf16_gap``: the program's error in units of the control's on the
  same step. One step's Lanczos can turn the subspace 100x more than
  another's under the same rounding, in the program and the control
  alike; the quotient takes that conditioning out.

The number compared is ``step_gap_vs_bf16``, the largest quotient over
the modes. The control in the program's place reads about 1.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import ml_dtypes
import numpy as np
import scipy.sparse

# elements of one chunk of the Z build hold at most this many values
_CHUNK_VALUES = 1 << 20
_THREADS = max(1, min(16, os.cpu_count() or 1))
# The control's draws. Each rounds the operands of the control's matrix
# products, times its scale, to bfloat16, so each rounds on a grid of its
# own: scales that are not powers of two give independent rounding errors
# of one size. The first draw is plain bfloat16.
CONTROL_SCALES = (1.0, 2 ** 0.25, 2 ** 0.5, 2 ** 0.75)


def _bf16(x: np.ndarray, scale: float = 1.0) -> np.ndarray:
    """``x`` rounded to bfloat16 on the grid of ``scale``, as float32."""
    x = np.asarray(x, np.float32)
    if scale == 1.0:
        return x.astype(ml_dtypes.bfloat16).astype(np.float32)
    y = (x * np.float32(scale)).astype(ml_dtypes.bfloat16)
    return y.astype(np.float32) / np.float32(scale)


def _z_rows(coords, values, F, others, mode: int, outs):
    """Add the contributions of a run of elements sorted by their ``mode``
    row to ``outs[0]`` (float64) and, rounded to bfloat16, to ``outs[1]``
    (float32) where given: all rows but the run's first and last, which
    other runs may share. Returns those two rows' sums."""
    khat = outs[0].shape[1]
    ends = (int(coords[0, mode]), int(coords[-1, mode]))
    own = {r: [np.zeros(khat, o.dtype) for o in outs] for r in ends}
    chunk = max(1, _CHUNK_VALUES // khat)
    for s in range(0, len(values), chunk):
        c = coords[s:s + chunk]
        n = len(c)
        W = np.asarray(values[s:s + chunk], np.float64)[:, None]
        for j in others:
            W = (W[:, :, None] * F[j][c[:, j]][:, None, :]).reshape(n, -1)
        a, b = int(c[0, mode]), int(c[-1, mode]) + 1
        S = scipy.sparse.csc_matrix(
            (np.ones(n), c[:, mode] - a, np.arange(n + 1)), shape=(b - a, n))
        sums = [S @ W]
        if len(outs) > 1:
            sums.append(S.astype(np.float32) @ _bf16(W))
        lo = a + (a in own)
        hi = b - (b - 1 in own and b - 1 >= lo)
        for k, (out, R) in enumerate(zip(outs, sums)):
            out[lo:hi] += R[lo - a:hi - a]
            for r in {a, b - 1} & own.keys():
                own[r][k] += R[r - a]
    return own


def mode_z(coords: np.ndarray, values: np.ndarray, shape, factors, mode: int,
           *, control: bool = False):
    """``Z`` of ``mode`` in float64, and with ``control`` also the
    control's (each contribution rounded to bfloat16, summed in float32;
    else None).

    ``factors[mode]`` is not read. Threads take runs of about equal length
    of the elements sorted by row; a row that two runs share is summed
    apart and added once the threads are done.
    """
    others = [j for j in range(len(shape)) if j != mode]
    F = {j: np.asarray(factors[j], np.float64) for j in others}
    khat = int(np.prod([F[j].shape[1] for j in others]))
    outs = [np.zeros((int(shape[mode]), khat))]
    if control:
        outs.append(np.zeros(outs[0].shape, np.float32))
    order = np.argsort(coords[:, mode], kind="stable")
    cuts = np.unique(np.linspace(0, len(order), _THREADS + 1).astype(int))
    with ThreadPoolExecutor(_THREADS) as pool:
        owns = list(pool.map(
            lambda a, b: _z_rows(coords[order[a:b]], values[order[a:b]], F,
                                 others, mode, outs),
            cuts[:-1], cuts[1:]))
    for own in owns:
        for r, sums in own.items():
            for out, z in zip(outs, sums):
                out[r] += z
    return outs[0], (outs[1] if control else None)


def gk_lanczos(Z: np.ndarray, v0: np.ndarray, niter: int, k: int,
               bf16_scale: float | None = None):
    """Top-k Ritz values and left vectors of ``Z`` after ``niter`` GK steps,
    with full reorthogonalization, in ``Z``'s precision.

    With ``bf16_scale``, every matrix product takes its operands rounded to
    bfloat16 on that scale's grid and sums in float32, as a matrix unit
    does at bfloat16 precision; ``Z`` is then float32.
    """
    if bf16_scale is None:
        def r(x):
            return x
    else:
        def r(x):
            return _bf16(x, bf16_scale)
        Z = r(Z)
    m, n = Z.shape
    dt = Z.dtype
    # the bases, and their operands as the products take them
    U, Ur = np.zeros((m, niter), dt), np.zeros((m, niter), dt)
    V, Vr = np.zeros((n, niter), dt), np.zeros((n, niter), dt)
    alphas = np.zeros(niter, dt)
    betas = np.zeros(niter, dt)
    v = np.asarray(v0, dt) / np.linalg.norm(np.asarray(v0, dt))
    u_prev = np.zeros(m, dt)
    beta = dt.type(0)
    for i in range(niter):
        V[:, i], Vr[:, i] = v, r(v)
        u = Z @ Vr[:, i] - beta * u_prev
        for _ in range(2):
            u -= Ur[:, :i] @ r(Ur[:, :i].T @ r(u))
        alpha = np.linalg.norm(u)
        if not alpha > 0:
            raise FloatingPointError(f"Lanczos broke down at step {i}")
        u /= alpha
        U[:, i], Ur[:, i] = u, r(u)
        w = Z.T @ Ur[:, i] - alpha * v
        for _ in range(2):
            w -= Vr[:, :i + 1] @ r(Vr[:, :i + 1].T @ r(w))
        beta = np.linalg.norm(w)
        alphas[i], betas[i] = alpha, beta
        v = w / beta if beta > 0 else w
        u_prev = u
    B = np.diag(alphas) + np.diag(betas[:-1], k=1)
    P, S, _ = np.linalg.svd(B)
    return S[:k], Ur @ r(P[:, :k])


def start_vector(seed: int, sweep: int, nmodes: int, mode: int,
                 ncols: int) -> np.ndarray:
    """The Lanczos start vector the program's mode step draws."""
    import jax

    key = jax.random.fold_in(jax.random.PRNGKey(seed),
                             1000 + sweep * nmodes + mode)
    v0 = jax.random.normal(jax.random.fold_in(key, 3), (ncols,))
    return np.asarray(v0, np.float64)


def _ritz(Z, seed, sweep, mode, N, K, bf16_scale=None):
    """Top-K Ritz vectors of ``Z`` from the program's start vector.

    Rows of ``Z`` that are zero stay zero in every Lanczos vector, so the
    iteration runs on the others alone.
    """
    niter = min(2 * K, Z.shape[0], Z.shape[1])
    v0 = start_vector(seed, sweep, N, mode, Z.shape[1])
    rows = np.flatnonzero(np.any(Z != 0, axis=1))
    U = np.zeros((Z.shape[0], K), Z.dtype)
    U[rows] = gk_lanczos(Z[rows], v0, niter, K, bf16_scale)[1]
    return U


def _as_core(P: np.ndarray, K) -> np.ndarray:
    """``U^T Z`` of the last mode, (K_last, K_hat), as the core tensor."""
    return P.T.reshape(tuple(K))


def _gap(Z, U_ref, factor, proj) -> float:
    """``proj`` (the answer's projection of ``Z``) against the reference's,
    ``U_ref`` turned to the basis of ``factor``."""
    W, _, Vt = np.linalg.svd(U_ref.T @ np.asarray(factor, np.float64))
    G_ref = (U_ref @ (W @ Vt)).T @ Z
    return float(np.linalg.norm(proj - G_ref) / np.linalg.norm(G_ref))


def _inputs(before, after, mode):
    return list(after[:mode]) + [None] + list(before[mode + 1:])


def control_answer(coords: np.ndarray, values: np.ndarray, shape, *,
                   seed: int, n_sweeps: int, before) -> dict:
    """The control in the program's place: the last sweep from ``before``,
    each mode step one precision down (contributions to Z rounded to
    bfloat16 and summed in float32, Lanczos with bfloat16 products), and
    the core from its last Z."""
    N = len(shape)
    after = [np.asarray(f, np.float32) for f in before]
    for m in range(N):
        _, Zc = mode_z(coords, values, shape, _inputs(before, after, m), m,
                       control=True)
        after[m] = _ritz(Zc, seed, n_sweeps - 1, m, N, after[m].shape[1],
                         bf16_scale=1.0)
    core = _as_core(after[-1].T @ _bf16(Zc), [f.shape[1] for f in after])
    return {"before": before, "factors": after, "core": core}


def compare(coords: np.ndarray, values: np.ndarray, shape, *, seed: int,
            n_sweeps: int, before, factors, core) -> dict:
    """The readings of one decomposition; ``step_gap_vs_bf16`` is compared.

    ``before``: the call's factors after ``n_sweeps - 1`` sweeps;
    ``factors`` and ``core``: its answer after ``n_sweeps``. A mode's
    control gap is the root mean square over the control's draws: where
    one direction of the subspace is ill-conditioned, a single draw's gap
    is one rounding's projection on it and can fall near zero by chance.
    """
    N = len(shape)
    K = [np.shape(f)[1] for f in factors]
    after = [np.asarray(f, np.float64) for f in factors]
    gaps, ctl_gaps, draw_gaps = [], [], []
    for m in range(N):
        Z, Zc = mode_z(coords, values, shape, _inputs(before, after, m), m,
                       control=True)
        U_ref = _ritz(Z, seed, n_sweeps - 1, m, N, K[m])
        if m < N - 1:
            proj = after[m].T @ Z
        else:  # the answer's core
            proj = np.asarray(core, np.float64).reshape(-1, K[m]).T
        gaps.append(_gap(Z, U_ref, after[m], proj))
        draws = []
        for scale in CONTROL_SCALES:
            U_c = _ritz(Zc, seed, n_sweeps - 1, m, N, K[m], bf16_scale=scale)
            U_c = U_c.astype(np.float64)
            # the last mode's control projection is its core, as built
            P_c = U_c.T @ (Z if m < N - 1 else _bf16(Zc, scale))
            draws.append(_gap(Z, U_ref, U_c, P_c))
        draw_gaps.append(draws)
        ctl_gaps.append(float(np.sqrt(np.mean(np.square(draws)))))
    ratios = [g / c for g, c in zip(gaps, ctl_gaps)]
    return {"step_gap_vs_bf16": max(ratios), "mode_gap_vs_bf16": ratios,
            "mode_gap": gaps, "bf16_mode_gap": ctl_gaps,
            "bf16_draw_gaps": draw_gaps}
