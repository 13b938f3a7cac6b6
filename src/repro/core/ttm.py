"""TTM-chain via the per-element Kronecker reformulation (paper §3 + Appendix A).

Conventions (fixed across the whole repo):

* ``unfold(T, n)`` = ``np.moveaxis(T, n, 0).reshape(L_n, -1)`` — columns are
  C-order flattenings of the remaining modes in increasing mode order (largest
  remaining mode varies fastest).
* The matching per-element contribution is therefore
  ``contr_n(e) = val(e) * kron(F_{j1}[l_{j1}], ..., F_{jr}[l_{jr}])`` with
  ``j1 < j2 < ... < jr`` the modes != n and ``np.kron`` semantics (second
  operand fastest).

Everything here is pure jnp (device-agnostic); the Pallas kernels in
``repro.kernels`` implement the same contract for the TPU hot path and are
verified against these functions.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "unfold",
    "fold",
    "dense_ttm",
    "dense_ttm_chain",
    "kron_contributions",
    "fold_elements",
    "ELEMENT_CHUNK",
    "penultimate",
    "penultimate_local",
    "core_from_factors",
]


# --------------------------------------------------------------------- dense
def unfold(T: jnp.ndarray, mode: int) -> jnp.ndarray:
    """Mode-n unfolding, L_n x prod(other)."""
    return jnp.moveaxis(T, mode, 0).reshape(T.shape[mode], -1)


def fold(M: jnp.ndarray, mode: int, shape: Sequence[int]) -> jnp.ndarray:
    """Inverse of :func:`unfold`."""
    shape = list(shape)
    rest = [shape[j] for j in range(len(shape)) if j != mode]
    T = M.reshape([shape[mode]] + rest)
    return jnp.moveaxis(T, 0, mode)


def dense_ttm(T: jnp.ndarray, mode: int, A: jnp.ndarray) -> jnp.ndarray:
    """T x_mode A  (A: K x L_mode). Dense oracle."""
    moved = jnp.moveaxis(T, mode, -1)
    out = jnp.tensordot(moved, A.T, axes=([-1], [0]))
    return jnp.moveaxis(out, -1, mode)


def dense_ttm_chain(
    T: jnp.ndarray, mats: dict[int, jnp.ndarray]
) -> jnp.ndarray:
    """Apply T x_j mats[j] for every j in mats (commutative, paper §2.1)."""
    out = T
    for j in sorted(mats):
        out = dense_ttm(out, j, mats[j])
    return out


# -------------------------------------------------------------------- sparse
def kron_contributions(
    coords: jnp.ndarray,  # (nnz, N) int32
    values: jnp.ndarray,  # (nnz,)
    factors: Sequence[jnp.ndarray],  # F_j: (L_j, K_j)
    mode: int,
) -> jnp.ndarray:
    """contr_n(e) for every element: (nnz, K_hat_n).

    K_hat_n = prod_{j != n} K_j. Batched Kronecker built by successive
    outer products in increasing mode order (keeps C-order convention).
    """
    nnz = values.shape[0]
    cur = values[:, None]  # (nnz, 1)
    for j in range(len(factors)):
        if j == mode:
            continue
        rows = jnp.take(factors[j], coords[:, j], axis=0)  # (nnz, K_j)
        # explicit width (not -1): must also trace for nnz == 0
        cur = (cur[:, :, None] * rows[:, None, :]).reshape(
            nnz, cur.shape[1] * rows.shape[1])
    return cur


# Elements per chunk of ``fold_elements``. A whole-tensor contribution array
# costs ~1 KB per element on the TPU (the (nnz, K) rows pad to 128 lanes)
# and its XLA compile time grows linearly with nnz; a loop over fixed-size
# chunks keeps both independent of nnz.
ELEMENT_CHUNK = 1 << 16


def fold_elements(step, acc, *arrays, chunk: int = ELEMENT_CHUNK):
    """``acc = step(acc, *chunk_arrays)`` over consecutive element chunks.

    ``arrays`` share their leading (element) dimension. Full chunks run in
    a ``fori_loop`` and the remainder in one last call, so a tensor of at
    most ``chunk`` elements is a single ``step`` on the whole arrays.
    """
    nnz = arrays[0].shape[0]
    n_full = nnz // chunk if nnz > chunk else 0
    if n_full:
        def body(i, acc):
            return step(acc, *(jax.lax.dynamic_slice_in_dim(a, i * chunk,
                                                            chunk)
                               for a in arrays))

        acc = jax.lax.fori_loop(0, n_full, body, acc)
        arrays = tuple(a[n_full * chunk:] for a in arrays)
    return step(acc, *arrays)


def penultimate(
    coords: jnp.ndarray,
    values: jnp.ndarray,
    factors: Sequence[jnp.ndarray],
    mode: int,
    num_rows: int,
) -> jnp.ndarray:
    """Global penultimate matrix Z_(n): (L_n, K_hat_n), eq. (1) of the paper."""
    contribs = kron_contributions(coords, values, factors, mode)
    return jax.ops.segment_sum(contribs, coords[:, mode], num_segments=num_rows)


def penultimate_local(
    coords: jnp.ndarray,
    values: jnp.ndarray,
    local_rows: jnp.ndarray,  # (nnz,) dense-renumbered local row ids
    factors: Sequence[jnp.ndarray],
    mode: int,
    num_local_rows: int,
) -> jnp.ndarray:
    """Local copy Z^p with empty rows truncated (paper §3 'TTM Component').

    ``local_rows`` is the dense renumbering of the mode-n coordinates of the
    elements owned by this rank (padding elements must carry value 0 and any
    valid row id).
    """
    contribs = kron_contributions(coords, values, factors, mode)
    return jax.ops.segment_sum(contribs, local_rows, num_segments=num_local_rows)


def core_from_factors(
    coords: jnp.ndarray,
    values: jnp.ndarray,
    factors: Sequence[jnp.ndarray],
) -> jnp.ndarray:
    """Core G = T x_1 F_1^T x_2 ... x_N F_N^T  (paper Fig 2 last step).

    Computed element-wise: G = sum_e val(e) * outer(F_1[l_1], ..., F_N[l_N]),
    in chunks of ``ELEMENT_CHUNK`` elements. Returns a (K_1, ..., K_N)
    tensor.
    """
    dims = tuple(int(f.shape[1]) for f in factors)

    def chunk_sum(acc, coords, values):
        cur = values[:, None]
        for j in range(len(factors)):
            rows = jnp.take(factors[j], coords[:, j], axis=0)
            cur = (cur[:, :, None] * rows[:, None, :]).reshape(
                values.shape[0], cur.shape[1] * rows.shape[1])
        return acc + cur.sum(axis=0)

    acc = jnp.zeros((int(np.prod(dims)),), jnp.result_type(values, *factors))
    return fold_elements(chunk_sum, acc, coords, values).reshape(dims)
