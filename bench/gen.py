"""Seeded sparse tensor generator, kept with the benchmark.

A copy of the program's ``repro.data.tensors.synth_tensor`` (Zipf-skewed
mode coordinates, optional hub slices, duplicates merged), kept here so
that a change to the program cannot move the yardstick. It returns plain
numpy arrays; ``tests/test_bench.py`` checks that it reproduces the
program's generator bit for bit.

``cell_tensor`` is what a run uses: the sparsity pattern comes from the
configuration's fixed ``structure_seed``, so every seed runs the same
sizes (the program compiles per padded shape, and a new shape per seed
would put a compile into every run's set-up); the values come from the
run's ``--seed``.
"""

from __future__ import annotations

import numpy as np


def _zipf_coords(rng, L: int, n: int, alpha: float) -> np.ndarray:
    """n samples in [0, L) with a Zipf(alpha)-shaped marginal (alpha=0: uniform)."""
    if alpha <= 0:
        return rng.integers(0, L, size=n)
    ranks = np.arange(1, L + 1, dtype=np.float64)
    w = ranks ** (-alpha)
    cdf = np.cumsum(w)
    cdf /= cdf[-1]
    u = rng.random(n)
    idx = np.searchsorted(cdf, u, side="left")
    perm = rng.permutation(L)
    return perm[np.minimum(idx, L - 1)]


def dedup(coords: np.ndarray, values: np.ndarray, shape) -> tuple:
    """Merge duplicate coordinates (sum values); coordinates come out sorted."""
    flat = np.ravel_multi_index(tuple(coords.T), tuple(shape))
    uniq, inv = np.unique(flat, return_inverse=True)
    vals = np.zeros(len(uniq), dtype=values.dtype)
    np.add.at(vals, inv, values)
    out = np.stack(np.unravel_index(uniq, tuple(shape)), axis=1)
    return out, vals


def synth_coo(shape, nnz: int, alphas, hub_fraction: float = 0.0,
              hub_modes=(), seed: int = 0) -> tuple:
    """(coords, values) exactly as ``synth_tensor`` draws them."""
    rng = np.random.default_rng(seed)
    N = len(shape)
    if isinstance(alphas, (int, float)):
        alphas = tuple(float(alphas) for _ in range(N))
    cols = [_zipf_coords(rng, shape[n], nnz, alphas[n]) for n in range(N)]
    coords = np.stack(cols, axis=1).astype(np.int64)
    if hub_fraction > 0 and hub_modes:
        k = int(nnz * hub_fraction)
        pick = rng.choice(nnz, size=k, replace=False)
        for m in hub_modes:
            coords[pick, m] = rng.integers(0, shape[m])
    values = rng.standard_normal(nnz)
    return dedup(coords, values, shape)


def cell_tensor(cfg: dict, seed: int) -> tuple:
    """(coords, values) of a configuration: fixed pattern, values from seed."""
    coords, _ = synth_coo(tuple(cfg["shape"]), int(cfg["nnz"]),
                          tuple(cfg["alphas"]),
                          hub_fraction=float(cfg.get("hub_fraction", 0.0)),
                          hub_modes=tuple(cfg.get("hub_modes", ())),
                          seed=int(cfg["structure_seed"]))
    values = np.random.default_rng(seed).standard_normal(len(coords))
    return coords, values
