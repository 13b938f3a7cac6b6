"""Kernel-backed distributed mode step: differential vs segment_sum,
step-key discrimination, empty/padding-heavy inputs, cache behavior.

The Pallas kron_segsum kernel runs in interpret mode here (CPU); the jnp
segment_sum reference path is the law. In-process multi-device tests rely on
conftest.py setting 8 simulated host devices before jax initializes.
"""

import numpy as np
import pytest

from repro.core.coo import SparseTensor
from repro.core.plan import plan


def _need_devices(n):
    import jax

    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} simulated devices (conftest sets XLA_FLAGS)")


@pytest.fixture
def executor():
    _need_devices(4)
    from repro.distributed.executor import HooiExecutor

    return HooiExecutor(4)


@pytest.fixture
def uneven_tensor():
    """Uneven mode lengths, nnz not divisible by P — every rank list gets
    padding elements, and mode steps see ragged R_pad/E_pad shapes."""
    r = np.random.default_rng(11)
    shape = (13, 7, 9)
    coords = np.stack([r.integers(0, L, 153) for L in shape], axis=1)
    return SparseTensor(coords, r.standard_normal(153), shape).dedup()


# ------------------------------------------------------------ differential
@pytest.mark.slow
@pytest.mark.parametrize("path", ["baseline", "liteopt"])
def test_kernel_matches_reference_lowrank(executor, lowrank_tensor, path):
    """On an exactly rank-(2,2,2) tensor both Z-build variants must converge
    to the same (near-1) fit and the same factor subspaces.

    The fit of each decomposition is evaluated in float64 from its core and
    factors: the fit the sweep reports, ``1 - sqrt(|T|² - |G|²) / |T|`` in
    float32, moves in steps of ~2.6e-4 this close to 1 (a one-ulp change of
    a value moves it by 4e-4), so it only agrees between variants whose
    float32 sums coincide bit for bit."""
    t = lowrank_tensor
    pl = plan(t, "lite", 4, core_dims=(2, 2, 2), path=path)
    dec_k, sk = executor.run(t, (2, 2, 2), pl, n_invocations=2, seed=0,
                             path=path, use_kernel=True)
    dec_r, sr = executor.run(t, (2, 2, 2), pl, n_invocations=2, seed=0,
                             path=path, use_kernel=False)
    assert all(sk.z_kernel.values()), sk.z_kernel
    assert not any(sr.z_kernel.values()), sr.z_kernel
    dense = np.zeros(t.shape)
    dense[tuple(t.coords.T)] = t.values

    def fit64(dec):
        F = [np.asarray(f, np.float64) for f in dec.factors]
        X = np.einsum("abc,ia,jb,kc->ijk",
                      np.asarray(dec.core, np.float64), *F)
        return 1 - np.linalg.norm(dense - X) / np.linalg.norm(dense)

    np.testing.assert_allclose(fit64(dec_k), fit64(dec_r), atol=1e-4)
    assert min(fit64(dec_k), fit64(dec_r)) > 0.99999
    assert sk.fits[-1] > 0.99
    for n in range(t.ndim):  # same column space, sign/rotation-invariant
        Fk, Fr = np.asarray(dec_k.factors[n]), np.asarray(dec_r.factors[n])
        np.testing.assert_allclose(Fk @ Fk.T, Fr @ Fr.T, atol=1e-3)


@pytest.mark.slow
@pytest.mark.parametrize("path", ["baseline", "liteopt"])
@pytest.mark.parametrize("scheme", ["lite", "coarse"])
def test_kernel_matches_reference_uneven_padded(executor, uneven_tensor,
                                                path, scheme):
    """All modes, uneven shapes, padding elements present on most ranks."""
    t = uneven_tensor
    pl = plan(t, scheme, 4, core_dims=(2, 3, 2), path=path)
    # the partitions really contain padding elements (value-0 tail)
    assert any((mp.e_per_rank < mp.E_pad).any() for mp in pl.parts)
    _, sk = executor.run(t, (2, 3, 2), pl, n_invocations=2, seed=3,
                         path=path, use_kernel=True)
    _, sr = executor.run(t, (2, 3, 2), pl, n_invocations=2, seed=3,
                         path=path, use_kernel=False)
    assert all(sk.z_kernel.values())
    np.testing.assert_allclose(sk.fits, sr.fits, atol=1e-3)


@pytest.mark.slow
def test_kernel_path_with_nearly_empty_ranks(executor):
    """nnz < P: most ranks hold only padding elements — the kernel must
    produce the same decomposition as the reference on pure-padding blocks."""
    coords = np.array([[0, 0, 0], [4, 3, 2]])
    t = SparseTensor(coords, np.array([2.0, -3.0]), (5, 4, 3))
    _, sk = executor.run(t, (1, 1, 1), "lite", n_invocations=2, seed=0,
                         use_kernel=True)
    _, sr = executor.run(t, (1, 1, 1), "lite", n_invocations=2, seed=0,
                         use_kernel=False)
    assert all(sk.z_kernel.values())
    np.testing.assert_allclose(sk.fits, sr.fits, atol=1e-5)
    assert np.isfinite(sk.fits).all()


# --------------------------------------------------------------- step keys
def test_kernel_and_fallback_have_distinct_step_keys():
    """Kernel and reference variants of the same shapes must not share a
    compiled executable — the Z build is baked into the trace."""
    _need_devices(4)
    from repro.distributed.executor import HooiExecutor

    ex = HooiExecutor(4)

    class FakeMP:
        P = 4

        def __init__(self):
            self.mode, self.R_pad, self.Lp, self.S_pad = 0, 8, 3, 1

    mp = FakeMP()
    k_kern = ex._step_key(mp, "liteopt", 2, 4, use_kernel=True)
    k_ref = ex._step_key(mp, "liteopt", 2, 4, use_kernel=False)
    assert k_kern != k_ref
    ex._get_step(mp, "liteopt", 2, use_kernel=True)
    ex._get_step(mp, "liteopt", 2, use_kernel=False)
    assert k_kern in ex._steps and k_ref in ex._steps
    assert len(ex._steps) == 2


@pytest.mark.slow
def test_step_cache_holds_both_variants_after_runs(executor, lowrank_tensor):
    t = lowrank_tensor
    pl = plan(t, "lite", 4, core_dims=(2, 2, 2))
    _, s1 = executor.run(t, (2, 2, 2), pl, n_invocations=1, seed=0,
                         use_kernel=True)
    assert s1.step_compilations == t.ndim
    _, s2 = executor.run(t, (2, 2, 2), pl, n_invocations=1, seed=0,
                         use_kernel=False)
    # fallback variants are new executables, not cache hits of the kernel's
    assert s2.step_compilations == t.ndim
    assert s2.executor["cached_steps"] == 2 * t.ndim


# ------------------------------------------------------------ cache reuse
@pytest.mark.slow
def test_second_kernel_run_zero_compilations_zero_uploads(executor,
                                                          lowrank_tensor):
    """Acceptance: the cached-plan rerun guarantee holds on the kernel path
    too — 0 new compilations, 0 new uploads."""
    t = lowrank_tensor
    pl = plan(t, "lite", 4, core_dims=(2, 2, 2))
    _, s1 = executor.run(t, (2, 2, 2), pl, n_invocations=1, seed=0,
                         use_kernel=True)
    assert s1.step_compilations == t.ndim
    assert s1.uploads == 9 * t.ndim + 2
    _, s2 = executor.run(t, (2, 2, 2), pl, n_invocations=1, seed=1,
                         use_kernel=True)
    assert s2.step_compilations == 0
    assert s2.uploads == 0
    assert s2.upload_cache_hit
    assert all(s2.z_kernel.values())


def test_resolve_kernel_vmem_gate():
    """The static choice honors the VMEM gates and the force/pin modes: the
    plain sorted-rows build takes the windowed kernel at any row count, the
    fused build keeps the resident kernel's row gate."""
    _need_devices(4)
    import jax
    from repro.distributed.executor import HooiExecutor
    from repro.kernels import ops

    ex = HooiExecutor(4)

    class FakeMP:
        def __init__(self, R_pad):
            self.mode, self.R_pad = 0, R_pad

    small, huge = FakeMP(64), FakeMP(4_000_000)
    core = (4, 4, 4)
    assert ops.kernel_fits_vmem(64, 4, 4)
    assert not ops.kernel_fits_vmem(4_000_000, 4, 4)
    assert ex.resolve_zbuild(small, core, True) == "windowed"
    assert ex.resolve_zbuild(huge, core, True) == "windowed"  # no row gate
    assert ex.resolve_zbuild(small, core, True, oracle_s=2) == "resident"
    assert ex.resolve_zbuild(huge, core, True, oracle_s=2) == "reference"
    assert ex.resolve_zbuild(small, core, False) == "reference"
    # None: auto — kernel only on a real TPU backend, unless the CI matrix
    # forces the interpret-mode kernel path via REPRO_FORCE_KERNEL=1
    from repro.engine.zbuild import kernel_forced_by_env

    expect = jax.default_backend() == "tpu" or kernel_forced_by_env()
    assert (ex.resolve_zbuild(small, core, None) == "windowed") is expect


# -------------------------------------------------------- phase profiling
@pytest.mark.slow
def test_profile_phases_feeds_per_phase_fit(executor, lowrank_tensor):
    """The zbuild probe + full sweeps give fit_cost_model a full-rank
    per-phase design; the fitted model carries separate TTM/SVD rates."""
    from repro.core.calibrate import fit_cost_model

    t = lowrank_tensor
    executor.run(t, (2, 2, 2), "lite", n_invocations=2, seed=0)
    prof = executor.profile_phases(t, (2, 2, 2), "lite", repeats=2)
    assert prof["ttm_s"] > 0 and prof["full_s"] >= prof["ttm_s"] > 0
    assert set(prof["per_mode"]) == {0, 1, 2}
    samples = executor.calibration_samples()
    assert any(s.get("phase") == "ttm" and s["svd_flops"] == 0
               for s in samples)
    cm = fit_cost_model(samples)
    assert cm.source.startswith("fitted")
    if cm.source.startswith("fitted-phases"):
        rt, rs = cm.phase_rates()
        assert rt > 0 and rs > 0


@pytest.mark.slow
def test_profile_phases_registers_compilations(executor, lowrank_tensor):
    """Regression: profile_phases compiles (and runs) the mode steps, so a
    subsequent run() on the same shapes must report 0 new compilations and
    record its first sweep as warm — the probe must register its shape
    signatures through the same ledger as run()."""
    t = lowrank_tensor
    executor.profile_phases(t, (2, 2, 2), "lite", repeats=1)
    _, s = executor.run(t, (2, 2, 2), "lite", n_invocations=1, seed=0)
    assert s.step_compilations == 0
    assert s.step_cache_hits == t.ndim
    assert executor.calibration_samples()[-1]["warm"] is True
