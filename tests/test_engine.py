"""The layered SweepEngine: stage seams, comm backends, P=1 structural
parity, and the cached-plan rerun contract on every backend.

In-process multi-device tests rely on conftest.py setting 8 simulated host
devices before jax initializes.
"""

import dataclasses

import numpy as np
import pytest

from repro.core.calibrate import (
    CostModel,
    _fit_backend_bandwidths,
    set_cost_model,
)
from repro.core.plan import plan, plan_cache_clear


def _need_devices(n):
    import jax

    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} simulated devices (conftest sets XLA_FLAGS)")


@pytest.fixture(autouse=True)
def _restore_cost_model():
    yield
    set_cost_model(None)


# ------------------------------------------------ Z-build seam (chunked)
def test_fold_elements_matches_one_pass():
    import jax.numpy as jnp

    from repro.core.ttm import fold_elements

    x = jnp.arange(23.0)

    def step(acc, v):
        return acc + v.sum()

    assert float(fold_elements(step, 0.0, x, chunk=100)) == float(x.sum())
    assert float(fold_elements(step, 0.0, x, chunk=5)) == float(x.sum())


def test_chunked_reference_z_and_core_match_whole_tensor():
    """Past ``ELEMENT_CHUNK`` elements the reference Z build and the core
    accumulate chunk by chunk; both still equal the whole-tensor sums."""
    import jax.numpy as jnp

    from repro.core.ttm import ELEMENT_CHUNK, core_from_factors, penultimate
    from repro.engine.zbuild import build_local_z

    rng = np.random.default_rng(0)
    shape, nnz = (40, 30, 20), ELEMENT_CHUNK + 1234
    c = np.stack([rng.integers(0, L, nnz) for L in shape], axis=1)
    v = rng.standard_normal(nnz)
    F = [rng.standard_normal((L, 3)) for L in shape]
    coords = jnp.asarray(c, jnp.int32)
    values = jnp.asarray(v, jnp.float32)
    factors = [jnp.asarray(f, jnp.float32) for f in F]

    Z = build_local_z(coords, values, coords[:, 1], factors, 1, shape[1],
                      sorted_rows=False)
    np.testing.assert_allclose(
        Z, penultimate(coords, values, factors, 1, shape[1]),
        rtol=1e-4, atol=1e-3)
    G_ref = np.einsum("e,ea,eb,ec->abc", v, F[0][c[:, 0]], F[1][c[:, 1]],
                      F[2][c[:, 2]])
    np.testing.assert_allclose(core_from_factors(coords, values, factors),
                               G_ref, rtol=1e-4, atol=1e-2)


# --------------------------------------------------- oracle seam (fused)
def test_fused_oracle_matches_svd_via_lanczos():
    """The Pallas oracle_pair kernel, wired through the oracle seam, must
    reproduce svd_via_lanczos on an explicit Z (same key, same driver)."""
    import jax
    import jax.numpy as jnp

    from repro.core.lanczos import lanczos_bidiag, svd_via_lanczos
    from repro.engine.oracle import z_products

    key = jax.random.PRNGKey(7)
    m, n, k = 40, 12, 4
    u, _ = jnp.linalg.qr(jax.random.normal(key, (m, m)))
    v, _ = jnp.linalg.qr(jax.random.normal(jax.random.fold_in(key, 1),
                                           (n, n)))
    s = jnp.concatenate([10.0 * 0.5 ** jnp.arange(k),
                         1e-3 * jnp.ones(n - k)])
    Z = (u[:, :n] * s) @ v
    ref = svd_via_lanczos(Z, k, key=jax.random.fold_in(key, 2))
    mv, rmv = z_products(Z, fused=True)
    fused = lanczos_bidiag(mv, rmv, m, n, k, key=jax.random.fold_in(key, 2))
    np.testing.assert_allclose(fused.singular_values, ref.singular_values,
                               rtol=1e-4)
    Pf = fused.left_vectors @ fused.left_vectors.T
    Pr = ref.left_vectors @ ref.left_vectors.T
    np.testing.assert_allclose(Pf, Pr, atol=1e-3)
    assert fused.n_queries == ref.n_queries


def test_hooi_fused_oracle_flag(small_tensor):
    """use_fused_oracle=None/False is off; True routes the oracle products
    through the kernel and must not change the trajectory."""
    from repro.core.hooi import hooi

    t = small_tensor
    _, fits_plain = hooi(t, (3, 3, 3), n_invocations=2, seed=1)
    _, fits_none = hooi(t, (3, 3, 3), n_invocations=2, seed=1,
                        use_fused_oracle=None)
    _, fits_fused = hooi(t, (3, 3, 3), n_invocations=2, seed=1,
                         use_fused_oracle=True)
    np.testing.assert_allclose(fits_none, fits_plain, atol=0)  # None == off
    np.testing.assert_allclose(fits_fused, fits_plain, atol=1e-4)


@pytest.mark.slow
def test_dist_fused_oracle_differential(lowrank_tensor):
    """The fused oracle is a distinct compiled variant of the distributed
    step and converges to the same decomposition."""
    _need_devices(4)
    from repro.distributed.executor import HooiExecutor

    t = lowrank_tensor
    ex = HooiExecutor(4)
    pl = plan(t, "lite", 4, core_dims=(2, 2, 2))
    _, sf = ex.run(t, (2, 2, 2), pl, n_invocations=2, seed=0,
                   use_fused_oracle=True)
    _, sp = ex.run(t, (2, 2, 2), pl, n_invocations=2, seed=0)
    assert sf.fused_oracle and not sp.fused_oracle
    # fused and plain variants are distinct executables, not cache hits
    assert sf.step_compilations == t.ndim
    assert sp.step_compilations == t.ndim
    # the exactly-rank-2 tensor drives Lanczos through breakdown restarts,
    # where the kernel's blocked f32 accumulation can flip the threshold
    # branch — trajectories agree to restart-level tolerance, and both
    # must nail the exact rank
    np.testing.assert_allclose(sf.fits, sp.fits, atol=1e-3)
    assert sf.fits[-1] > 0.999 and sp.fits[-1] > 0.999


# ------------------------------------------------------- comm backends
def test_resolve_backend_mapping():
    from repro.engine import resolve_backend

    assert resolve_backend("baseline", 4) == "psum"
    assert resolve_backend("liteopt", 4) == "boundary"
    assert resolve_backend("baseline", 1) == "local"
    assert resolve_backend("liteopt", 1) == "local"
    assert resolve_backend("auto", 1) == "local"
    assert resolve_backend("boundary", 8) == "boundary"
    cheap_psum = {"baseline_bytes": 1.0, "liteopt_bytes": 2.0}
    cheap_bnd = {"baseline_bytes": 2.0, "liteopt_bytes": 1.0}
    assert resolve_backend("auto", 4, cheap_psum) == "psum"
    assert resolve_backend("auto", 4, cheap_bnd) == "boundary"
    with pytest.raises(ValueError, match="unknown path"):
        resolve_backend("bogus", 4)


def test_plan_backend_cost_entries(small_tensor):
    """PlanCost scores every comm backend and records the per-mode choice
    — the auto selector compares backends, not just schemes."""
    t = small_tensor
    pb = plan(t, "lite", 8, path="baseline", use_cache=False)
    pl = plan(t, "lite", 8, path="liteopt", use_cache=False)
    pa = plan(t, "lite", 8, path="auto", use_cache=False)
    for p in (pb, pl, pa):
        assert set(p.cost.backend_s) >= {"psum", "boundary"}
        assert all(v >= 0 for v in p.cost.backend_s.values())
    assert pb.cost.mode_backends == ("psum",) * t.ndim
    assert pl.cost.mode_backends == ("boundary",) * t.ndim
    assert pa.cost.path == "auto"
    assert all(b in ("psum", "boundary") for b in pa.cost.mode_backends)
    # auto's comm model is never worse than either forced family
    assert pa.cost.comm_s <= pb.cost.comm_s + 1e-15
    assert pa.cost.comm_s <= pl.cost.comm_s + 1e-15
    # P=1: the collective-free local backend
    p1 = plan(t, "lite", 1, path="liteopt", use_cache=False)
    assert p1.cost.mode_backends == ("local",) * t.ndim
    assert "local" in p1.cost.backend_s


def test_backend_bandwidths_rescore_auto(small_tensor):
    """Calibrated per-backend bandwidths shift the auto backend choice
    through the versioned cost model."""
    t = small_tensor
    plan_cache_clear()
    base = plan(t, "lite", 8, path="auto")
    # boundary moves fewer bytes, so the default model picks it everywhere
    assert base.cost.mode_backends == ("boundary",) * t.ndim
    set_cost_model(CostModel(psum_bandwidth=1e18,  # psum now ~free
                             boundary_bandwidth=1e6))
    recal = plan(t, "lite", 8, path="auto")
    assert recal is not base  # version bump: no stale-cost reuse
    assert recal.cost.mode_backends == ("psum",) * t.ndim
    assert recal.cost.backend_s["psum"] < recal.cost.backend_s["boundary"]


def test_fit_backend_bandwidths_helper():
    """Labelled samples with known per-backend bandwidths are recovered
    exactly from the comm residual."""
    cm = CostModel(flop_rate=2e10, source="fitted:test")
    bw = {"psum": 1e9, "boundary": 5e9}

    def sample(flops, nbytes, backend):
        return {"critical_path_flops": flops, "ttm_flops": flops,
                "svd_flops": 0.0, "comm_bytes": nbytes,
                "seconds": flops / 2e10 + nbytes / bw[backend],
                "comm_backend": backend}

    use = [sample(1e9, 1e8, "psum"), sample(2e9, 3e8, "psum"),
           sample(1e9, 1e8, "boundary"), sample(3e9, 2e8, "boundary")]
    out = _fit_backend_bandwidths(use, cm)
    assert out.psum_bandwidth == pytest.approx(1e9, rel=1e-6)
    assert out.boundary_bandwidth == pytest.approx(5e9, rel=1e-6)
    assert out.source == "fitted:test+backends"
    assert out.bandwidth_for("psum") == out.psum_bandwidth
    assert out.bandwidth_for("boundary") == out.boundary_bandwidth
    assert out.bandwidth_for("local") == out.net_bandwidth
    assert out.comm_seconds(2e9, "psum") == pytest.approx(2.0)
    # unlabelled / mixed samples leave the model untouched
    mixed = dict(sample(1e9, 1e8, "psum"), comm_backend="mixed")
    assert _fit_backend_bandwidths([mixed], cm) is cm


@pytest.mark.slow
def test_executor_samples_carry_backend_label(lowrank_tensor):
    _need_devices(4)
    from repro.distributed.executor import HooiExecutor

    ex = HooiExecutor(4)
    ex.run(lowrank_tensor, (2, 2, 2), "lite", n_invocations=1, seed=0)
    ex.run(lowrank_tensor, (2, 2, 2), "lite", n_invocations=1, seed=0,
           path="baseline")
    labels = {s["comm_backend"] for s in ex.calibration_samples()}
    assert labels == {"boundary", "psum"}


# ------------------------------------------------ P=1 structural parity
@pytest.mark.slow
@pytest.mark.parametrize("path", ["baseline", "liteopt", "auto"])
def test_p1_trajectory_identical_to_single_process(path, lowrank_tensor):
    """Acceptance: dist_hooi(P=1) runs the very same engine stages as
    single-process hooi (local backend, shared loop, shared key schedule),
    so the fit trajectories coincide — parity by architecture, not by
    differential tolerance."""
    from repro.core.hooi import hooi
    from repro.distributed.dist_hooi import dist_hooi

    t = lowrank_tensor
    core = (2, 2, 2)
    _, fits_ref = hooi(t, core, n_invocations=3, seed=0)
    _, st = dist_hooi(t, core, 1, scheme="lite", n_invocations=3,
                      path=path, seed=0)
    assert set(st.comm_backends.values()) == {"local"}
    np.testing.assert_allclose(st.fits, fits_ref, atol=1e-6)
    assert fits_ref[-1] > 0.99


# ---------------------------------------- rerun contract, every backend
@pytest.mark.slow
@pytest.mark.parametrize("P,path,backend", [
    (1, "liteopt", "local"),
    (4, "baseline", "psum"),
    (4, "liteopt", "boundary"),
])
def test_rerun_contract_all_backends(lowrank_tensor, P, path, backend):
    """Acceptance: the cached-plan rerun guarantee (0 new compilations,
    0 new uploads) holds on all three comm backends."""
    _need_devices(P)
    from repro.distributed.executor import HooiExecutor

    t = lowrank_tensor
    ex = HooiExecutor(P)
    pl = plan(t, "lite", P, core_dims=(2, 2, 2), path=path)
    _, s1 = ex.run(t, (2, 2, 2), pl, n_invocations=1, seed=0, path=path)
    assert set(s1.comm_backends.values()) == {backend}
    assert s1.step_compilations == t.ndim
    assert s1.uploads == 9 * t.ndim + 2
    _, s2 = ex.run(t, (2, 2, 2), pl, n_invocations=1, seed=1, path=path)
    assert s2.step_compilations == 0
    assert s2.uploads == 0
    assert s2.upload_cache_hit
    assert s2.step_cache_hits == t.ndim
    assert s2.fits[-1] > 0.99


# ----------------------------- plan persistence meets the fitted model
@pytest.mark.slow
def test_loaded_plan_upload_cache_and_fitted_cost(lowrank_tensor, tmp_path):
    """A save()/load() round-tripped plan must preserve the fitted
    CostModel's scoring (per-phase and per-backend entries included) and
    hit the executor's upload-cache semantics: jit shared via shapes on
    first run, one upload for the new object, then the full 0/0 rerun."""
    _need_devices(4)
    from repro.core.plan import PartitionPlan
    from repro.distributed.executor import HooiExecutor

    set_cost_model(CostModel(
        flop_rate=2e10, net_bandwidth=2e9,
        ttm_flop_rate=8e10, svd_flop_rate=1e10,
        psum_bandwidth=1e9, boundary_bandwidth=6e9,
        source="fitted-phases:test+backends"))
    t = lowrank_tensor
    ex = HooiExecutor(4)
    pl = plan(t, "auto", 4, core_dims=(2, 2, 2))
    assert pl.cost.backend_s is not None
    _, s1 = ex.run(t, (2, 2, 2), pl, n_invocations=1, seed=0)
    assert s1.uploads == 9 * t.ndim + 2

    f = str(tmp_path / "plan.npz")
    pl.save(f)
    loaded = PartitionPlan.load(f, t)
    assert loaded is not pl
    # fitted scoring survives the round-trip bit-exactly
    assert dataclasses.asdict(loaded.cost) == dataclasses.asdict(pl.cost)
    assert loaded.cost.backend_s == pl.cost.backend_s
    assert loaded.cost.mode_backends == pl.cost.mode_backends
    assert loaded.candidates == pl.candidates

    _, s2 = ex.run(t, (2, 2, 2), loaded, n_invocations=1, seed=0)
    assert s2.step_compilations == 0  # identical padded shapes share jit
    assert s2.uploads == 9 * t.ndim + 2  # new object -> one upload
    assert abs(s2.fits[-1] - s1.fits[-1]) < 1e-6
    _, s3 = ex.run(t, (2, 2, 2), loaded, n_invocations=1, seed=1)
    assert s3.step_compilations == 0 and s3.uploads == 0
    assert s3.upload_cache_hit
