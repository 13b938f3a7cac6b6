"""HooiExecutor engine: compiled-step + device-upload reuse across runs,
tensors, and processes (loaded plans); plan_seed threading; wrapper compat.

In-process multi-device tests rely on conftest.py setting 8 simulated host
devices before jax initializes.
"""

import numpy as np
import pytest

from repro.core.coo import SparseTensor
from repro.core.plan import PartitionPlan, plan, plan_cache_clear, \
    plan_cache_stats


def _need_devices(n):
    import jax

    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} simulated devices (conftest sets XLA_FLAGS)")


@pytest.fixture
def executor():
    _need_devices(4)
    from repro.distributed.executor import HooiExecutor

    return HooiExecutor(4)


# ------------------------------------------------------------ cache layers
@pytest.mark.slow
def test_second_run_zero_compilations_zero_uploads(executor, lowrank_tensor):
    """Acceptance: a rerun on a cached plan touches neither jit nor PCIe."""
    t = lowrank_tensor
    pl = plan(t, "lite", 4, core_dims=(2, 2, 2))
    _, s1 = executor.run(t, (2, 2, 2), pl, n_invocations=1, seed=0)
    assert s1.step_compilations == t.ndim  # one XLA compile per mode
    assert s1.uploads == 9 * t.ndim + 2
    assert not s1.upload_cache_hit

    _, s2 = executor.run(t, (2, 2, 2), pl, n_invocations=1, seed=1)
    assert s2.step_compilations == 0
    assert s2.uploads == 0
    assert s2.upload_cache_hit
    assert s2.step_cache_hits == t.ndim
    assert s2.executor["runs"] == 2
    assert s2.fits[-1] > 0.99  # still a correct decomposition


@pytest.mark.slow
def test_rank_arrays_sit_on_their_rank_devices(executor, lowrank_tensor):
    """Each rank's slice of a resident plan is uploaded to that rank's
    device of the mesh, not left on one device for the step to reshard."""
    t = lowrank_tensor
    pl = plan(t, "lite", 4, core_dims=(2, 2, 2))
    assert executor.shard_devices(pl) == {}
    executor.run(t, (2, 2, 2), pl, n_invocations=1, seed=0)
    placed = executor.shard_devices(pl)
    assert sorted(placed) == list(range(t.ndim))
    for devs in placed.values():
        assert devs == list(executor.mesh.devices.flat)


@pytest.mark.slow
def test_identical_padded_shapes_share_compiled_steps(executor,
                                                      lowrank_tensor):
    """Multi-tensor batching: a second tensor whose partitions pad to the
    same shapes reuses every compiled step (only its uploads are new)."""
    t1 = lowrank_tensor
    t2 = SparseTensor(t1.coords.copy(), (t1.values * 1.5).copy(), t1.shape)
    assert t1.fingerprint() != t2.fingerprint()

    _, s1 = executor.run(t1, (2, 2, 2), "lite", n_invocations=1, seed=0)
    assert s1.step_compilations == t1.ndim
    _, s2 = executor.run(t2, (2, 2, 2), "lite", n_invocations=1, seed=0)
    assert s2.step_compilations == 0  # same (path, pads, P, K, niter)
    assert s2.uploads == 9 * t2.ndim + 2  # its own arrays still move once
    # interleave again: both plans stay resident on the one mesh
    _, s3 = executor.run(t1, (2, 2, 2), "lite", n_invocations=1, seed=1)
    assert s3.step_compilations == 0 and s3.uploads == 0
    assert s3.executor["cached_plans"] == 2


@pytest.mark.slow
def test_loaded_plan_reuses_compiled_steps(executor, lowrank_tensor,
                                           tmp_path):
    """Cross-process persistence meets the engine: a save/load round-tripped
    plan skips partitioning AND jit; only its device upload is paid."""
    t = lowrank_tensor
    pl = plan(t, "lite", 4, core_dims=(2, 2, 2))
    _, s1 = executor.run(t, (2, 2, 2), pl, n_invocations=1, seed=0)
    path = str(tmp_path / "plan.npz")
    pl.save(path)
    loaded = PartitionPlan.load(path, t)
    assert loaded is not pl
    _, s2 = executor.run(t, (2, 2, 2), loaded, n_invocations=1, seed=0)
    assert s2.step_compilations == 0  # identical padded shapes -> shared jit
    assert s2.uploads == 9 * t.ndim + 2  # new object -> one upload
    assert abs(s2.fits[-1] - s1.fits[-1]) < 1e-6  # same plan, same run


@pytest.mark.slow
def test_auto_plan_shares_upload_with_winner_candidate(executor,
                                                       lowrank_tensor):
    """An auto plan is a replace-copy of its winning candidate sharing the
    same parts tuple — the device arrays must go up (and stay resident)
    once, not twice."""
    t = lowrank_tensor
    _, s1 = executor.run(t, (2, 2, 2), "auto", n_invocations=1, seed=0)
    assert s1.uploads == 9 * t.ndim + 2
    # the concrete winner scheme resolves to the cached candidate object,
    # whose parts are identical to the auto plan's
    _, s2 = executor.run(t, (2, 2, 2), s1.scheme, n_invocations=1, seed=1)
    assert s2.uploads == 0
    assert s2.upload_cache_hit
    assert s2.step_compilations == 0


def test_compiled_step_cache_is_bounded(monkeypatch):
    """The jitted-executable cache on a long-lived executor is LRU-bounded;
    evicting a step also forgets its shape signatures so a re-created
    callable recounts its compilations."""
    _need_devices(4)
    import repro.distributed.executor as exmod

    ex = exmod.HooiExecutor(4)
    monkeypatch.setattr(exmod, "MAX_COMPILED_STEPS", 2)

    class FakeMP:  # only the static-signature fields are read before a call
        P = 4

        def __init__(self, mode):
            self.mode, self.R_pad, self.Lp, self.S_pad = mode, 8, 3, 1

    k0, s0 = ex._get_step(FakeMP(0), "liteopt", 2)
    ex._seen_shapes[(k0, ("fake",))] = ()  # shape signature -> its args
    k1, _ = ex._get_step(FakeMP(1), "liteopt", 2)
    assert ex._get_step(FakeMP(0), "liteopt", 2)[1] is s0  # hit -> MRU
    k2, _ = ex._get_step(FakeMP(2), "liteopt", 2)  # evicts k1 (LRU), not k0
    assert len(ex._steps) == 2
    assert k0 in ex._steps and k2 in ex._steps and k1 not in ex._steps
    assert ex._get_step(FakeMP(0), "liteopt", 2)[1] is s0  # survived
    assert (k0, ("fake",)) in ex._seen_shapes  # kept with its live step
    ex._get_step(FakeMP(3), "liteopt", 2)  # evicts k2; k0 is MRU
    ex._get_step(FakeMP(4), "liteopt", 2)  # now evicts k0
    assert k0 not in ex._steps
    assert (k0, ("fake",)) not in ex._seen_shapes  # purged with its step


# ------------------------------------------------------------- wrapper API
@pytest.mark.slow
def test_dist_hooi_wrapper_shares_engine(lowrank_tensor):
    """The historical entry point now amortizes across calls automatically."""
    _need_devices(4)
    from repro.distributed.dist_hooi import dist_hooi

    t = lowrank_tensor
    _, s1 = dist_hooi(t, (2, 2, 2), 4, scheme="lite", n_invocations=1, seed=0)
    _, s2 = dist_hooi(t, (2, 2, 2), 4, scheme="lite", n_invocations=1, seed=1)
    assert s2.plan_cache_hit
    assert s2.step_compilations == 0
    assert s2.uploads == 0
    assert s2.upload_cache_hit


@pytest.mark.slow
def test_plan_seed_threads_to_randomized_schemes(lowrank_tensor):
    """dist_hooi used to hardcode seed=0 into build_plan; plan_seed must
    reach the scheme constructor and discriminate the plan cache key."""
    _need_devices(4)
    from repro.distributed.dist_hooi import dist_hooi

    t = lowrank_tensor
    plan_cache_clear()
    _, s1 = dist_hooi(t, (2, 2, 2), 4, scheme="medium", n_invocations=1,
                      seed=0, plan_seed=0)
    assert not s1.plan_cache_hit
    # same plan_seed -> cache hit even though the factor seed changed
    _, s2 = dist_hooi(t, (2, 2, 2), 4, scheme="medium", n_invocations=1,
                      seed=1, plan_seed=0)
    assert s2.plan_cache_hit
    # different plan_seed -> distinct cache key, fresh partitioning
    misses = plan_cache_stats()["misses"]
    _, s3 = dist_hooi(t, (2, 2, 2), 4, scheme="medium", n_invocations=1,
                      seed=1, plan_seed=7)
    assert not s3.plan_cache_hit
    assert plan_cache_stats()["misses"] == misses + 1
    # the two seeds really produced different distributions
    p0 = plan(t, "medium", 4, core_dims=(2, 2, 2), seed=0)
    p7 = plan(t, "medium", 4, core_dims=(2, 2, 2), seed=7)
    assert p0 is not p7
    assert not np.array_equal(p0.scheme.policy(0), p7.scheme.policy(0))


@pytest.mark.slow
def test_executor_rejects_mismatched_plan(executor, lowrank_tensor):
    t = lowrank_tensor
    pl = plan(t, "lite", 2, core_dims=(2, 2, 2))
    with pytest.raises(ValueError, match="P=2"):
        executor.run(t, (2, 2, 2), pl, n_invocations=1)
    pl4 = plan(t, "lite", 4, core_dims=(2, 2, 2))
    # wrong tensor: the upload cache is plan-keyed, silently reusing the
    # original tensor's device arrays would corrupt the decomposition
    other = SparseTensor(t.coords.copy(), (t.values + 1.0).copy(), t.shape)
    with pytest.raises(ValueError, match="built for tensor"):
        executor.run(other, (2, 2, 2), pl4, n_invocations=1)
    with pytest.raises(ValueError, match="core_dims"):
        executor.run(t, (3, 3, 3), pl4, n_invocations=1)
    with pytest.raises(ValueError, match="path"):
        executor.run(t, (2, 2, 2), pl4, n_invocations=1, path="baseline")


# ------------------------------------------------------------- calibration
@pytest.mark.slow
def test_executor_records_calibration_samples(executor, lowrank_tensor):
    from repro.core.calibrate import fit_cost_model

    t = lowrank_tensor
    executor.run(t, (2, 2, 2), "lite", n_invocations=2, seed=0)
    executor.run(t, (2, 2, 2), "lite", n_invocations=1, seed=1)
    samples = executor.calibration_samples()
    assert len(samples) == 3
    assert all(s["seconds"] > 0 for s in samples)
    assert samples[0]["warm"] is False  # first sweep paid jit
    assert all(s["warm"] for s in samples[1:])
    cm = fit_cost_model(samples)
    assert cm.flop_rate > 0 and cm.source.startswith("fitted:")
