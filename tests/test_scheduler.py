"""StreamScheduler: pipelined multi-tensor serving on one executor.

These run real (small) distributed decompositions on the 8 simulated host
devices from conftest, so they carry the ``slow`` marker like the executor
suite. The contracts under test:

  * device runs happen in submission order and match a direct
    ``HooiExecutor.run`` on the same plan bit-for-bit (the scheduler adds
    pipelining, not math);
  * the streaming refresh ladder — reuse / repartition / reselect — with
    the rerun contract (0 new compilations, 0 new uploads) extended to
    the scheduler path, and distribution-preserving appends keeping the
    selected scheme with 0 new compilations (geometric pads);
  * producer failures surface on the job's future without wedging the
    pipeline.
"""

import numpy as np
import pytest

from repro.core.coo import SparseTensor
from repro.streaming import StreamingTensor

CORE = (2, 2, 2)


@pytest.fixture
def executor():
    from repro.distributed.executor import HooiExecutor

    return HooiExecutor(4)


@pytest.fixture
def scheduler(executor):
    from repro.engine.scheduler import StreamScheduler

    with StreamScheduler(executor, CORE, n_invocations=1,
                         workers=2) as sched:
        yield sched


@pytest.mark.slow
def test_pipeline_preserves_order_and_trajectories(scheduler, executor,
                                                   lowrank_tensor,
                                                   small_tensor):
    futs = [scheduler.submit(lowrank_tensor, name="a", seed=0),
            scheduler.submit(small_tensor, name="b", seed=1)]
    res = scheduler.drain()
    assert [r.name for r in res] == ["a", "b"]
    assert [r.seq for r in res] == [0, 1]
    assert all(r.decision == "plan" for r in res)
    assert futs[0].result() is res[0]
    # the scheduler is pipelining, not changing math: a direct run on the
    # same plan and seed reproduces the fit trajectory exactly
    _, direct = executor.run(lowrank_tensor, CORE, res[0].plan,
                             n_invocations=1, seed=0)
    assert direct.fits == res[0].fits
    st = scheduler.stats()
    assert st["completed"] == 2 and st["failed"] == 0
    assert st["host_s"] > 0 and st["run_s"] > 0 and st["wall_s"] > 0
    assert st["decisions"] == {"plan": 2}


@pytest.mark.slow
def test_streaming_refresh_ladder(scheduler, small_tensor):
    rng = np.random.default_rng(0)
    t = small_tensor
    stream = StreamingTensor.from_tensor(t, name="s")

    first = scheduler.submit(stream, seed=0).result()
    assert first.decision == "plan"
    assert first.stream_version == 1
    assert first.stats.stream_decision == "plan"

    # rerun on the unchanged stream: same plan object, fully cached run
    rerun = scheduler.submit(stream, seed=1).result()
    assert rerun.decision == "reuse"
    assert rerun.plan is first.plan
    assert rerun.stats.step_compilations == 0
    assert rerun.stats.uploads == 0
    assert rerun.stats.upload_cache_hit

    # value updates at existing coordinates preserve the distribution:
    # the scheme survives (no re-selection) and — thanks to geometric
    # pads — so do the compiled shapes
    idx = rng.integers(0, t.nnz, 25)
    stream.append(t.coords[idx], rng.standard_normal(25) * 0.1)
    upd = scheduler.submit(stream, seed=2).result()
    assert upd.decision == "repartition"
    assert upd.stats.stream_decision == "repartition"
    assert upd.plan is not first.plan
    assert upd.plan.candidates is None  # auto selection did NOT rerun
    assert upd.plan.scheme.name == first.plan.scheme.name
    assert upd.stats.step_compilations == 0
    assert upd.stats.uploads == 0  # staged off the hot path by the producer
    assert upd.drift is not None and upd.drift["worst"] <= 1.25

    # rerun after the append: the refreshed plan is now the cached one
    rerun2 = scheduler.submit(stream, seed=3).result()
    assert rerun2.decision == "reuse"
    assert rerun2.plan is upd.plan
    assert rerun2.stats.step_compilations == 0
    assert rerun2.stats.uploads == 0

    # a hub append skews mode loads past the tolerance -> full re-selection
    hub = np.tile(t.coords[0], (4 * t.nnz, 1))
    stream.append(hub, rng.standard_normal(4 * t.nnz))
    skew = scheduler.submit(stream, seed=4).result()
    assert skew.decision == "reselect"
    assert skew.drift["worst"] > 1.25
    assert skew.plan.candidates is not None  # auto selector ran again
    assert skew.stats.stream_drift == skew.drift


@pytest.mark.slow
def test_producer_failure_does_not_wedge_pipeline(scheduler,
                                                  lowrank_tensor):
    bad = SparseTensor(np.zeros((1, 2), dtype=np.int64), np.ones(1),
                       (3, 3))  # 2-D: plan() must reject CORE of length 3
    f_bad = scheduler.submit(bad, name="bad")
    f_ok = scheduler.submit(lowrank_tensor, name="ok", seed=0)
    # drain with return_exceptions keeps the batch's good results: the
    # failure appears in-place instead of aborting the collection
    res = scheduler.drain(return_exceptions=True)
    assert isinstance(res[0], ValueError)
    assert res[1].fits  # pipeline advanced past the failure
    with pytest.raises(ValueError):
        f_bad.result()
    st = scheduler.stats()
    assert st["failed"] == 1 and st["completed"] == 1


@pytest.mark.slow
def test_cancelled_future_does_not_wedge_pipeline(scheduler,
                                                  lowrank_tensor,
                                                  small_tensor):
    """Future.cancel() on a pending job must not kill the worker threads:
    later submissions still complete and the counters stay consistent."""
    f1 = scheduler.submit(lowrank_tensor, name="a", seed=0)
    f2 = scheduler.submit(small_tensor, name="b", seed=1)
    cancelled = f2.cancel()  # may lose the race; both outcomes are legal
    f3 = scheduler.submit(lowrank_tensor, name="c", seed=2)
    assert f1.result().fits
    assert f3.result().fits  # the consumer survived the cancellation
    st = scheduler.stats()
    if cancelled:
        assert f2.cancelled()
        assert st["completed"] == 2 and st["failed"] == 1
    else:
        assert f2.result().fits
        assert st["completed"] == 3 and st["failed"] == 0


@pytest.mark.slow
def test_submit_after_close_raises(executor, lowrank_tensor):
    from repro.engine.scheduler import StreamScheduler

    sched = StreamScheduler(executor, CORE, n_invocations=1)
    sched.close()
    with pytest.raises(RuntimeError, match="closed"):
        sched.submit(lowrank_tensor)
