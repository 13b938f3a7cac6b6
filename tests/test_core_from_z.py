"""The sweep's core from the last mode's Z (``engine.steps.make_core_step_fn``).

``HooiExecutor.run`` takes each sweep's core as ``G_(N) = U_Nᵀ Z_N``
through the last step's ``OracleSpace``, where it used to fold every
element. Checked against the fold (``core.ttm.core_from_factors``) on
3-, 4- and 5-mode tensors at P = 1 and at P = 2 under both collective
backends; under NN-Tucker, whose ``refine_factor`` changes the factor
after the oracle; and the readers of the metrics this adds.
"""

import jax
import numpy as np
import pytest

from repro.core.coo import SparseTensor
from repro.core.plan import plan
from repro.core.ttm import core_from_factors

SHAPES = {3: ((30, 26, 40), (3, 4, 3)),
          4: ((24, 20, 30, 18), (3, 4, 3, 2)),
          5: ((20, 24, 18, 22, 16), (3, 2, 3, 4, 2))}


def _tensor(N, nnz=1500, seed=0):
    shape, K = SHAPES[N]
    r = np.random.default_rng(seed + N)
    coords = np.stack([r.integers(0, L, nnz) for L in shape], axis=1)
    return SparseTensor(coords, r.standard_normal(nnz), shape).dedup(), K


def _fold(t, factors):
    return np.asarray(core_from_factors(
        jax.numpy.asarray(t.coords, jax.numpy.int32),
        jax.numpy.asarray(t.values, jax.numpy.float32), factors))


@pytest.mark.parametrize("N", [3, 4, 5])
@pytest.mark.parametrize("P,path", [(1, "liteopt"), (2, "liteopt"),
                                    (2, "baseline")],
                         ids=["P1", "P2-boundary", "P2-psum"])
def test_core_from_last_z_equals_the_fold(N, P, path):
    from repro.distributed.executor import HooiExecutor

    if len(jax.devices()) < P:
        pytest.skip(f"needs {P} simulated devices (conftest sets XLA_FLAGS)")
    t, K = _tensor(N)
    ex = HooiExecutor(P)
    pl_ = plan(t, "lite", P, core_dims=K, path=path)
    dec, st = ex.run(t, K, pl_, n_invocations=2, seed=4, path=path)
    want = _fold(t, dec.factors)
    assert dec.core.shape == tuple(K)
    np.testing.assert_allclose(np.asarray(dec.core), want, rtol=1e-4,
                               atol=1e-5 * np.abs(want).max())
    # one compile of the core, in the first call only; none after
    _, st2 = ex.run(t, K, pl_, n_invocations=2, seed=5, path=path)
    assert st2.compiles.get("hooi.core", 0) == 0
    assert st2.step_compilations == 0


def test_core_is_taken_with_the_refined_factor():
    """NN-Tucker changes U_N after the oracle (``refine_factor``): the core
    is the projection with the factor the step returns, Gram-corrected by
    ``finalize_core`` as before."""
    from repro.distributed.executor import HooiExecutor
    from repro.engine.objective import resolve_objective

    t, K = _tensor(4, seed=3)
    obj = resolve_objective("nn")
    pl_ = plan(t, "lite", 1, core_dims=K, path="liteopt", objective=obj)
    dec, _ = HooiExecutor(1).run(t, K, pl_, n_invocations=2, seed=2,
                                 objective=obj)
    want = np.asarray(obj.finalize_core(_fold(t, dec.factors), dec.factors))
    np.testing.assert_allclose(np.asarray(dec.core), want, rtol=1e-4,
                               atol=1e-5 * np.abs(want).max())


def test_no_sweep_folds_the_initial_factors():
    from repro.distributed.executor import HooiExecutor

    t, K = _tensor(3, seed=1)
    dec, _ = HooiExecutor(1).run(t, K, "lite", n_invocations=0, seed=1)
    np.testing.assert_allclose(np.asarray(dec.core), _fold(t, dec.factors),
                               rtol=1e-6, atol=1e-7)


def test_core_ops_name_the_core_executable():
    from repro.distributed.executor import HooiExecutor

    t, K = _tensor(5, seed=2)
    ex = HooiExecutor(1)
    ex.run(t, K, "lite", n_invocations=1, seed=1)
    ops = ex.core_ops()
    assert ops and {o.split("/", 1)[0] for o in ops} == {
        "jit_hooi_core_m4_local"}
    # the mode steps' scopes keep their executables
    assert {k.split("/", 1)[0] for k in ex.op_scopes()} == {
        f"jit_hooi_step_m{n}_local" for n in range(5)}


# ----------------------------------------------------------- the readers
class _Ex:
    def __init__(self, ops):
        self._ops = ops

    def core_ops(self):
        return self._ops


def _ctx(**kw):
    ctx = {"trace": {"op_self_s": {"jit_hooi_core_m2_local/dot.1": 0.03,
                                   "jit_hooi_core_m2_local/fusion": 0.006,
                                   "jit_hooi_step_m0_local/f": 1.0}},
           "setup": {"executor": _Ex({"jit_hooi_core_m2_local/dot.1",
                                      "jit_hooi_core_m2_local/fusion"})},
           "window": {"n_sweeps": 9}, "stats": []}
    ctx.update(kw)
    return ctx


def test_core_ms_reader():
    from bench import run_cell

    read = run_cell.load_reader("core_ms")
    assert read(_ctx()) == pytest.approx(4.0)
    # a program without the core executables: nothing to read
    assert read(_ctx(setup={"executor": object()})) is None
    assert read(_ctx(trace=None)) is None


def test_z_panels_reader():
    from bench import run_cell

    read = run_cell.load_reader("z_panels")

    class St:
        def __init__(self, z_panels):
            self.z_panels = z_panels

    win = {"n_sweeps": 6}
    assert read({"stats": [St(9), St(9)], "window": win}) == 3.0
    assert read({"stats": [St(120), St(120)], "window": win}) == 40.0
    assert read({"stats": [object()], "window": win}) is None
    assert read({"stats": [], "window": win}) is None


def test_zbuild_roofline_counts():
    """The Z build's work from the configuration and the plan: 2·E·K̂ plus
    the leading Kronecker products per mode; Z written once, the COO read,
    a factor row per element and other mode."""
    from bench import counts, zbuild_counts

    class M:
        def __init__(self, E, R):
            self.E_max, self.R_max = E, R

    K = (10, 10, 10, 10, 10)
    per_mode = [M(1000, 50)] * 5
    assert zbuild_counts.flops_per_sweep(K, per_mode) == 5 * 1000 * (
        2 * 10 ** 4 + 10 + 100 + 1000)
    assert zbuild_counts.bytes_per_sweep(K, per_mode) == 5 * (
        50 * 10 ** 4 * 4 + 1000 * (5 * 4 + 4) + 1000 * 40 * 4)
    assert zbuild_counts.flops_per_sweep((10, 10, 10), [M(7, 3)] * 3) == \
        3 * 7 * (200 + 10)
    peak = counts.peaks("TPU v5 lite")
    least, bound = zbuild_counts.least_time_s(K, per_mode, peak)
    assert bound == "bytes" and least == pytest.approx(
        zbuild_counts.bytes_per_sweep(K, per_mode) / peak["hbm_bytes_per_s"])
