"""repro.tracing: host spans, per-call tallies, compile counts, and the
device scopes of the compiled steps, on the CPU.

In-process multi-device tests rely on conftest.py setting 8 simulated host
devices before jax initializes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.coo import SparseTensor
from repro.core.plan import plan
from repro.tracing import Tally, hlo_scopes, open_spans, span

HOOI_SPANS = ("hooi.run", "hooi.plan", "hooi.upload", "hooi.sweep",
              "hooi.step", "hooi.wait", "hooi.core", "hooi.fit")
PLAN_CHILDREN = ("plan.fingerprint", "plan.scheme", "plan.partition",
                 "plan.metrics", "plan.cost")


def _tensor(nnz, shape=(60, 50, 40), seed=0):
    r = np.random.default_rng(seed)
    coords = np.stack([r.integers(0, L, nnz) for L in shape], axis=1)
    return SparseTensor(coords, r.standard_normal(nnz), shape).dedup()


@pytest.fixture(scope="module")
def p1_run():
    """One 2-sweep P=1 run on a fresh executor: (executor, stats)."""
    from repro.distributed.executor import HooiExecutor

    ex = HooiExecutor(1)
    t = _tensor(3000)
    _, st = ex.run(t, (3, 3, 3), "lite", n_invocations=2, seed=0)
    return ex, st


# ------------------------------------------------------------ the facility
def test_spans_nest():
    assert open_spans() == ()
    with span("outer") as outer:
        with span("inner", it=0, mode=1) as inner:
            assert open_spans() == ("outer", "inner")
        assert open_spans() == ("outer",)
    assert open_spans() == ()
    assert outer.seconds >= inner.seconds >= 0.0
    assert outer.start <= inner.start


def test_span_closes_on_error():
    with pytest.raises(ValueError), span("failing"):
        raise ValueError("boom")
    assert open_spans() == ()


def test_tally_counts_and_sums():
    with Tally() as outer:
        with span("a") as a1:
            pass
        with Tally() as inner:
            with span("a") as a2:
                with span("b"):
                    pass
    with span("a"):  # no tally open: recorded nowhere
        pass
    assert outer.spans["a"][0] == 2 and inner.spans["a"][0] == 1
    assert outer.spans["a"][1] == pytest.approx(a1.seconds + a2.seconds)
    assert inner.spans["a"][1] == pytest.approx(a2.seconds)
    assert outer.spans["b"][0] == inner.spans["b"][0] == 1
    assert set(outer.spans) == {"a", "b"} and outer.compiles == {}


def test_fresh_jit_charges_one_compile_to_the_innermost_span():
    x = jnp.arange(7.0)
    with Tally() as tl:
        with span("outer"):
            with span("inner"):
                jax.jit(lambda v: v * 3.0 + 1.0)(x).block_until_ready()
            fn = jax.jit(lambda v: v - 2.0)
            fn(x).block_until_ready()
            fn(x).block_until_ready()  # cached: no second compile
    assert tl.compiles == {"inner": 1, "outer": 1}


# ----------------------------------------------------------- the executor
def test_run_fills_every_hooi_span(p1_run):
    _, st = p1_run
    assert set(HOOI_SPANS) <= set(st.spans)
    assert st.spans["hooi.run"][0] == 1
    assert st.spans["hooi.sweep"][0] == 2
    assert st.spans["hooi.step"][0] == 2 * 3  # per sweep and mode
    for name in ("hooi.wait", "hooi.core", "hooi.fit"):
        assert st.spans[name][0] == 2
    assert st.partition_build_s == st.spans["hooi.plan"][1]
    assert st.compiles["hooi.core"] >= 1
    # the three step executables, and the eager ops a fresh process meets
    assert st.compiles["hooi.step"] >= st.step_compilations == 3
    run_s = st.spans["hooi.run"][1]
    assert all(sec <= run_s for _, sec in st.spans.values())


def test_sweep_seconds_are_the_step_and_wait_spans(p1_run):
    ex, st = p1_run
    sweeps = ex.calibration_samples()[-2:]
    assert sum(s["seconds"] for s in sweeps) == pytest.approx(
        st.spans["hooi.step"][1] + st.spans["hooi.wait"][1])


def test_run_on_a_plan_nests_its_build_under_hooi_plan():
    from repro.distributed.executor import HooiExecutor

    t = _tensor(2000, seed=3)
    _, st = HooiExecutor(1).run(t, (2, 2, 2), "lite", n_invocations=1,
                                plan_seed=7)
    assert st.spans["plan"][0] == 1
    assert set(PLAN_CHILDREN) <= set(st.spans)
    assert st.spans["plan"][1] <= st.spans["hooi.plan"][1]


def test_op_scopes_map_zbuild_and_oracle(p1_run):
    ex, _ = p1_run
    scopes = ex.op_scopes()
    modules = {k.split("/", 1)[0] for k in scopes}
    assert modules == {f"jit_hooi_step_m{n}_local" for n in range(3)}
    for scope in ("zbuild", "oracle"):
        assert sum(v == scope for v in scopes.values()) >= 1, scope
    assert set(scopes.values()) <= {"zbuild", "oracle", "comm"}


@pytest.mark.parametrize("path", ["liteopt", "baseline"])
def test_op_scopes_map_comm_across_ranks(path):
    from repro.distributed.executor import HooiExecutor

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 simulated devices (conftest sets XLA_FLAGS)")
    ex = HooiExecutor(2)
    ex.run(_tensor(1500, seed=5), (2, 2, 2), "lite", n_invocations=1,
           path=path)
    scopes = ex.op_scopes()
    backend = {"liteopt": "boundary", "baseline": "psum"}[path]
    assert {k.split("/", 1)[0] for k in scopes} == {
        f"jit_hooi_step_m{n}_{backend}" for n in range(3)}
    assert {"zbuild", "oracle", "comm"} <= set(scopes.values())


def test_op_scopes_compile_nothing(p1_run):
    ex, _ = p1_run
    with Tally() as tl, span("scopes"):
        ex.op_scopes()
    assert tl.compiles == {}


# ------------------------------------------------------------------- plan
def test_plan_fills_build_spans():
    t = _tensor(150000, shape=(400, 300, 200), seed=1)
    plan(t, "lite", 4, core_dims=(4, 4, 4), use_cache=False)  # warm imports
    pl = plan(t, "lite", 4, core_dims=(4, 4, 4), use_cache=False)
    spans = pl.build_spans
    assert set(spans) == {"plan", *PLAN_CHILDREN}
    assert spans["plan"] == [1, pl.build_s]
    children = sum(spans[c][1] for c in PLAN_CHILDREN)
    assert children <= pl.build_s
    assert children == pytest.approx(pl.build_s, rel=0.05)


def test_cached_plan_keeps_its_own_build_spans():
    t = _tensor(2000, seed=2)
    first = plan(t, "lite", 2, core_dims=(2, 2, 2))
    again = plan(t, "lite", 2, core_dims=(2, 2, 2))
    assert again is first
    assert again.build_spans["plan"][1] == again.build_s


# ------------------------------------------------------------ HLO scopes
HLO = """HloModule jit_hooi_step_m0_local, is_scheduled=true

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %sine.1 = f32[8]{0} sine(%param_0), metadata={op_name="jit(f)/zbuild/sin"}
}

ENTRY %main (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0), metadata={op_name="p"}
  %fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/shard_map/zbuild/sin"}
  %copy.4 = f32[8]{0} copy(%fusion.3)
  %all-reduce.5 = f32[8]{0} all-reduce(%copy.4), metadata={op_name="jit(f)/oracle/while/body/comm/psum"}
  ROOT %multiply.6 = f32[8]{0} multiply(%all-reduce.5, %copy.4), metadata={op_name="jit(f)/oracle/mul"}
}
"""


def test_hlo_scopes_take_the_innermost_scope_and_inherit():
    got = hlo_scopes(HLO)
    m = "jit_hooi_step_m0_local/"
    assert got[m + "fusion.3"] == "zbuild"
    assert got[m + "copy.4"] == "zbuild"  # no metadata: its operand's
    assert got[m + "all-reduce.5"] == "comm"
    assert got[m + "multiply.6"] == "oracle"
    assert m + "p" not in got  # no scope anywhere
