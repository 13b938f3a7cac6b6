"""Tests of the program's spans and scopes as the benchmark reads them.

    PYTHONPATH=src python -m pytest -q bench/tests/test_trace_spans.py

* ``trace_reduce`` gives the recorded one-chip trace the same reduction as
  before, key by key, also through ``trace_spans.reduce_file``;
* the keys ``trace_spans`` adds (``op_self_s``, ``program_spans``,
  ``idle_by_span``), on a small trace recorded on a TPU v5e chip with the
  program's spans (Python tracer off) and on made-up ones;
* each new reader on that trace, and None from each where its input is
  missing (as with a program that has no spans or scopes).
"""

from __future__ import annotations

import json
import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from bench import run_cell, trace_reduce, trace_spans  # noqa: E402

DATA = os.path.join(ROOT, "bench", "testdata")
OLD_TRACE = os.path.join(DATA, "tiny-P1.xplane.pb")
SPAN_TRACE = os.path.join(DATA, "tiny-spans.xplane.pb")
NEW_KEYS = {"op_self_s", "program_spans", "idle_by_span"}
READERS = ("zbuild_ms", "oracle_ms", "comm_ms", "host_gap_ms",
           "core_compiles", "scheme_s", "partition_s")


def _jsonable(r: dict) -> dict:
    return json.loads(json.dumps(dict(r, devices={
        str(k): v for k, v in r["devices"].items()})))


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "tiny-spans.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def span_trace():
    return trace_spans.reduce_file(SPAN_TRACE)


def _ctx(recorded, trace):
    ex = SimpleNamespace(op_scopes=lambda: dict(recorded["op_scopes"]))
    stats = [SimpleNamespace(spans=s, compiles=c)
             for s, c in zip(recorded["spans"], recorded["compiles"])]
    return {"trace": trace, "window": {"n_sweeps": recorded["n_sweeps"]},
            "setup": {"executor": ex}, "stats": stats,
            "plan": SimpleNamespace(build_spans=recorded["build_spans"])}


# ------------------------------------------------ the existing reduction
@pytest.mark.parametrize("key", ["window_s", "n_calls", "busiest", "devices",
                                 "busy_s_mean", "busiest_busy_s",
                                 "busiest_collective_s", "idle_share",
                                 "device_ops", "idle_gaps"])
def test_old_trace_reduces_as_before(key):
    with open(os.path.join(DATA, "tiny-P1.reduce.json")) as f:
        before = json.load(f)
    assert set(before) == {"window_s", "n_calls", "busiest", "devices",
                           "busy_s_mean", "busiest_busy_s",
                           "busiest_collective_s", "idle_share",
                           "device_ops", "idle_gaps"}
    plain = _jsonable(trace_reduce.reduce_file(OLD_TRACE))
    joint = trace_spans.reduce_file(OLD_TRACE)
    assert set(joint) == set(before) | NEW_KEYS
    joint = _jsonable({k: v for k, v in joint.items() if k not in NEW_KEYS})
    assert plain[key] == before[key]
    assert joint[key] == before[key]


def _plain_reduce_file(path, top=10):
    from jax.profiler import ProfileData

    return trace_reduce.reduce_profile(ProfileData.from_file(path), top=top)


def test_install_adds_the_keys_to_reduce_file(monkeypatch):
    monkeypatch.setattr(trace_reduce, "reduce_file", _plain_reduce_file)
    assert not NEW_KEYS & set(trace_reduce.reduce_file(OLD_TRACE))
    trace_spans.install()
    assert NEW_KEYS <= set(trace_reduce.reduce_file(OLD_TRACE))


@pytest.mark.parametrize("name", ["zbuild_ms", "oracle_ms", "comm_ms",
                                  "host_gap_ms"])
def test_trace_readers_install_when_loaded(monkeypatch, name):
    monkeypatch.setattr(trace_reduce, "reduce_file", _plain_reduce_file)
    run_cell.load_reader(name)
    assert trace_reduce.reduce_file is trace_spans.reduce_file


# -------------------------------------------------------- the new keys
def test_old_trace_has_no_program_spans():
    r = trace_spans.reduce_file(OLD_TRACE)
    assert r["program_spans"] == {}
    idle = r["window_s"] - r["busiest_busy_s"]
    assert r["idle_by_span"] == {trace_spans.OUTSIDE: pytest.approx(idle)}
    assert sum(r["op_self_s"].values()) == pytest.approx(r["busiest_busy_s"])


def test_recorded_spans_and_idle(span_trace, recorded):
    r = span_trace
    spans = r["program_spans"]
    sweeps = recorded["n_sweeps"]
    calls = r["n_calls"]
    assert calls == 2 and sweeps == 4
    assert len(spans["hooi.run"]) == calls
    assert len(spans["hooi.sweep"]) == sweeps
    assert len(spans["hooi.step"]) == 3 * sweeps
    for name in ("hooi.wait", "hooi.core", "hooi.fit"):
        assert len(spans[name]) == sweeps
    for ivs in spans.values():
        assert all(s <= e for s, e in ivs) and ivs == sorted(ivs)
    idle = r["window_s"] - r["busiest_busy_s"]
    assert sum(r["idle_by_span"].values()) == pytest.approx(idle, rel=1e-9)
    assert set(r["idle_by_span"]) <= set(spans) | {trace_spans.OUTSIDE}
    assert sum(r["op_self_s"].values()) == pytest.approx(r["busiest_busy_s"])
    assert r["device_ops"][0][1] == max(r["op_self_s"].values())


def test_recorded_ops_carry_the_step_names(span_trace, recorded):
    ops = span_trace["op_self_s"]
    steps = {f"jit_hooi_step_m{n}_local" for n in range(3)}
    assert steps <= {k.split("/", 1)[0] for k in ops}
    named = [k for k in ops if k.split("/", 1)[0] in steps]
    scoped = [k for k in named if k in recorded["op_scopes"]]
    # the instruction names in the trace are those of the compiled text
    assert sum(ops[k] for k in scoped) >= 0.95 * sum(ops[k] for k in named)


def test_innermost_timeline_and_charge():
    spans = {"hooi.run": [[0, 100]], "hooi.step": [[10, 20], [30, 40]],
             "hooi.wait": [[40, 60]]}
    tl = trace_spans.innermost_timeline(spans, -10, 110)
    assert tl == [(-10, 0, "(outside)"), (0, 10, "hooi.run"),
                  (10, 20, "hooi.step"), (20, 30, "hooi.run"),
                  (30, 40, "hooi.step"), (40, 60, "hooi.wait"),
                  (60, 100, "hooi.run"), (100, 110, "(outside)")]
    got = trace_spans.charge([(-5, 5), (15, 35), (50, 105)], tl)
    assert got == pytest.approx({"(outside)": 10e-9, "hooi.run": 55e-9,
                                 "hooi.step": 10e-9, "hooi.wait": 10e-9})


def test_nested_spans_starting_together_charge_the_inner():
    tl = trace_spans.innermost_timeline(
        {"hooi.sweep": [[0, 50]], "hooi.step": [[0, 10]]}, 0, 50)
    assert tl == [(0, 10, "hooi.step"), (10, 50, "hooi.sweep")]


@pytest.mark.parametrize("name,program", [
    ("hooi.step#it=0,mode=2#", True), ("hooi.run#call=3#", True),
    ("plan", True), ("plan.scheme", True), ("sched.run#seq=1#", True),
    ("bench.call", False), ("plan.py:450 plan", False),
    ("PjitFunction(hooi_step_m0_local)", False)])
def test_program_span_names(name, program):
    base = trace_spans.base_name(name)
    assert bool(trace_spans.PROGRAM_SPAN.match(base)) is program


# ---------------------------------------------------------- the readers
def _read(name, ctx):
    return run_cell.load_reader(name)(ctx)


def test_scope_readers_on_the_recorded_trace(span_trace, recorded):
    ctx = _ctx(recorded, span_trace)
    ops, scopes = span_trace["op_self_s"], recorded["op_scopes"]
    sweeps = recorded["n_sweeps"]
    for scope in ("zbuild", "oracle", "comm"):
        want = 1e3 * sum(v for k, v in ops.items()
                         if scopes.get(k) == scope) / sweeps
        assert _read(f"{scope}_ms", ctx) == pytest.approx(want)
    zb, orc = _read("zbuild_ms", ctx), _read("oracle_ms", ctx)
    assert zb > 0 and orc > 0 and _read("comm_ms", ctx) == 0.0
    assert zb + orc <= 1e3 * span_trace["busiest_busy_s"] / sweeps


def test_host_gap_on_the_recorded_trace(span_trace, recorded):
    ctx = _ctx(recorded, span_trace)
    idle = span_trace["idle_by_span"]
    want = 1e3 * sum(v for k, v in idle.items()
                     if k.startswith("hooi.") and k != "hooi.wait")
    got = _read("host_gap_ms", ctx)
    assert got == pytest.approx(want / recorded["n_sweeps"])
    assert 0 < got <= 1e3 * (span_trace["window_s"]
                             - span_trace["busiest_busy_s"]) / 4


def test_counter_readers_on_the_recorded_run(span_trace, recorded):
    ctx = _ctx(recorded, span_trace)
    core = sum(c.get("hooi.core", 0) for c in recorded["compiles"])
    assert _read("core_compiles", ctx) == core / recorded["n_sweeps"]
    for name, span in (("scheme_s", "plan.scheme"),
                       ("partition_s", "plan.partition")):
        assert _read(name, ctx) == recorded["build_spans"][span][1] > 0


@pytest.mark.parametrize("name", READERS)
def test_readers_return_none_without_their_input(name, span_trace, recorded):
    parent = {  # a program without spans, scopes or counters
        "trace": trace_spans.reduce_file(OLD_TRACE),
        "window": {"n_sweeps": 3},
        "setup": {"executor": SimpleNamespace()},
        "stats": [SimpleNamespace(), SimpleNamespace()],
        "plan": SimpleNamespace(build_s=1.0)}
    assert _read(name, parent) is None
    empty = _ctx(recorded, None)
    empty.update(stats=[], plan=SimpleNamespace(build_spans=None))
    assert _read(name, empty) is None
    no_sweeps = _ctx(recorded, span_trace)
    no_sweeps["window"] = {"n_sweeps": 0}
    if name not in ("scheme_s", "partition_s"):  # set-up, not per sweep
        assert _read(name, no_sweeps) is None
