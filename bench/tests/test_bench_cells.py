"""Tests of the benchmark harness, on the CPU at small sizes.

    PYTHONPATH=src python -m pytest -q bench/tests

* the manifest: every cell resolves its configuration, mix and metric
  readers by name, every entry keeps the manifest's rules, and a new cell
  needs new files and entries only;
* the harness refuses to measure without a TPU;
* the copied generator, the work counts and the trace reduction;
* the check: a sound run is correct; the control (the reference one
  precision down, in the program's place) and each fault a cell can have
  come out not correct: a step that returns its state unchanged and half
  of the nonzeros left out of the Z build (each in every mode and in one
  mode alone), the exchange between chips left out, and an answer altered
  where it is produced.
"""

from __future__ import annotations

import copy
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from bench import counts, gen, reference, run_cell, trace_reduce  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
TRACE = os.path.join(ROOT, "bench", "testdata", "tiny-P1.xplane.pb")


@pytest.fixture(scope="module")
def manifest():
    return run_cell.load_manifest()


# ------------------------------------------------------------- manifest
def test_every_cell_resolves_by_name(manifest):
    for w in manifest["workloads"]:
        spec = run_cell.resolve_cell(w["name"])
        assert spec["config"]["name"] == w["config"]
        assert spec["config"]["P"] == w["chips"]
        assert set(spec["readers"]) == {m["name"] for m in
                                        spec["per_layer"] + spec["end_to_end"]}
        assert all(callable(r) for r in spec["readers"].values())
        assert callable(spec["loop"].window)
        names = {m["name"] for m in spec["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2
        assert spec["per_layer"]


def test_manifest_entries_keep_the_rules(manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    configs = {c["name"]: c for c in manifest["configs"]}
    e2e = {m["name"] for m in manifest["end_to_end"]}
    for c in manifest["configs"]:
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert set(cfg["limits"]) == {"step_gap_vs_bf16"}
    for w in manifest["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs
        assert w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200
    for m in manifest["end_to_end"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert os.path.isfile(run_cell.metric_file(m["name"]))
    for m in manifest["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert m["layer"] and "\n" not in m["layer"] and m["moves"] in e2e
        assert m["workloads"] and set(m["workloads"]) <= cells
        for w in m["workloads"]:
            reported = {x["name"] for x in
                        run_cell.metrics_of_cell(manifest, w)[0]}
            assert m["moves"] in reported
        assert os.path.isfile(run_cell.metric_file(m["name"]))


def test_new_cell_needs_only_new_files(tmp_path, manifest):
    """A configuration, a mix of a new kind, a per-layer metric and an
    end-to-end metric added as files plus entries, and a run of the cell."""
    import jax

    harness = os.path.join(ROOT, "bench", "run_cell.py")
    with open(harness, "rb") as f:
        before = hashlib.sha256(f.read()).hexdigest()
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    m = copy.deepcopy(manifest)
    cfg = json.loads((tmp_path / "bench/configs/nell2-1chip.json").read_text())
    cfg.update(name="tiny-1chip", **TINY)
    (tmp_path / "bench/configs/tiny-1chip.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/mixes/tiny.json").write_text(json.dumps(
        {"kind": "counted_loop", "n_invocations": 1, "check_calls": 1,
         "trace_calls": 1}))
    # a new kind of mix: the closed loop, counting its calls for a new
    # end-to-end metric
    (tmp_path / "bench/loops/counted_loop.py").write_text(
        "from bench.loops.closed_loop import answers, setup\n"
        "from bench.loops import closed_loop\n\n\n"
        "def window(*a, **kw):\n"
        "    win = closed_loop.window(*a, **kw)\n"
        "    win['calls_per_s'] = win['attempted'] / win['window_s']\n"
        "    return win\n")
    (tmp_path / "bench/metrics/calls_per_s.py").write_text(
        "def read(ctx):\n    return ctx['window']['calls_per_s']\n")
    (tmp_path / "bench/metrics/tiny_metric.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    m["configs"].append({"name": "tiny-1chip", "source": cfg["source"],
                         "file": "bench/configs/tiny-1chip.json",
                         "reduced": cfg["reduced"], "why": "test"})
    m["workloads"].append({"name": "tiny.tiny", "config": "tiny-1chip",
                           "traffic": "tiny", "chips": 1, "why": "test"})
    m["end_to_end"].append({"name": "calls_per_s", "unit": "1/s",
                            "better": "higher", "bound": 0.05,
                            "source": "host_clock"})
    m["per_layer"].append({"name": "tiny_metric", "unit": "s",
                           "better": "lower", "source": "host_clock",
                           "layer": "host plan", "moves": "setup_s",
                           "workloads": ["tiny.tiny"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    spec = run_cell.resolve_cell("tiny.tiny", root=str(tmp_path))
    assert spec["config"]["name"] == "tiny-1chip"
    assert spec["mix"]["n_invocations"] == 1
    assert spec["readers"]["tiny_metric"]({}) == 42.0
    r = run_cell.run(spec, 2**31 + 23, 0.0, False, jax.devices())
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"sweep_s", "hbm_peak_gb", "setup_s",
                                 "calls_per_s"}
    assert r["metrics"]["calls_per_s"]["value"] > 0
    with open(harness, "rb") as f:
        assert hashlib.sha256(f.read()).hexdigest() == before


def test_unknown_names_are_refused():
    with pytest.raises(run_cell.CellError):
        run_cell.resolve_cell("no-such.cell")
    with pytest.raises(run_cell.CellError):
        run_cell.metric_file("../run_cell")


def _run_cli(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, "bench/run_cell.py", "--workload", "nell2.sweeps",
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    r = _run_cli(ROOT, {})
    assert r.returncode != 0
    assert "{" not in r.stdout
    assert "no TPU" in r.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    r = _run_cli(str(tmp_path), {"PYTHONPATH": ""})
    assert r.returncode != 0 and "{" not in r.stdout


# ------------------------------------------------------- yardstick parts
@pytest.mark.parametrize("kw", [
    dict(shape=(40, 30, 50), nnz=3000, alphas=(0.9, 0.9, 1.0), seed=0),
    dict(shape=(60, 50, 400, 12), nnz=5000, alphas=(1.4, 1.4, 1.1, 0.8),
         hub_fraction=0.09, hub_modes=(0,), seed=2**31 + 5),
])
def test_generator_matches_the_program_bit_for_bit(kw):
    from repro.data.tensors import synth_tensor

    t = synth_tensor(**kw)
    coords, values = gen.synth_coo(**kw)
    assert np.array_equal(coords, t.coords)
    assert values.dtype == t.values.dtype
    assert np.array_equal(values, t.values)


def test_cell_tensor_keeps_the_pattern_and_draws_values_from_seed():
    cfg = {"shape": [40, 30, 50], "nnz": 3000, "alphas": [0.9, 0.9, 1.0],
           "structure_seed": 0}
    c1, v1 = gen.cell_tensor(cfg, 1)
    c2, v2 = gen.cell_tensor(cfg, 2**31 + 9)
    c3, v3 = gen.cell_tensor(cfg, 1)
    assert np.array_equal(c1, c2) and np.array_equal(v1, v3)
    assert not np.array_equal(v1, v2)


def test_counts_match_scheme_metrics(small_tensor):
    from repro.core.plan import plan

    K = (3, 4, 2)
    pl = plan(small_tensor, "lite", 2, core_dims=K, path="liteopt")
    m = pl.metrics
    assert counts.flops_per_sweep(K, m.per_mode) == m.critical_path_flops
    expect = sum(pm.E_max * (3 * 4 + 4) + 2 * pm.R_max * counts.khat(K, n) * 4
                 for n, pm in enumerate(m.per_mode))
    assert counts.bytes_per_sweep(K, m.per_mode) == expect
    peak = counts.peaks("TPU v5 lite")
    least, bound = counts.least_time_s(K, m.per_mode, peak)
    t_f = m.critical_path_flops / peak["bf16_flops_per_s"]
    t_b = expect / peak["hbm_bytes_per_s"]
    assert least == max(t_f, t_b) and bound == ("flops" if t_f >= t_b
                                                else "bytes")


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        counts.peaks("TPU v99 imaginary")


# ------------------------------------------------------------ the trace
def test_union_intervals():
    iv = [(5, 7), (0, 2), (1, 3), (6, 9), (12, 20)]
    assert trace_reduce.union_intervals(iv, 0, 15) == [[0, 3], [5, 9],
                                                         [12, 15]]
    assert trace_reduce.union_intervals(iv, 4, 4) == []


def test_self_times_subtract_nested_ops():
    evs = [("while", 0, 10), ("a", 1, 3), ("b", 4, 6), ("a", 7, 8),
           ("c", 12, 14)]
    got = trace_reduce.self_times(evs, 0, 13)
    assert got == pytest.approx({"while": 5e-9, "a": 3e-9, "b": 2e-9,
                                 "c": 1e-9})


def test_recorded_trace_reduction():
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(TRACE)
    r = trace_reduce.reduce_profile(pd)
    devs, host = trace_reduce._events(pd)
    calls = [(s, e) for evs in host.values() for n, s, e in evs
             if n == "bench.call"]
    assert r["n_calls"] == len(calls) >= 1
    lo, hi = min(s for s, _ in calls), max(e for _, e in calls)
    assert r["window_s"] == pytest.approx((hi - lo) * 1e-9)
    # busy union by a plain sweep over a fine grid of event edges
    ops = devs[r["busiest"]]
    edges = sorted({lo, hi} | {min(max(x, lo), hi) for _, s, e in ops
                               for x in (s, e)})
    busy = sum(b - a for a, b in zip(edges, edges[1:])
               if any(s <= a and b <= e for _, s, e in ops))
    assert r["busiest_busy_s"] == pytest.approx(busy * 1e-9, rel=1e-9)
    assert 0.0 <= r["idle_share"] < 1.0
    assert r["idle_share"] == pytest.approx(
        1 - r["busiest_busy_s"] / r["window_s"])
    coll = sum(min(e, hi) - max(s, lo) for n, s, e in ops
               if trace_reduce.COLLECTIVE.match(n) and e > lo and s < hi)
    assert r["busiest_collective_s"] == pytest.approx(coll * 1e-9)
    secs = [s for _, s in r["device_ops"]]
    assert secs == sorted(secs, reverse=True) and len(secs) <= 10
    gaps = [g for _, g in r["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert sum(gaps) <= r["window_s"] - r["busiest_busy_s"] + 1e-9


# ------------------------------------------------------------ the check
TINY = {"shape": [300, 250, 700], "nnz": 20000}
LIMIT = "step_gap_vs_bf16"


def _tiny_spec(workload="nell2.sweeps"):
    spec = run_cell.resolve_cell(workload)
    spec["config"].update(TINY)
    return spec


def _run(spec):
    import jax

    return run_cell.run(spec, 2**31 + 17, 0.0, False, jax.devices())


def _fails(r):
    c = r["checks"][LIMIT]
    return not r["correct"] and (c["value"] is None or c["value"] > c["limit"])


def test_sound_run_is_correct():
    r = _run(_tiny_spec())
    assert r["correct"], r["checks"]
    assert r["attempted"] == 1 and r["failed"] == 0
    assert set(r["metrics"]) == {"sweep_s", "hbm_peak_gb", "setup_s"}
    assert list(r)[-1] == "checks"


def test_control_is_not_correct():
    """The reference one precision down, put in the program's place."""
    import jax

    spec = _tiny_spec()
    cfg, mix, loop = spec["config"], spec["mix"], spec["loop"]
    state = loop.setup(cfg, mix, 5, jax.devices())
    win = loop.window(state, cfg, mix, 5, 0.0, max_calls=1)
    ans = loop.answers(state, cfg, mix, win)[0]
    t = state["tensor"]
    sound, _ = run_cell.check(t, cfg, [ans])
    assert sound[LIMIT]["value"] <= sound[LIMIT]["limit"]
    ctl = reference.control_answer(t.coords, t.values, t.shape,
                                   seed=ans["seed"], n_sweeps=ans["n_sweeps"],
                                   before=ans["before"])
    ctl.update(call=0, seed=ans["seed"], n_sweeps=ans["n_sweeps"])
    checks, rows = run_cell.check(t, cfg, [ctl])
    # the control's gap on each mode is that of its own draw
    assert rows[0]["mode_gap"] == pytest.approx(
        [d[0] for d in rows[0]["bf16_draw_gaps"]], rel=1e-3)
    assert checks[LIMIT]["value"] > checks[LIMIT]["limit"]


def _stale_in(monkeypatch, modes):
    """Mode steps of ``modes`` return their state unchanged."""
    from repro.distributed import executor

    real = executor.run_hooi_sweeps

    def stale(coords, values, t, factors, key, n_inv, mode_step, **kw):
        def step(n, facs, kk):
            new = mode_step(n, facs, kk)
            return facs[n] if n in modes else new
        return real(coords, values, t, factors, key, n_inv, step, **kw)

    monkeypatch.setattr(executor, "run_hooi_sweeps", stale)


def _z_fault_in(monkeypatch, modes, fault):
    """``fault`` applied to the Z build of ``modes``."""
    from repro.engine import steps

    real = steps.build_local_z

    def faulty(coords, values, local_rows, factors, mode, *a, **kw):
        if mode not in modes:
            return real(coords, values, local_rows, factors, mode, *a, **kw)
        return fault(real, coords, values, local_rows, factors, mode, *a,
                     **kw)

    monkeypatch.setattr(steps, "build_local_z", faulty)


def _half(real, coords, values, *a, **kw):
    keep = (np.arange(values.shape[0]) % 2 == 0).astype(np.float32)
    return real(coords, values * keep, *a, **kw)


@pytest.mark.parametrize("modes", [(0, 1, 2), (0,), (1,)])
def test_fault_state_unchanged(monkeypatch, modes):
    _stale_in(monkeypatch, modes)
    r = _run(_tiny_spec())
    assert _fails(r), r["checks"]


@pytest.mark.parametrize("modes", [(0, 1, 2), (0,), (1,)])
def test_fault_half_the_nonzeros_left_out(monkeypatch, modes):
    _z_fault_in(monkeypatch, modes, _half)
    r = _run(_tiny_spec())
    assert _fails(r), r["checks"]


def _p4_spec():
    """nell2.sweeps on the four-chip configuration, which no cell uses until
    its cell is proven on four chips."""
    spec = _tiny_spec()
    with open(os.path.join(ROOT, "bench", "configs", "nell2-4chip.json")) as f:
        spec["config"] = dict(json.load(f), **TINY)
    return spec


def test_fault_exchange_left_out(monkeypatch):
    import jax

    sound = _run(_p4_spec())
    assert sound["correct"], sound["checks"]
    monkeypatch.setattr(jax.lax, "psum", lambda x, *a, **kw: x)
    r = _run(_p4_spec())
    assert _fails(r), r["checks"]


def test_fault_answer_altered(monkeypatch):
    from repro.core import ttm

    real = ttm.core_from_factors

    def altered(*a, **kw):
        core = real(*a, **kw)
        return core.at[(0,) * core.ndim].add(1e-2 * float(
            np.abs(np.asarray(core)).max()))

    monkeypatch.setattr(ttm, "core_from_factors", altered)
    r = _run(_tiny_spec())
    assert _fails(r), r["checks"]
