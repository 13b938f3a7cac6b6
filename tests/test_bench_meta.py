"""Benchmark harness provenance: BENCH_*.json stamping + empty-list guard."""

import json
import os
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, "benchmarks"))

import run as benchrun  # noqa: E402


def test_run_benches_refuses_empty_list(tmp_path):
    """A filtering bug upstream must fail loudly, not write no artifacts."""
    with pytest.raises(ValueError, match="empty bench list"):
        benchrun.run_benches([], out_dir=str(tmp_path))
    with pytest.raises(ValueError, match="empty bench list"):
        benchrun.run_benches(iter(()), out_dir=str(tmp_path))
    assert list(tmp_path.iterdir()) == []


def test_bench_json_carries_environment_stamp(tmp_path):
    """Every BENCH_<name>.json is stamped with git SHA, timestamp, jax
    version and device kind so the trajectory across PRs is comparable."""

    def bench_fake():
        benchrun._row("fake/row", 1.0, "derived=ok")

    paths = benchrun.run_benches([bench_fake], out_dir=str(tmp_path))
    assert len(paths) == 1
    with open(paths[0]) as f:
        data = json.load(f)
    assert data["bench"] == "bench_fake"
    assert data["rows"] and data["rows"][0]["name"] == "fake/row"
    meta = data["meta"]
    for field in ("git_sha", "timestamp", "jax_version", "device_kind",
                  "platform"):
        assert meta.get(field), field
    # a real git checkout resolves to a 40-hex SHA; degraded environments
    # record the sentinel rather than crashing the bench run
    assert meta["git_sha"] == "unknown" or len(meta["git_sha"]) == 40
    assert "T" in meta["timestamp"]  # ISO-8601


def test_bench_environment_is_self_contained():
    meta = benchrun.bench_environment()
    import jax

    assert meta["jax_version"] == jax.__version__


def test_artifact_path_guard(tmp_path):
    """Slug sanitization + containment: a dynamic bench name can never
    route a BENCH_*.json outside the artifact dir."""
    p = benchrun._artifact_path(str(tmp_path), "bench_hooi_time")
    assert p == os.path.join(os.path.realpath(str(tmp_path)),
                             "BENCH_hooi_time.json")
    for bad in ("bench_../evil", "bench_a/b", "bench_", "bench_a b"):
        with pytest.raises(RuntimeError, match="unsafe artifact slug"):
            benchrun._artifact_path(str(tmp_path), bad)


def test_run_benches_detects_stray_artifacts(tmp_path, monkeypatch):
    """A bench that drops BENCH_*.json into the working dir (instead of
    out_dir) fails the whole run loudly — CI would otherwise upload
    nothing while reading all green."""
    workdir = tmp_path / "cwd"
    workdir.mkdir()
    monkeypatch.chdir(workdir)

    def bench_rogue():
        with open("BENCH_rogue.json", "w") as f:
            f.write("{}")

    out = tmp_path / "artifacts"
    with pytest.raises(RuntimeError, match="outside the artifact dir"):
        benchrun.run_benches([bench_rogue], out_dir=str(out))
    # the well-routed artifact was still written before the guard fired
    assert (out / "BENCH_rogue.json").exists()

    # pre-existing strays don't trip the guard (only new ones do)
    def bench_clean():
        benchrun._row("clean/row", 1.0, "ok")

    paths = benchrun.run_benches([bench_clean], out_dir=str(out))
    assert len(paths) == 1


def test_bench_environment_raises_without_a_device(monkeypatch):
    """An artifact must never claim a device it did not see."""
    import jax

    def no_devices():
        raise RuntimeError("no backend")

    monkeypatch.setattr(jax, "devices", no_devices)
    with pytest.raises(RuntimeError, match="no backend"):
        benchrun.bench_environment()


def test_child_benches_run_before_in_process_ones(tmp_path):
    """A bench that computes with JAX here would hold the accelerator a
    child process needs, so every child bench runs first."""
    order = []

    def bench_here():
        order.append("here")

    @benchrun._in_child
    def bench_child():
        order.append("child")

    benchrun.run_benches([bench_here, bench_child], out_dir=str(tmp_path))
    assert order == ["child", "here"]
