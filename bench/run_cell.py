#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run_cell.py --workload nell2.sweeps --seed 7 --seconds 10 --trace 0

Everything a cell is made of is found by name from ``BENCHMARK.json``: its
configuration file (``configs``), its traffic mix
``bench/mixes/<traffic>.json``, the loop module of the mix's kind
``bench/loops/<kind>.py``, and one reader ``bench/metrics/<name>.py`` per
metric, end-to-end and per-layer alike. Adding a cell, a mix, a kind of
mix, a configuration or a metric adds files and entries; this file does
not change.

A run:

1. set-up (``setup_s``): the loop's ``setup``;
2. the window: the loop's ``window`` for ``--seconds`` (``--trace 1``:
   the mix's ``trace_calls`` calls under the profiler instead);
3. after the window: device memory is read, the metrics are read, the
   loop's ``answers`` (a sample of the window's calls drawn from the
   seed) are copied to the host, the program's state is freed, and each
   answer is compared with the float64 reference (``bench/reference.py``).

The last line on standard output is one JSON object; the compared numbers
and their limits are the last lines on standard error and the last key of
that object. Without a TPU, or with fewer chips than the cell asks for, the
run exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.util
import json
import math
import os
import re
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
SRC = os.path.join(ROOT, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
RUN_DIR = os.path.join(ROOT, ".bench_run")  # traces; listed in .gitignore


class CellError(Exception):
    """The manifest or a file it names is missing or malformed."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ------------------------------------------------------------- manifest
def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _bench_file(root: str, kind: str, name: str, ext: str) -> str:
    """``<root>/bench/<kind>/<name><ext>``, for a legal name only."""
    if not NAME.match(name):
        raise CellError(f"illegal name {name!r}")
    path = os.path.join(root, "bench", kind, name + ext)
    if not os.path.isfile(path):
        raise CellError(f"missing {os.path.relpath(path, root)}")
    return path


def mix_file(traffic: str, root: str = ROOT) -> str:
    return _bench_file(root, "mixes", traffic, ".json")


def metric_file(name: str, root: str = ROOT) -> str:
    return _bench_file(root, "metrics", name, ".py")


def loop_file(kind: str, root: str = ROOT) -> str:
    return _bench_file(root, "loops", kind, ".py")


def _load(path: str, prefix: str):
    name = os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(
        prefix + "".join(c if c.isalnum() else "_" for c in name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str, root: str = ROOT):
    """``read(ctx)`` of ``bench/metrics/<name>.py``."""
    return _load(metric_file(name, root), "bench_metric_").read


def load_loop(kind: str, root: str = ROOT):
    """The module ``bench/loops/<kind>.py``: ``setup``, ``window``,
    ``answers``."""
    return _load(loop_file(kind, root), "bench_loop_")


def metrics_of_cell(manifest: dict, workload: str) -> tuple[list, list]:
    """(end-to-end, per-layer) metric entries a cell reports."""
    return (list(manifest["end_to_end"]),
            [m for m in manifest["per_layer"] if workload in m["workloads"]])


def resolve_cell(workload: str, root: str = ROOT,
                 manifest: dict | None = None) -> dict:
    """Everything one cell is made of, found by name."""
    manifest = load_manifest(root) if manifest is None else manifest
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise CellError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    confs = {c["name"]: c for c in manifest["configs"]}
    if cell["config"] not in confs:
        raise CellError(f"no configuration {cell['config']!r}")
    conf = confs[cell["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        config = json.load(f)
    with open(mix_file(cell["traffic"], root)) as f:
        mix = json.load(f)
    e2e, layer = metrics_of_cell(manifest, workload)
    readers = {m["name"]: load_reader(m["name"], root) for m in e2e + layer}
    return {"cell": cell, "config": config, "mix": mix,
            "loop": load_loop(str(mix.get("kind")), root),
            "end_to_end": e2e, "per_layer": layer, "readers": readers}


# --------------------------------------------------------------- device
def accelerator(chips: int):
    """The TPU devices, or an error message when there are too few."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        return None, f"JAX found no devices: {e}"
    if devs[0].platform != "tpu":
        return None, f"no TPU: JAX runs on {devs[0].platform}"
    if len(devs) < chips:
        return None, f"the cell needs {chips} chips, JAX found {len(devs)}"
    return devs, None


def peak_bytes(devices) -> int:
    """``peak_bytes_in_use`` of the fullest device (0 where not reported)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks)) if peaks else 0


@contextlib.contextmanager
def compile_counter():
    """Counts XLA compilations (not cache loads) while the block runs."""
    import jax

    box = {"n": 0, "on": True}

    def listener(event, *_args, **_kw):
        if box["on"] and event == "/jax/core/compile/backend_compile_duration":
            box["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        yield box
    finally:
        box["on"] = False


# ------------------------------------------------------------------ run
def check(t, cfg: dict, answers: list) -> tuple[dict, list]:
    """Compare the sampled answers with the reference.

    Returns the worst reading of each compared number beside its limit,
    and every reading of every sampled answer.
    """
    from bench import reference

    limits = cfg["limits"]
    readings = []
    for a in answers:
        r = reference.compare(t.coords, t.values, t.shape, seed=a["seed"],
                              n_sweeps=a["n_sweeps"], before=a["before"],
                              factors=a["factors"], core=a["core"])
        readings.append(dict(r, call=a["call"], seed=a["seed"]))
    checks = {}
    for name, limit in limits.items():
        vals = [r[name] for r in readings]
        # no answer, or a reading that is not a number, has no value
        worst = (max(vals) if vals and all(map(math.isfinite, vals))
                 else None)
        checks[name] = {"value": worst, "limit": float(limit)}
    return checks, readings


def run(spec: dict, seed: int, seconds: float, trace: bool,
        devices) -> dict:
    """One run of a resolved cell on ``devices``; returns the result line."""
    import jax

    from bench import counts, trace_reduce

    cfg, mix, cell, loop = (spec["config"], spec["mix"], spec["cell"],
                           spec["loop"])
    kind = devices[0].device_kind
    peaks = counts.peaks(kind) if devices[0].platform == "tpu" else None
    used = devices[:int(cfg["P"])]

    state = loop.setup(cfg, mix, seed, devices)
    with compile_counter() as compiles:
        if trace:
            trace_dir = os.path.join(RUN_DIR, f"trace-{cell['name']}-{seed}")
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
            try:
                win = loop.window(state, cfg, mix, seed, seconds,
                                 max_calls=int(mix["trace_calls"]))
            finally:
                jax.profiler.stop_trace()
        else:
            win = loop.window(state, cfg, mix, seed, seconds)
    hbm = peak_bytes(devices)
    log(f"[window] {win['attempted']} calls, {win['failed']} failed, "
        f"{win['n_sweeps']} sweeps in {win['window_s']:.3f} s; XLA "
        f"compilations in the window: {compiles['n']}")

    ctx = {"config": cfg, "plan": state["plan"], "stats": win["stats"],
           "setup": state, "window": win, "peak": peaks, "trace": None,
           "xla_compiles": compiles["n"], "memory_peak_bytes": hbm}
    if trace:
        ctx["trace"] = trace_reduce.reduce_file(
            trace_reduce.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
    metrics = {}
    for m in spec["per_layer"] if trace else spec["end_to_end"]:
        v = spec["readers"][m["name"]](ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": hbm}
    breakdown = None
    tr = ctx["trace"]
    if tr is not None and tr.get("devices"):
        used_ids = {d.id for d in used}
        busy = [x["busy_s"] for d, x in tr["devices"].items()
                if d in used_ids] or [tr["busy_s_mean"]]
        device["busy_s"] = sum(busy) / len(busy)
        device["window_s"] = tr["window_s"]
        breakdown = {"device_ops": tr["device_ops"],
                     "idle_gaps": tr["idle_gaps"]}

    answers = loop.answers(state, cfg, mix, win)
    # free the program's state before the reference runs
    t = state["tensor"]
    state.clear()
    ctx.clear()
    win["stats"] = None
    gc.collect()

    t0 = time.perf_counter()
    checks, readings = check(t, cfg, answers)
    for r in readings:
        log("[check] call {call} seed {seed}: ".format(**r) + ", ".join(
            f"{k} {v!r}" for k, v in r.items() if k not in ("call", "seed")))
    log(f"[check] {len(readings)} answers in {time.perf_counter() - t0:.1f} s")
    correct = (win["failed"] == 0 and bool(answers)
               and all(c["value"] is not None and c["value"] <= c["limit"]
                       for c in checks.values()))
    result = {"correct": correct, "attempted": win["attempted"],
              "failed": win["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        log(f"no program under {SRC}: nothing to measure")
        return 2
    try:
        spec = resolve_cell(args.workload)
    except (CellError, OSError, KeyError, ValueError) as e:
        log(f"cell {args.workload!r}: {e}")
        return 2

    from repro.runtime import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices, err = accelerator(int(spec["cell"]["chips"]))
    if err:
        log(f"refusing to measure: {err}")
        return 2
    log(f"[env] jax {jax.__version__}, {len(devices)} x "
        f"{devices[0].device_kind}, compile cache {cache}")
    result = run(spec, args.seed, args.seconds, bool(args.trace), devices)
    for name, c in result["checks"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
