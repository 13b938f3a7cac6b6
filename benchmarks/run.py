"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (derived = the figure's metric)
and, when run through ``main()`` / ``run_benches()``, writes one
``BENCH_<name>.json`` per bench (rows + wall time + error, if any) so CI can
upload the perf trajectory as artifacts. Output dir: ``--out-dir`` or the
``BENCH_OUT_DIR`` env var (default: current directory).

Mapping (see DESIGN.md §7):
  Fig 9   bench_dataset_suite       tensor stats of the synthetic mirror suite
  Fig10/14 bench_hooi_time          HOOI wall-time x scheme (8 simulated ranks)
  Fig 12  bench_metrics             E^max/R^sum/R^max (imbalance + redundancy)
  Fig 13  bench_comm_volume         SVD vs factor-matrix volumes x scheme
  Fig 15  bench_scaling             critical-path scaling P=4..64
  Fig 16  bench_distribution_time   scheme construction wall-time
  Fig 17  bench_memory              memory model per rank x scheme
  (ours)  bench_kernel_oracle       fused-oracle kernel vs two-pass reference
  (ours)  bench_auto_selection      real-time auto selector choice + overhead
  (ours)  bench_plan_cache          PartitionPlan cache: 2nd dist_hooi call
                                    skips host-side partition construction
  (ours)  bench_executor_reuse      HooiExecutor engine: 2nd run on a cached
                                    plan does zero jit compilations and zero
                                    host->device uploads
  (ours)  bench_scheduler_overlap   StreamScheduler pipeline: host
                                    partitioning overlapped with device
                                    sweeps beats the sequential sum; the
                                    streaming-append rerun stays fully cached
  (ours)  bench_pool_throughput     ExecutorPool serving tier: 2 executors
                                    on disjoint device slices vs a single
                                    executor on a queue of concurrent
                                    streams (streams/sec + SLO accounting)
  (ours)  bench_objectives          objective-pluggable sweeps: masked
                                    completion beats the unmasked baseline
                                    on held-out RMSE under corrupted
                                    entries; a FROSTT .tns fixture streams
                                    through StreamingTensor -> scheduler
  (ours)  bench_sketch_warmstart    sketch warm starts cut counted oracle
                                    Z passes >=1.5x at equal final fit;
                                    adaptive per-mode rank grows AND
                                    shrinks mid-stream with the cost model
                                    re-scored each step
  (ours)  bench_mixed_backends      path="auto" under a per-backend-skewed
                                    CostModel picks a heterogeneous
                                    per-mode comm-backend map

Multi-device benches run in a child process (8 simulated host devices on the
CPU). ``run_benches`` runs them first and queries the device for the
provenance stamp last, so this process touches no accelerator while a child
may need it.

Discover bench names with ``--list``; run a subset by naming benches on the
command line (``python benchmarks/run.py plan_cache scheduler_overlap``).
"""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap
import time

import numpy as np

_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "src")
sys.path.insert(0, _SRC)

SCHEMES = ("lite", "coarse", "medium", "hypergraph")
DIST_SCHEMES = SCHEMES + ("auto",)  # runtime sweeps
CORE = (10, 10, 10)  # paper default K=10


def _suite(scale=0.25):
    from repro.data.tensors import paper_suite

    return paper_suite(scale=scale)


_ROWS: list = []  # rows of the currently-running bench (JSON artifact)


def _row(name: str, us: float, derived: str) -> None:
    print(f"{name},{us:.1f},{derived}")
    _ROWS.append({"name": name, "us_per_call": us, "derived": derived})


# ----------------------------------------------------------------- Fig 9
def bench_dataset_suite() -> None:
    t0 = time.perf_counter()
    suite = _suite()
    us = (time.perf_counter() - t0) * 1e6 / max(len(suite), 1)
    for name, t in suite.items():
        _row(f"fig9/{name}", us,
             f"shape={'x'.join(map(str, t.shape))};nnz={t.nnz};"
             f"sparsity={t.sparsity:.2e}")


# --------------------------------------------------------------- Fig 10/14
_DIST_BENCH_BODY = """
    import json, time
    import numpy as np
    from repro.data.tensors import paper_suite
    from repro.core.plan import plan
    from repro.distributed.dist_hooi import dist_hooi
    suite = paper_suite(scale=0.12)
    out = {}
    for tname in ["delicious-s", "enron-s", "nell2-s"]:
        t = suite[tname]
        core = (10,) * t.ndim
        out[tname] = {}
        for scheme in %r:
            try:
                t0 = time.perf_counter()
                dec, stats = dist_hooi(t, core, 8, scheme=scheme,
                                       n_invocations=1, path="liteopt",
                                       seed=0)
                dt = time.perf_counter() - t0
                # second run = steady-state (compiled) timing; the plan
                # cache makes its host-side partition time ~0
                t0 = time.perf_counter()
                dec, stats = dist_hooi(t, core, 8, scheme=scheme,
                                       n_invocations=1, path="liteopt",
                                       seed=1)
                warm = time.perf_counter() - t0
                # NOTE: all 8 simulated ranks share ONE physical core, so
                # wall time cannot show load imbalance; the critical-path
                # FLOPs ratio is the hardware-faithful signal (paper Fig 10)
                sm = plan(t, scheme, 8, core_dims=core).metrics
                out[tname][scheme] = {"cold_s": dt, "warm_s": warm,
                                      "fit": stats.fits[-1],
                                      "ran": stats.scheme,
                                      "cache_hit": stats.plan_cache_hit,
                                      "objective": stats.objective,
                                      "backends": "/".join(
                                          stats.comm_backends[n] for n in
                                          sorted(stats.comm_backends)),
                                      "crit_flops": sm.critical_path_flops}
            except Exception as e:
                out[tname][scheme] = {"error": str(e)[:100]}
    print("JSON::" + json.dumps(out))
"""


def _in_child(bench):
    """Mark a bench whose work runs in a child process: ``run_benches`` runs
    these before any bench that computes with JAX in this process, because
    a process that has touched an accelerator holds it."""
    bench.in_child = True
    return bench


def _run_subprocess_bench(body: str, devices: int = 8) -> dict:
    import json

    script = textwrap.dedent(f"""
        from repro.runtime import enable_compile_cache, simulate_cpu_devices
        simulate_cpu_devices({devices})
        enable_compile_cache()
    """) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = _SRC
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=3600, env=env)
    if res.returncode != 0:
        raise RuntimeError(f"subprocess bench failed:\n{res.stderr[-2000:]}")
    for line in res.stdout.splitlines():
        if line.startswith("JSON::"):
            return json.loads(line[6:])
    raise RuntimeError(f"no JSON in output:\n{res.stdout[-2000:]}")


@_in_child
def bench_hooi_time() -> None:
    out = _run_subprocess_bench(_DIST_BENCH_BODY % (DIST_SCHEMES,))
    for tname, per in out.items():
        base = per.get("lite", {}).get("warm_s")
        base_cf = per.get("lite", {}).get("crit_flops")
        for scheme, rec in per.items():
            if "error" in rec:
                _row(f"fig10/{tname}/{scheme}", -1.0, f"error={rec['error']}")
                continue
            rel = rec["warm_s"] / base if base else float("nan")
            crel = rec["crit_flops"] / base_cf if base_cf else float("nan")
            _row(f"fig10/{tname}/{scheme}", rec["warm_s"] * 1e6,
                 f"wall_rel_to_lite={rel:.2f};critpath_rel_to_lite={crel:.2f};"
                 f"fit={rec['fit']:.4f};ran={rec['ran']};"
                 f"warm_cache_hit={rec['cache_hit']};"
                 f"objective={rec['objective']};backends={rec['backends']}")


# ----------------------------------------------------------------- Fig 12
def bench_metrics() -> None:
    from repro.core.plan import plan

    suite = _suite()
    P = 64
    for tname, t in suite.items():
        core = (10,) * t.ndim
        for scheme_name in SCHEMES:
            if scheme_name == "hypergraph" and t.nnz > 60_000:
                _row(f"fig12/{tname}/{scheme_name}", -1.0,
                     "skipped=too_large_for_hyperg (paper: same for Zoltan)")
                continue
            t0 = time.perf_counter()
            sm = plan(t, scheme_name, P, core_dims=core).metrics
            us = (time.perf_counter() - t0) * 1e6
            imb = max(m.ttm_imbalance for m in sm.per_mode)
            red = max(m.svd_redundancy for m in sm.per_mode)
            svd_imb = max(m.svd_imbalance for m in sm.per_mode)
            _row(f"fig12/{tname}/{scheme_name}", us,
                 f"ttm_imbalance={imb:.2f};svd_redundancy={red:.2f};"
                 f"svd_imbalance={svd_imb:.2f}")


# ----------------------------------------------------------------- Fig 13
def bench_comm_volume() -> None:
    from repro.core.plan import plan

    suite = _suite()
    P = 64
    for tname in ("delicious-s", "enron-s", "flickr-s"):
        t = suite[tname]
        core = (10,) * t.ndim
        for scheme_name in SCHEMES:
            if scheme_name == "hypergraph" and t.nnz > 60_000:
                continue
            t0 = time.perf_counter()
            sm = plan(t, scheme_name, P, core_dims=core).metrics
            us = (time.perf_counter() - t0) * 1e6
            _row(f"fig13/{tname}/{scheme_name}", us,
                 f"svd_vol={sm.svd_volume};fm_vol={sm.fm_volume};"
                 f"total={sm.svd_volume + sm.fm_volume}")


# ----------------------------------------------------------------- Fig 15
def bench_scaling() -> None:
    """Critical-path FLOPs scaling P=4..64 (model-based strong scaling; the
    paper's Fig 15 wall-time speedups follow the same curve since HOOI is
    computation-dominated)."""
    from repro.core.plan import plan

    suite = _suite()
    for tname in ("delicious-s", "enron-s", "amazon-s"):
        t = suite[tname]
        core = (10,) * t.ndim
        for scheme_name in ("lite", "coarse", "medium"):
            flops = {}
            t0 = time.perf_counter()
            for P in (4, 8, 16, 32, 64):
                sm = plan(t, scheme_name, P, core_dims=core).metrics
                flops[P] = sm.critical_path_flops
            us = (time.perf_counter() - t0) * 1e6 / 5
            speedup = flops[4] / flops[64]
            _row(f"fig15/{tname}/{scheme_name}", us,
                 f"speedup_4_to_64={speedup:.1f};ideal=16.0")


# ----------------------------------------------------------------- Fig 16
def bench_distribution_time() -> None:
    """Scheme (policy) construction wall time, as the paper's Fig 16 charges
    it — partition/metric building is excluded so the cross-scheme ratios
    stay comparable to the paper. "auto" pays for all three candidates plus
    the cost-model scoring (uncached on purpose)."""
    from repro.core.distribution import build_scheme

    suite = _suite()
    P = 64
    for tname, t in suite.items():
        for scheme_name in SCHEMES + ("auto",):
            if scheme_name == "hypergraph" and t.nnz > 60_000:
                _row(f"fig16/{tname}/{scheme_name}", -1.0, "skipped=big")
                continue
            kw = {"use_cache": False} if scheme_name == "auto" else {}
            t0 = time.perf_counter()
            s = build_scheme(t, scheme_name, P, **kw)
            us = (time.perf_counter() - t0) * 1e6
            _row(f"fig16/{tname}/{scheme_name}", us,
                 f"nnz={t.nnz};ran={s.name}")


# ----------------------------------------------------------------- Fig 17
def bench_memory() -> None:
    from repro.core.plan import plan

    suite = _suite()
    P = 64
    for tname in ("delicious-s", "nell2-s", "amazon-s"):
        t = suite[tname]
        core = (10,) * t.ndim
        for scheme_name in ("lite", "coarse", "medium"):
            t0 = time.perf_counter()
            sm = plan(t, scheme_name, P, core_dims=core).metrics
            mem = sm.memory_bytes_per_rank()
            us = (time.perf_counter() - t0) * 1e6
            _row(f"fig17/{tname}/{scheme_name}", us,
                 f"tensor_MB={mem['tensor']/1e6:.2f};"
                 f"penult_MB={mem['penultimate']/1e6:.2f};"
                 f"total_MB={mem['total']/1e6:.2f}")


# ---------------------------------------------------------------- kernels
def bench_kernel_oracle() -> None:
    """Fused oracle pair vs two-pass reference: HBM bytes per Lanczos query
    (the kernel's raison d'être — reported analytically; wall time is the
    jnp reference since interpret-mode timing is meaningless)."""
    import jax.numpy as jnp
    from repro.kernels import ref

    rng = np.random.default_rng(0)
    for R, K in ((4096, 100), (16384, 100), (4096, 1000)):
        Z = jnp.asarray(rng.standard_normal((R, K)), jnp.float32)
        x = jnp.asarray(rng.standard_normal(K), jnp.float32)
        y = jnp.asarray(rng.standard_normal(R), jnp.float32)
        ref.oracle_pair_ref(Z, x, y)  # warm
        t0 = time.perf_counter()
        n = 20
        for _ in range(n):
            a, b = ref.oracle_pair_ref(Z, x, y)
        a.block_until_ready()
        us = (time.perf_counter() - t0) * 1e6 / n
        two_pass = 2 * R * K * 4
        fused = R * K * 4
        _row(f"kernel_oracle/R{R}_K{K}", us,
             f"hbm_two_pass_B={two_pass};hbm_fused_B={fused};saving=2.0x")


def bench_kernel_ttm() -> None:
    """TTM hot loop: Pallas kron_segsum vs the jnp segment_sum reference.

    Reference wall time is the meaningful number off-TPU (the kernel runs in
    interpret mode here, orders of magnitude slower than compiled); what the
    kernel buys is reported analytically — MXU MACs of the one-hot-matmul
    reformulation vs the scatter-add's MACs (~1.5x minimal work, but on the
    systolic array instead of serialized scatters) — plus the max abs
    difference as a correctness check.
    """
    import jax.numpy as jnp
    from repro.kernels import ref
    from repro.kernels.kron_segsum import ROW_BLOCK, kron_segsum, \
        tile_geometry

    rng = np.random.default_rng(1)
    block_e = 256
    for E, Ka, Kb, R in ((4096, 10, 10, 512), (16384, 10, 10, 2048),
                         (8192, 4, 100, 1024)):
        rows = np.sort(rng.integers(0, R, E)).astype(np.int32)
        a = rng.standard_normal((E, Ka)).astype(np.float32)
        b = rng.standard_normal((E, Kb)).astype(np.float32)
        jrows, ja, jb = jnp.asarray(rows), jnp.asarray(a), jnp.asarray(b)

        want = ref.kron_segsum_ref(jrows, ja, jb, R)  # warm
        t0 = time.perf_counter()
        n = 10
        for _ in range(n):
            want = ref.kron_segsum_ref(jrows, ja, jb, R)
        want.block_until_ready()
        ref_us = (time.perf_counter() - t0) * 1e6 / n

        t0 = time.perf_counter()
        got = kron_segsum(jrows, ja, jb, R, interpret=True)
        got.block_until_ready()
        interp_us = (time.perf_counter() - t0) * 1e6
        max_diff = float(np.abs(np.asarray(got) - np.asarray(want)).max())

        g = tile_geometry(R, Ka, Kb, block_e)
        n_eb = -(-E // block_e)
        n_kb = g.Kb_pad // g.kb_blk
        mxu_macs = n_kb * n_eb * g.span * ROW_BLOCK * block_e * Ka * g.kb_blk
        min_macs = E * Ka * Kb
        # systolic overhead decomposes into the span factor (row windows per
        # element block) and lane padding (Kb -> kb_blk multiples of 128)
        span_x = g.span * ROW_BLOCK / block_e
        lane_x = n_kb * g.kb_blk / Kb
        _row(f"kernel_ttm/E{E}_Ka{Ka}_Kb{Kb}_R{R}", ref_us,
             f"ref_us={ref_us:.1f};kernel_interpret_us={interp_us:.1f};"
             f"max_abs_diff={max_diff:.2e};"
             f"mxu_macs_over_minimal={mxu_macs / min_macs:.2f};"
             f"span_overhead={span_x:.2f}x;lane_pad={lane_x:.2f}x;"
             f"vmem_bytes={g.vmem_bytes}")


def bench_kernel_roofline() -> None:
    """Roofline: counted HBM passes over Z per sweep·mode — PR-6 reference
    path vs the fused Z-build→oracle pipeline vs fused + block Lanczos —
    with end-to-end fit parity between the variants (the passes drop is
    structural, not a quality trade). Acceptance: fused+block cuts the
    counted passes ≥2x vs the reference path."""
    from repro.core.hooi import hooi
    from repro.core.lanczos import effective_block_size, lanczos_niter
    from repro.data.tensors import synth_tensor
    from repro.engine import count_z_passes

    t = synth_tensor((120, 100, 90), 20_000, alphas=(1.1, 1.0, 1.0),
                     hub_fraction=0.1, hub_modes=(0,), seed=5)
    core = CORE  # paper default K=10
    variants = (
        ("reference", dict()),
        ("fused", dict(fused_zbuild=True)),
        ("fused_block8", dict(fused_zbuild=True, lanczos_block=8)),
    )
    passes = {}
    fits = {}
    for name, kw in variants:
        blk = int(kw.get("lanczos_block", 1))
        fz = bool(kw.get("fused_zbuild", False))
        per_mode = []
        for n in range(t.ndim):
            khat = int(np.prod([core[j] for j in range(t.ndim) if j != n]))
            s_eff = effective_block_size(core[n], t.shape[n], khat, blk)
            niter = lanczos_niter(core[n], t.shape[n], khat,
                                  s_eff if (fz or s_eff > 1) else 1)
            per_mode.append(count_z_passes(niter, fz))
        passes[name] = per_mode
        t0 = time.perf_counter()
        _, fit_traj = hooi(t, core, n_invocations=2, seed=0, **kw)
        us = (time.perf_counter() - t0) * 1e6
        fits[name] = fit_traj[-1]
        _row(f"kernel_roofline/{name}", us,
             f"z_passes_per_mode={'/'.join(map(str, per_mode))};"
             f"z_passes_sweep_total={sum(per_mode)};"
             f"final_fit={fit_traj[-1]:.4f}")
    ratio = sum(passes["reference"]) / max(sum(passes["fused_block8"]), 1)
    parity = max(abs(fits[n] - fits["reference"]) for n in fits)
    _row("kernel_roofline/acceptance", -1.0,
         f"passes_drop={ratio:.2f}x;ok={ratio >= 2.0};"
         f"max_fit_delta_vs_reference={parity:.4f};"
         f"parity_ok={parity < 5e-3}")


# ------------------------------------------------------- auto + plan cache
def bench_auto_selection() -> None:
    """Real-time selector: which candidate wins per tensor, and what the
    selection costs relative to building the winner alone."""
    from repro.core.plan import plan

    suite = _suite()
    P = 16
    for tname, t in suite.items():
        core = (10,) * t.ndim
        t0 = time.perf_counter()
        pl = plan(t, "auto", P, core_dims=core, use_cache=False)
        us = (time.perf_counter() - t0) * 1e6
        t0 = time.perf_counter()
        plan(t, pl.name, P, core_dims=core, use_cache=False)
        winner_us = (time.perf_counter() - t0) * 1e6
        cands = ";".join(f"{c}={v:.2e}" for c, v in
                         sorted(pl.candidates.items(), key=lambda kv: kv[1]))
        _row(f"auto/{tname}", us,
             f"picked={pl.name};overhead_vs_winner={us/max(winner_us,1):.2f}x;"
             + cands)


_PLAN_CACHE_BODY = """
    import json, time
    from repro.data.tensors import paper_suite
    from repro.distributed.dist_hooi import dist_hooi
    t = paper_suite(scale=0.12)["delicious-s"]
    core = (10,) * t.ndim
    out = {}
    for run in ("first", "second"):
        t0 = time.perf_counter()
        dec, stats = dist_hooi(t, core, 8, scheme="auto", n_invocations=1,
                               seed=0 if run == "first" else 1)
        out[run] = {"total_s": time.perf_counter() - t0,
                    "partition_build_s": stats.partition_build_s,
                    "cache_hit": stats.plan_cache_hit,
                    "scheme": stats.scheme,
                    "objective": stats.objective,
                    "backends": "/".join(stats.comm_backends[n] for n in
                                         sorted(stats.comm_backends))}
    print("JSON::" + json.dumps(out))
"""


@_in_child
def bench_plan_cache() -> None:
    """Acceptance: the second dist_hooi call on the same tensor must skip
    partition construction (host-side partition time ~ 0)."""
    out = _run_subprocess_bench(_PLAN_CACHE_BODY)
    first, second = out["first"], out["second"]
    for run, rec in (("first", first), ("second", second)):
        _row(f"plan_cache/{run}", rec["partition_build_s"] * 1e6,
             f"cache_hit={rec['cache_hit']};scheme={rec['scheme']};"
             f"total_s={rec['total_s']:.2f};objective={rec['objective']};"
             f"backends={rec['backends']}")
    speedup = first["partition_build_s"] / max(second["partition_build_s"],
                                               1e-9)
    _row("plan_cache/partition_speedup", second["partition_build_s"] * 1e6,
         f"first_vs_second={speedup:.0f}x;second_hit={second['cache_hit']}")


_SCHED_OVERLAP_BODY = """
    import json, time
    import numpy as np
    from repro.core.plan import plan_cache_clear
    from repro.data.tensors import synth_tensor
    from repro.distributed.executor import HooiExecutor
    from repro.engine.scheduler import StreamScheduler
    from repro.streaming import StreamingTensor

    core = (8, 8, 8)
    tensors = [synth_tensor((260, 220, 200), 60_000,
                            alphas=(1.2, 1.05, 1.05), hub_fraction=0.1,
                            hub_modes=(0,), seed=s) for s in range(4)]
    out = {}

    # one-time warmup so neither phase is charged XLA platform startup
    warm = synth_tensor((24, 20, 18), 500, seed=99)
    HooiExecutor(8).run(warm, (2, 2, 2), "lite", n_invocations=1)

    # --- sequential reference: plan -> stage -> sweep, one tensor at a time
    plan_cache_clear()
    ex_seq = HooiExecutor(8)
    t0 = time.perf_counter()
    host_s = dev_s = 0.0
    for i, t in enumerate(tensors):
        h0 = time.perf_counter()
        pl, _ = ex_seq.prepare(t, core, "auto", pad_geometric=True)
        h1 = time.perf_counter()
        ex_seq.run(t, core, pl, n_invocations=1, seed=i)
        dev_s += time.perf_counter() - h1
        host_s += h1 - h0
    seq_wall = time.perf_counter() - t0
    out["sequential"] = {"wall_s": seq_wall, "host_s": host_s,
                         "device_s": dev_s}

    # --- pipelined: same tensors, fresh caches + executor, scheduler overlap
    plan_cache_clear()
    ex_pipe = HooiExecutor(8)
    sched = StreamScheduler(ex_pipe, core, scheme="auto", n_invocations=1,
                            workers=2)
    t0 = time.perf_counter()
    futs = [sched.submit(t, name="t%d" % i, seed=i)
            for i, t in enumerate(tensors)]
    res = sched.drain()
    pipe_wall = time.perf_counter() - t0
    st = sched.stats()
    sched.close()
    out["pipelined"] = {"wall_s": pipe_wall, "host_s": st["host_s"],
                        "device_s": st["device_s"],
                        "overlap_s": st["overlap_s"],
                        "decisions": st["decisions"]}
    out["overlap_ok"] = pipe_wall < seq_wall

    # --- streaming ladder on the warm executor: append -> rerun contract
    stream = StreamingTensor.from_tensor(tensors[0], name="stream")
    sched = StreamScheduler(ex_pipe, core, scheme="auto", n_invocations=1,
                            workers=2)
    rng = np.random.default_rng(0)
    r1 = sched.submit(stream, seed=0).result()
    idx = rng.integers(0, tensors[0].nnz, 500)  # value updates: same coords
    stream.append(tensors[0].coords[idx], rng.standard_normal(500))
    r2 = sched.submit(stream, seed=1).result()
    r3 = sched.submit(stream, seed=2).result()  # rerun, unchanged stream
    sched.close()
    for name, r in (("stream_first", r1), ("stream_append", r2),
                    ("stream_rerun", r3)):
        out[name] = {"decision": r.decision,
                     "compilations": r.stats.step_compilations,
                     "uploads": r.stats.uploads,
                     "fit": r.fits[-1],
                     "objective": r.stats.objective,
                     "backends": "/".join(r.stats.comm_backends[n] for n in
                                          sorted(r.stats.comm_backends)),
                     # did THIS submit run the auto selector? (a reused
                     # auto plan still carries its adoption candidates)
                     "reselected": r.decision in ("plan", "reselect")}
    out["rerun_ok"] = (r3.decision == "reuse"
                       and r3.stats.step_compilations == 0
                       and r3.stats.uploads == 0)
    print("JSON::" + json.dumps(out))
"""


@_in_child
def bench_scheduler_overlap() -> None:
    """Acceptance: the scheduler pipeline (host partitioning overlapped
    with device sweeps) beats the sequential plan+sweep sum on a queue of
    tensors, and the streaming-append rerun on an unchanged distribution
    reports 0 new compilations and 0 new uploads."""
    out = _run_subprocess_bench(_SCHED_OVERLAP_BODY)
    seq, pipe = out["sequential"], out["pipelined"]
    _row("scheduler_overlap/sequential", seq["wall_s"] * 1e6,
         f"host_s={seq['host_s']:.2f};device_s={seq['device_s']:.2f}")
    _row("scheduler_overlap/pipelined", pipe["wall_s"] * 1e6,
         f"host_s={pipe['host_s']:.2f};device_s={pipe['device_s']:.2f};"
         f"overlap_hidden_s={pipe['overlap_s']:.2f};"
         f"decisions={pipe['decisions']}")
    _row("scheduler_overlap/speedup", pipe["wall_s"] * 1e6,
         f"ok={out['overlap_ok']};"
         f"sequential_vs_pipelined="
         f"{seq['wall_s'] / max(pipe['wall_s'], 1e-9):.2f}x")
    for name in ("stream_first", "stream_append", "stream_rerun"):
        rec = out[name]
        _row(f"scheduler_overlap/{name}", -1.0,
             f"decision={rec['decision']};"
             f"compilations={rec['compilations']};"
             f"uploads={rec['uploads']};reselected={rec['reselected']};"
             f"objective={rec['objective']};backends={rec['backends']};"
             f"fit={rec['fit']:.4f}")
    _row("scheduler_overlap/rerun_fully_cached", -1.0,
         f"ok={out['rerun_ok']}")


_EXEC_REUSE_BODY = """
    import json, time
    from repro.core.calibrate import fit_cost_model
    from repro.core.plan import plan
    from repro.data.tensors import paper_suite
    from repro.distributed.executor import HooiExecutor
    t = paper_suite(scale=0.12)["delicious-s"]
    core = (10,) * t.ndim
    ex = HooiExecutor(8)
    pl = plan(t, "auto", 8, core_dims=core)
    out = {}
    for run in ("first", "second"):
        t0 = time.perf_counter()
        dec, st = ex.run(t, core, pl, n_invocations=1,
                         seed=0 if run == "first" else 1)
        out[run] = {"total_s": time.perf_counter() - t0,
                    "step_compilations": st.step_compilations,
                    "step_cache_hits": st.step_cache_hits,
                    "uploads": st.uploads,
                    "upload_cache_hit": st.upload_cache_hit,
                    "objective": st.objective,
                    "backends": "/".join(st.comm_backends[n] for n in
                                         sorted(st.comm_backends)),
                    "fit": st.fits[-1]}
    cm = fit_cost_model(ex.calibration_samples())
    out["calibration"] = {"flop_rate": cm.flop_rate,
                          "net_bandwidth": cm.net_bandwidth,
                          "source": cm.source}
    out["executor"] = ex.stats()
    print("JSON::" + json.dumps(out))
"""


@_in_child
def bench_executor_reuse() -> None:
    """Acceptance: the second HooiExecutor.run() on a cached plan performs
    no new jit compilations and no new host->device uploads; the measured
    sweeps also yield a fitted CostModel for the selector."""
    out = _run_subprocess_bench(_EXEC_REUSE_BODY)
    for run in ("first", "second"):
        rec = out[run]
        _row(f"executor_reuse/{run}", rec["total_s"] * 1e6,
             f"compilations={rec['step_compilations']};"
             f"uploads={rec['uploads']};"
             f"upload_cache_hit={rec['upload_cache_hit']};"
             f"objective={rec['objective']};backends={rec['backends']};"
             f"fit={rec['fit']:.4f}")
    second = out["second"]
    ok = second["step_compilations"] == 0 and second["uploads"] == 0
    speedup = out["first"]["total_s"] / max(second["total_s"], 1e-9)
    _row("executor_reuse/second_fully_cached", second["total_s"] * 1e6,
         f"ok={ok};first_vs_second={speedup:.1f}x;"
         f"calibrated_flop_rate={out['calibration']['flop_rate']:.2e};"
         f"source={out['calibration']['source']}")


_POOL_THROUGHPUT_BODY = """
    import json, time
    import numpy as np
    from repro.core.plan import plan_cache_clear
    from repro.data.tensors import synth_tensor
    from repro.distributed.executor import HooiExecutor
    from repro.engine import ExecutorPool, StreamRouter
    from repro.engine.scheduler import StreamScheduler
    from repro.streaming import StreamingTensor

    core = (8, 8, 8)
    n_streams = 8
    tensors = [synth_tensor((220, 200, 180), 40_000,
                            alphas=(1.2, 1.05, 1.05), hub_fraction=0.1,
                            hub_modes=(0,), seed=s) for s in range(n_streams)]
    out = {"n_streams": n_streams}

    # one-time warmup: platform startup charged to neither contender
    warm = synth_tensor((24, 20, 18), 500, seed=99)
    HooiExecutor(2).run(warm, (2, 2, 2), "lite", n_invocations=1)

    import jax
    devs = jax.devices()

    # --- single executor (P=2), one scheduler pipeline
    plan_cache_clear()
    ex = HooiExecutor(2)
    t0 = time.perf_counter()
    with StreamScheduler(ex, core, n_invocations=1, workers=2,
                         pad_geometric=True) as sched:
        for i, t in enumerate(tensors):
            sched.submit(t, seed=i, deadline_s=600.0)
        res_single = sched.drain()
    single_wall = time.perf_counter() - t0
    out["single"] = {
        "wall_s": single_wall,
        "streams_per_s": n_streams / single_wall,
        "slo_hit": sum(1 for r in res_single if r.slo_met),
        "objective": sorted({r.stats.objective for r in res_single}),
        "backends": sorted({b for r in res_single
                            for b in r.stats.comm_backends.values()}),
    }

    # --- pool of 2 executors (P=2 each) on disjoint device slices
    plan_cache_clear()
    t0 = time.perf_counter()
    with ExecutorPool(2, 2, core, devices=devs[:4], workers=2,
                      n_invocations=1, pad_geometric=True) as pool:
        router = StreamRouter(pool, max_pending=2 * n_streams)
        for i, t in enumerate(tensors):
            router.submit(t, seed=i, deadline_s=600.0)
        res_pool = router.drain()
        pool_wall = time.perf_counter() - t0
        st = router.stats()
        out["pool"] = {
            "wall_s": pool_wall,
            "streams_per_s": n_streams / pool_wall,
            "slo_hit": st.slo_hit,
            "slo_miss": st.slo_miss,
            "lanes_used": sorted({r.stats.lane for r in res_pool}),
            "queue_wait_s": st.queue_wait_s,
            "rejected": st.rejected,
            "objective": sorted({r.stats.objective for r in res_pool}),
            "backends": sorted({b for r in res_pool
                                for b in r.stats.comm_backends.values()}),
        }
    out["speedup"] = single_wall / max(pool_wall, 1e-9)
    print("JSON::" + json.dumps(out))
"""


@_in_child
def bench_pool_throughput() -> None:
    """Acceptance: a 2-executor pool on disjoint device slices serves a
    queue of concurrent streams at higher throughput (streams/sec) than a
    single executor pipeline, with every stream's SLO accounted."""
    out = _run_subprocess_bench(_POOL_THROUGHPUT_BODY)
    single, pool = out["single"], out["pool"]
    n = out["n_streams"]
    _row("pool_throughput/single_executor", single["wall_s"] * 1e6,
         f"streams_per_s={single['streams_per_s']:.3f};"
         f"slo_hit={single['slo_hit']}/{n};"
         f"objective={','.join(single['objective'])};"
         f"backends={','.join(single['backends'])}")
    _row("pool_throughput/pool_of_2", pool["wall_s"] * 1e6,
         f"streams_per_s={pool['streams_per_s']:.3f};"
         f"slo_hit={pool['slo_hit']}/{n};"
         f"lanes_used={pool['lanes_used']};"
         f"queue_wait_s={pool['queue_wait_s']:.2f};"
         f"rejected={pool['rejected']};"
         f"objective={','.join(pool['objective'])};"
         f"backends={','.join(pool['backends'])}")
    _row("pool_throughput/speedup", pool["wall_s"] * 1e6,
         f"single_vs_pool={out['speedup']:.2f}x;"
         f"ok={out['speedup'] > 1.0}")


_OBJECTIVES_BODY = """
    import json, os, tempfile, time
    import numpy as np
    from repro.core.coo import SparseTensor, write_tns
    from repro.data.frostt import iter_tns_batches, load_tns
    from repro.distributed.dist_hooi import dist_hooi
    from repro.distributed.executor import HooiExecutor
    from repro.engine.objective import holdout_mask, predict_at_coords
    from repro.engine.scheduler import StreamScheduler
    from repro.streaming import StreamingTensor

    out = {}
    rng = np.random.default_rng(0)

    # ground truth: an exact rank-(4,4,4) model sampled at random coords;
    # the held-out fraction of stored entries is then CORRUPTED with large
    # garbage values (untrusted measurements). Zero-corruption would be a
    # wash by construction — under the implicit-zero Frobenius objective,
    # masking an entry and storing it as zero are the same statement (see
    # docs/objectives.md) — so the corruption must be nonzero for the split
    # to matter. The unmasked baseline trains on everything and chases the
    # garbage; completion drops exactly those entries. Both are scored at
    # the held-out coords against the TRUE values.
    # a small shape sampled densely (~70% of cells observed) keeps the
    # sparse tensor close to its dense low-rank generator, so the sweeps
    # can actually recover the model and the held-out scores separate
    shape, core = (24, 20, 18), (4, 4, 4)
    g = rng.standard_normal(core)
    us = [np.linalg.qr(rng.standard_normal((L, r)))[0]
          for L, r in zip(shape, core)]
    nnz = 6000
    coords = np.unique(np.stack([rng.integers(0, L, 2 * nnz) for L in shape],
                                axis=1), axis=0)[:nnz]
    true_vals = predict_at_coords(g, us, coords)
    true_vals = true_vals / max(np.abs(true_vals).max(), 1e-12)

    frac, hseed = 0.2, 0  # CompletionObjective defaults
    held = holdout_mask(len(coords), frac, hseed)
    vals = true_vals.copy()
    vals[held] = rng.standard_normal(int(held.sum())) \
        * 5.0 * float(true_vals.std())
    t = SparseTensor(coords=coords, values=vals, shape=shape)

    recs = {}
    for name, obj in (("tucker_baseline", "tucker"),
                      ("completion", "completion")):
        t0 = time.perf_counter()
        dec, stats = dist_hooi(t, core, 8, scheme="medium", n_invocations=2,
                               seed=0, objective=obj)
        dt = time.perf_counter() - t0
        pred = predict_at_coords(dec.core, dec.factors, coords[held])
        rmse = float(np.sqrt(np.mean((pred - true_vals[held]) ** 2)))
        om = stats.objective_metrics or {}
        recs[name] = {"took_s": dt, "fit": stats.fits[-1],
                      "objective": stats.objective,
                      "backends": "/".join(stats.comm_backends[n] for n in
                                           sorted(stats.comm_backends)),
                      "heldout_rmse_vs_truth": rmse,
                      "masked_holdout_rmse_traj": om.get("holdout_rmse")}
    out["recovery"] = recs
    out["completion_beats_baseline"] = (
        recs["completion"]["heldout_rmse_vs_truth"]
        < recs["tucker_baseline"]["heldout_rmse_vs_truth"])

    # nonnegative ADMM Tucker on the same coords, from a nonneg generator
    # with block-supported (near-orthogonal) factor columns — the parts-
    # based structure NN Tucker is meant to recover
    us_nn = []
    for L in shape:
        f = np.zeros((L, 4))
        for j in range(4):
            lo, hi = j * L // 4, (j + 1) * L // 4
            f[lo:hi, j] = np.abs(rng.standard_normal(hi - lo)) + 0.1
        us_nn.append(f)
    g_nn = np.abs(rng.standard_normal(core))
    vals_nn = predict_at_coords(g_nn, us_nn, coords)
    vals_nn = vals_nn / max(vals_nn.max(), 1e-12)
    t_nn = SparseTensor(coords=coords, values=vals_nn, shape=shape)
    dec, stats = dist_hooi(t_nn, core, 8, scheme="medium", n_invocations=2,
                           seed=0, objective="nn")
    out["nn"] = {"fit": stats.fits[-1], "objective": stats.objective,
                 "backends": "/".join(stats.comm_backends[n] for n in
                                      sorted(stats.comm_backends)),
                 "min_factor": float(min(np.asarray(f).min()
                                         for f in dec.factors))}

    # FROSTT-format fixture -> StreamingTensor -> StreamScheduler, masked
    # completion over the growing stream (the scheduler's refresh ladder
    # runs on the objective's view)
    tmp = tempfile.mkdtemp()
    path = os.path.join(tmp, "fixture.tns")
    write_tns(path, t)
    full = load_tns(path)
    batches = list(iter_tns_batches(path, batch_nnz=2000))
    stream = StreamingTensor(full.shape, name="frostt-fixture")
    ex = HooiExecutor(8)
    with StreamScheduler(ex, core, scheme="auto", n_invocations=1,
                         objective="completion", workers=2) as sched:
        stream.append(*batches[0])
        r1 = sched.submit(stream, seed=0).result()
        for c, v in batches[1:]:
            stream.append(c, v)
        r2 = sched.submit(stream, seed=1).result()
    om = r2.stats.objective_metrics or {}
    out["frostt_stream"] = {
        "batches": len(batches), "nnz": int(full.nnz),
        "first_decision": r1.decision, "first_fit": r1.fits[-1],
        "final_decision": r2.decision, "final_fit": r2.fits[-1],
        "objective": r2.stats.objective,
        "backends": "/".join(r2.stats.comm_backends[n] for n in
                             sorted(r2.stats.comm_backends)),
        "holdout_rmse": (om.get("holdout_rmse") or [None])[-1],
    }
    print("JSON::" + json.dumps(out))
"""


@_in_child
def bench_objectives() -> None:
    """Acceptance: masked completion beats the unmasked Tucker baseline on
    held-out RMSE when a fraction of stored entries is corrupted; NN-ADMM
    emits exactly nonnegative factors; and a FROSTT-format .tns fixture
    streams end-to-end through StreamingTensor -> StreamScheduler under
    the completion objective."""
    out = _run_subprocess_bench(_OBJECTIVES_BODY)
    for name, rec in out["recovery"].items():
        traj = rec["masked_holdout_rmse_traj"]
        traj_s = ("none" if not traj
                  else "/".join(f"{x:.3f}" for x in traj))
        _row(f"objectives/{name}", rec["took_s"] * 1e6,
             f"heldout_rmse_vs_truth={rec['heldout_rmse_vs_truth']:.4f};"
             f"fit={rec['fit']:.4f};objective={rec['objective']};"
             f"backends={rec['backends']};masked_rmse_traj={traj_s}")
    base = out["recovery"]["tucker_baseline"]["heldout_rmse_vs_truth"]
    comp = out["recovery"]["completion"]["heldout_rmse_vs_truth"]
    _row("objectives/recovery_acceptance", -1.0,
         f"ok={out['completion_beats_baseline']};"
         f"baseline_over_completion_rmse={base / max(comp, 1e-12):.2f}x")
    nn = out["nn"]
    _row("objectives/nn_admm", -1.0,
         f"fit={nn['fit']:.4f};min_factor={nn['min_factor']:.3e};"
         f"nonneg_ok={nn['min_factor'] >= 0.0};objective={nn['objective']};"
         f"backends={nn['backends']}")
    fs = out["frostt_stream"]
    rmse_s = ("none" if fs["holdout_rmse"] is None
              else f"{fs['holdout_rmse']:.4f}")
    _row("objectives/frostt_stream", -1.0,
         f"batches={fs['batches']};nnz={fs['nnz']};"
         f"first_decision={fs['first_decision']};"
         f"final_decision={fs['final_decision']};"
         f"final_fit={fs['final_fit']:.4f};holdout_rmse={rmse_s};"
         f"objective={fs['objective']};backends={fs['backends']}")


_SKETCH_WARMSTART_BODY = """
    import json, time
    import numpy as np
    from repro.core.hooi import hooi
    from repro.core.lanczos import lanczos_niter
    from repro.core.sketch import (DEFAULT_POWER_ITERS, sketch_block_size,
                                   sketch_niter)
    from repro.data.tensors import synth_tensor
    from repro.distributed.executor import HooiExecutor
    from repro.engine import count_z_passes
    from repro.engine.scheduler import StreamScheduler
    from repro.streaming import StreamingTensor

    out = {}

    # --- Part A: counted oracle Z passes, full-GK vs sketch warm start.
    # Paper-default K=10 is where the halved refinement budget pays: the
    # full driver runs ceil(2K/s) block iterations, the sketched one
    # ceil(K/s) plus one seed product and one power iteration.
    t = synth_tensor((120, 100, 90), 20_000, alphas=(1.1, 1.0, 1.0),
                     hub_fraction=0.1, hub_modes=(0,), seed=5)
    core = (10, 10, 10)
    oracle = {}
    for name, ws in (("full_gk", "none"), ("sketch", "sketch")):
        per_mode = []
        for n in range(t.ndim):
            khat = int(np.prod([core[j] for j in range(t.ndim) if j != n]))
            if ws == "sketch":
                s_sk = sketch_block_size(core[n], t.shape[n], khat, 1)
                niter = sketch_niter(core[n], t.shape[n], khat, s_sk)
                per_mode.append(count_z_passes(
                    niter, False, warm_start="sketch",
                    power_iters=DEFAULT_POWER_ITERS))
            else:
                niter = lanczos_niter(core[n], t.shape[n], khat, 1)
                per_mode.append(count_z_passes(niter, False))
        t0 = time.perf_counter()
        _, traj = hooi(t, core, n_invocations=6, seed=0, warm_start=ws)
        oracle[name] = {"wall_s": time.perf_counter() - t0,
                       "z_passes_per_mode": per_mode,
                       "z_passes_total": sum(per_mode),
                       "final_fit": traj[-1]}
    out["oracle"] = oracle
    # warm_start="none" must reproduce the historical trajectory bitwise
    _, t_def = hooi(t, core, n_invocations=2, seed=0)
    _, t_none = hooi(t, core, n_invocations=2, seed=0, warm_start="none")
    out["none_bitwise"] = bool(t_def == t_none)

    # --- Part B: adaptive per-mode rank over a drifting stream. Phase 1
    # appends samples of a coherent rank-8 model (tail energy pushes ranks
    # up); phase 2 appends a much stronger rank-2 model (spectra collapse,
    # ranks come back down). Dense-ish non-replacement sampling keeps the
    # sparse view close to its low-rank generator so the sketch spectra
    # are informative.
    rng = np.random.default_rng(7)
    shape = (32, 28, 24)
    NN = shape[0] * shape[1] * shape[2]

    def model(R, scale):
        fac = [np.linalg.qr(rng.normal(size=(s, R)))[0] for s in shape]
        g = rng.normal(size=(R,) * 3) * scale
        return np.einsum("abc,ia,jb,kc->ijk", g, *fac)

    def sample(dense, n):
        flat = rng.choice(NN, n, replace=False)
        coords = np.stack(np.unravel_index(flat, shape), 1)
        return coords, dense[tuple(coords.T)]

    d8 = model(8, 1.0)
    d2 = model(2, 300.0)
    ex = HooiExecutor(4)
    stream = StreamingTensor(shape, name="adaptive-rank")
    steps = []
    with StreamScheduler(ex, (4, 4, 4), n_invocations=3,
                         warm_start="sketch", adaptive_rank=True,
                         rank_policy=dict(k_max=8, k_min=2, grow_thresh=0.45,
                                          shrink_thresh=0.3)) as sched:
        for phase, (dense, n, reps) in enumerate(
                ((d8, 2000, 3), (d2, 5000, 4))):
            for _ in range(reps):
                stream.append(*sample(dense, n))
                r = sched.submit(stream).result()
                rec = r.stats.rank_trajectory[-1]
                steps.append({"phase": phase,
                              "core_dims": list(rec["core_dims"]),
                              "modeled_total_s": rec["modeled_total_s"],
                              "decision": r.decision,
                              "fit": r.fits[-1]})
    dims = [s["core_dims"] for s in steps]
    grew = shrank = False
    for a, b in zip(dims, dims[1:]):
        grew = grew or any(y > x for x, y in zip(a, b))
        shrank = shrank or any(y < x for x, y in zip(a, b))
    out["adaptive"] = {"steps": steps, "grew": grew, "shrank": shrank}
    print("JSON::" + json.dumps(out))
"""


@_in_child
def bench_sketch_warmstart() -> None:
    """Acceptance: the sketched range-finder warm start cuts counted
    oracle Z passes >=1.5x vs the full Golub-Kahan budget at equal final
    fit (within 1e-3); the adaptive-rank scheduler demonstrably grows AND
    shrinks a mode's rank mid-stream with the plan cost re-scored at each
    rank change."""
    out = _run_subprocess_bench(_SKETCH_WARMSTART_BODY)
    oracle = out["oracle"]
    for name, rec in oracle.items():
        _row(f"sketch_warmstart/{name}", rec["wall_s"] * 1e6,
             f"z_passes_per_mode={'/'.join(map(str, rec['z_passes_per_mode']))};"
             f"z_passes_sweep_total={rec['z_passes_total']};"
             f"final_fit={rec['final_fit']:.4f}")
    ratio = oracle["full_gk"]["z_passes_total"] \
        / max(oracle["sketch"]["z_passes_total"], 1)
    delta = abs(oracle["full_gk"]["final_fit"] - oracle["sketch"]["final_fit"])
    _row("sketch_warmstart/oracle_acceptance", -1.0,
         f"passes_drop={ratio:.2f}x;ok={ratio >= 1.5};"
         f"fit_delta={delta:.2e};fit_ok={delta < 1e-3};"
         f"none_bitwise={out['none_bitwise']}")
    ad = out["adaptive"]
    for i, s in enumerate(ad["steps"]):
        _row(f"sketch_warmstart/adaptive_step{i}", -1.0,
             f"phase={s['phase']};core_dims={'x'.join(map(str, s['core_dims']))};"
             f"modeled_total_s={s['modeled_total_s']:.3e};"
             f"decision={s['decision']};fit={s['fit']:.4f}")
    _row("sketch_warmstart/adaptive_acceptance", -1.0,
         f"grew={ad['grew']};shrank={ad['shrank']};"
         f"ok={ad['grew'] and ad['shrank']}")


_MIXED_BACKENDS_BODY = """
    import json, time
    import numpy as np
    from repro.core.calibrate import CostModel, set_cost_model
    from repro.core.plan import plan, plan_cache_clear
    from repro.data.tensors import synth_tensor
    from repro.distributed.dist_hooi import dist_hooi

    out = {}
    t = synth_tensor((160, 140, 120), 30_000, alphas=(1.4, 1.0, 1.0),
                     hub_fraction=0.15, hub_modes=(0,), seed=7)
    core = (8, 8, 8)
    try:
        # per-mode baseline/liteopt byte ratios decide the psum/boundary
        # crossover; a bandwidth ratio strictly between the extremes makes
        # the auto selector split the modes across backends
        pl = plan(t, "medium", 8, core_dims=core, path="auto",
                  use_cache=False)
        ratios = {n: pl.comm(n)["baseline_bytes"]
                  / max(pl.comm(n)["liteopt_bytes"], 1.0)
                  for n in range(t.ndim)}
        out["byte_ratios"] = {str(n): r for n, r in ratios.items()}
        rs = sorted(ratios.values())
        mid = float(np.sqrt(rs[0] * rs[-1]))
        configs = (
            ("default", None),
            ("psum_favored", CostModel(psum_bandwidth=1e12,
                                       boundary_bandwidth=1e9,
                                       source="bench:psum_favored")),
            ("split", CostModel(psum_bandwidth=1e10 * mid,
                                boundary_bandwidth=1e10,
                                source="bench:split")),
        )
        for name, cm in configs:
            set_cost_model(cm)
            plan_cache_clear()
            t0 = time.perf_counter()
            dec, stats = dist_hooi(t, core, 8, scheme="medium",
                                   n_invocations=1, path="auto", seed=0)
            bk = {str(n): stats.comm_backends[n]
                  for n in sorted(stats.comm_backends)}
            out[name] = {"wall_s": time.perf_counter() - t0,
                         "backends": bk, "fit": stats.fits[-1],
                         "mixed": len(set(bk.values())) > 1}
    finally:
        set_cost_model(None)
    print("JSON::" + json.dumps(out))
"""


@_in_child
def bench_mixed_backends() -> None:
    """Acceptance: ``path="auto"`` under a CostModel with skewed
    per-backend bandwidths picks a *heterogeneous* per-mode comm-backend
    map (some modes psum, some boundary) and records the chosen map."""
    out = _run_subprocess_bench(_MIXED_BACKENDS_BODY)
    ratios = ";".join(f"mode{n}={r:.3f}"
                      for n, r in sorted(out["byte_ratios"].items()))
    _row("mixed_backends/byte_ratios", -1.0, ratios)
    for name in ("default", "psum_favored", "split"):
        rec = out[name]
        bk = "/".join(rec["backends"][k] for k in sorted(rec["backends"]))
        _row(f"mixed_backends/{name}", rec["wall_s"] * 1e6,
             f"backends={bk};mixed={rec['mixed']};fit={rec['fit']:.4f}")
    _row("mixed_backends/acceptance", -1.0,
         f"split_mixed_ok={out['split']['mixed']};"
         f"uniform_default_ok={not out['default']['mixed']}")


_STOCH_REFRESH_BODY = """
    import json, time
    import numpy as np
    from repro.core.plan import plan as make_plan, plan_cache_clear
    from repro.data.tensors import synth_tensor
    from repro.distributed.executor import HooiExecutor
    from repro.engine.scheduler import StreamScheduler
    from repro.streaming import StreamingTensor

    core = (8, 8, 8)
    shape = (220, 200, 180)
    base = synth_tensor(shape, 40_000, seed=0)
    rng = np.random.default_rng(123)
    batches = []
    for b in range(6):
        c = np.stack([rng.integers(0, L, 3000) for L in shape], axis=1)
        batches.append((c, rng.standard_normal(3000)))

    # one-time warmup: platform startup charged to neither arm
    HooiExecutor(2).run(synth_tensor((24, 20, 18), 500, seed=99),
                        (2, 2, 2), "lite", n_invocations=1)

    def run_arm(sample):
        plan_cache_clear()
        ex = HooiExecutor(8)
        stream = StreamingTensor.from_tensor(base, name="bench")
        kw = {}
        if sample:
            kw = dict(sample_fraction=0.25, sample_seed=7, replay_nnz=1024,
                      stochastic_tol=0.25, correction_every=0)
        recs = []
        with StreamScheduler(ex, core, n_invocations=2, workers=2,
                             **kw) as sched:
            first = sched.submit(stream, seed=0).result()
            for i, (c, v) in enumerate(batches):
                stream.append(c, v)
                r = sched.submit(stream, seed=1 + i).result()
                recs.append({"decision": r.decision, "run_s": r.run_s,
                             "compilations": r.stats.step_compilations,
                             "uploads": r.stats.uploads,
                             "fit": float(r.stats.fits[-1]),
                             "sample_nnz": r.stats.sample_nnz})
        return {"first_fit": float(first.stats.fits[-1]), "appends": recs,
                "final_fit": recs[-1]["fit"]}

    out = {"baseline": run_arm(False), "stochastic": run_arm(True)}

    # rerun contract on the refine path itself: the same refine twice on
    # one executor — second run must be fully cached and bitwise equal
    stream = StreamingTensor.from_tensor(base, name="rerun")
    snap0 = stream.snapshot()
    pl = make_plan(snap0, "lite", 8, core_dims=core, pad_geometric=True)
    ex = HooiExecutor(8)
    dec, _ = ex.run(snap0, core, pl, n_invocations=1, seed=0)
    stream.append(*batches[0])
    snap1 = stream.snapshot()
    runs = []
    for rep in range(2):
        rdec, rst = ex.run_stochastic(
            snap1, core, pl, init_factors=dec.factors,
            covered_nnz=snap0.nnz, sample_fraction=0.25, sample_seed=7,
            seed=1)
        runs.append({"compilations": rst.step_compilations,
                     "uploads": rst.uploads,
                     "fits": [float(f) for f in rst.fits]})
    out["rerun"] = {"compilations": runs[1]["compilations"],
                    "uploads": runs[1]["uploads"],
                    "fits_equal": runs[0]["fits"] == runs[1]["fits"]}
    print("JSON::" + json.dumps(out))
"""


@_in_child
def bench_stochastic_refresh() -> None:
    """Acceptance for the stochastic-refine rung: over a 6-batch append
    stream, sampled refines cut per-append device time >= 3x vs full
    sweeps while the final fit stays within 5e-2 of the full-sweep
    trajectory, and rerunning the same refine is fully cached (0/0)."""
    out = _run_subprocess_bench(_STOCH_REFRESH_BODY)
    base, stoch = out["baseline"], out["stochastic"]
    refines = [r for r in stoch["appends"]
               if r["decision"] == "stochastic-refine"]
    for arm, recs in (("full", base["appends"]),
                      ("sampled", stoch["appends"])):
        decisions = "/".join(r["decision"] for r in recs)
        # append 0 pays the arm's one-time step compile (the stochastic
        # minibatch step for the sampled arm); steady state is the rest
        steady = [r["run_s"] for r in recs[1:]]
        mean_s = sum(steady) / len(steady)
        per = "/".join(f"{r['run_s']:.2f}" for r in recs)
        _row(f"stochastic_refresh/{arm}_appends", mean_s * 1e6,
             f"decisions={decisions};per_append_s={per};"
             f"compilations={sum(r['compilations'] for r in recs[1:])};"
             f"final_fit={recs[-1]['fit']:.4f}")
    full_s = [r["run_s"] for r in base["appends"][1:]]
    refine_s = [r["run_s"] for r in stoch["appends"][1:]
                if r["decision"] == "stochastic-refine"]
    speedup = (sum(full_s) / len(full_s)) / max(
        sum(refine_s) / max(len(refine_s), 1), 1e-9) if refine_s else 0.0
    fit_delta = abs(stoch["final_fit"] - base["final_fit"])
    ok = (speedup >= 3.0 and fit_delta <= 5e-2
          and len(refines) == len(stoch["appends"]))
    _row("stochastic_refresh/acceptance", -1.0,
         f"ok={ok};speedup={speedup:.1f}x;fit_delta={fit_delta:.4f};"
         f"refines={len(refines)}/{len(stoch['appends'])};"
         f"sample_nnz={refines[0]['sample_nnz'] if refines else None}")
    rr = out["rerun"]
    rerun_ok = (rr["compilations"] == 0 and rr["uploads"] == 0
                and rr["fits_equal"])
    _row("stochastic_refresh/rerun_fully_cached", -1.0,
         f"ok={rerun_ok};compilations={rr['compilations']};"
         f"uploads={rr['uploads']};fits_bitwise_equal={rr['fits_equal']}")


BENCHES = [
    bench_dataset_suite,
    bench_metrics,
    bench_comm_volume,
    bench_scaling,
    bench_distribution_time,
    bench_memory,
    bench_kernel_oracle,
    bench_kernel_ttm,
    bench_kernel_roofline,
    bench_auto_selection,
    # the child-process benches below run before all of the above
    bench_plan_cache,
    bench_executor_reuse,
    bench_scheduler_overlap,
    bench_pool_throughput,
    bench_objectives,
    bench_sketch_warmstart,
    bench_mixed_backends,
    bench_stochastic_refresh,
    bench_hooi_time,  # slowest child
]


def bench_environment() -> dict:
    """Provenance stamp written into every ``BENCH_<name>.json``.

    Bench artifacts accumulate across PRs; without the git SHA, timestamp,
    jax version and device kind they are not comparable as a trajectory.
    Only the git SHA degrades to a sentinel (a copy without ``.git``); a
    device that cannot be queried raises — an artifact must never claim a
    device it did not see.
    """
    import datetime

    import jax

    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(_SRC),
        ).stdout.strip() or "unknown"
    except Exception:  # noqa: BLE001 — git absent / not a checkout
        sha = "unknown"
    dev = jax.devices()[0]
    return {
        "git_sha": sha,
        "timestamp": datetime.datetime.now(datetime.timezone.utc).isoformat(),
        "jax_version": jax.__version__,
        "device_kind": dev.device_kind,
        "platform": dev.platform,
    }


def _artifact_path(out_dir: str, bench_name: str) -> str:
    """``BENCH_<slug>.json`` inside ``out_dir`` — guarded.

    The slug comes from a function name today, but bench registries have
    grown dynamic entries before; a slug with a path separator (or any
    char outside ``[A-Za-z0-9_.-]``) could silently write an artifact
    outside the artifact dir, and CI would upload nothing while reading
    all green. Both the slug and the joined path are checked."""
    import re

    # bench_scheduler_overlap -> BENCH_scheduler_overlap.json
    slug = bench_name.removeprefix("bench_")
    if not re.fullmatch(r"[A-Za-z0-9_.-]+", slug):
        raise RuntimeError(
            f"bench name {bench_name!r} yields unsafe artifact slug "
            f"{slug!r} — refusing to write outside the artifact dir")
    out_real = os.path.realpath(out_dir)
    path = os.path.realpath(os.path.join(out_dir, f"BENCH_{slug}.json"))
    if os.path.dirname(path) != out_real:
        raise RuntimeError(
            f"artifact path {path!r} escapes the artifact dir {out_real!r}")
    return path


def run_benches(benches, out_dir: str | None = None) -> list[str]:
    """Run ``benches``, writing one ``BENCH_<name>.json`` each to
    ``out_dir`` (the perf-trajectory artifacts CI uploads). A bench that
    raises still produces a JSON (rows so far + the error) and does not
    stop the rest; an *empty* bench list is refused loudly — a filtering
    bug upstream would otherwise write no artifacts and read as "all
    green". A bench (or a buggy artifact path) that drops ``BENCH_*.json``
    files *outside* ``out_dir`` is also refused loudly: stray artifacts
    in the working or benchmarks directory would never be uploaded, and
    the perf trajectory would silently lose its data points. Benches marked
    ``_in_child`` run first and the environment stamp is taken after the
    last bench, so no child waits on an accelerator this process holds.
    Returns the written paths."""
    import glob
    import json

    benches = sorted(benches, key=lambda b: not getattr(b, "in_child", False))
    if not benches:
        raise ValueError(
            "run_benches() got an empty bench list — refusing to silently "
            "produce no artifacts (check the bench selection/filter)")
    out_dir = out_dir or os.environ.get("BENCH_OUT_DIR") or "."
    os.makedirs(out_dir, exist_ok=True)
    out_real = os.path.realpath(out_dir)
    # dirs a misdirected artifact would plausibly land in
    scan_dirs = sorted({os.path.realpath(os.getcwd()),
                        os.path.realpath(os.path.dirname(
                            os.path.abspath(__file__)))} - {out_real})
    before = {d: set(glob.glob(os.path.join(d, "BENCH_*.json")))
              for d in scan_dirs}
    results = []
    for bench in benches:
        _ROWS.clear()
        err = None
        t0 = time.perf_counter()
        try:
            bench()
        except Exception as e:  # noqa: BLE001
            err = f"{type(e).__name__}: {e}"
            _row(bench.__name__, -1.0, f"ERROR={err}")
        dt = time.perf_counter() - t0
        print(f"# {bench.__name__} took {dt:.1f}s", file=sys.stderr)
        results.append({"bench": bench.__name__, "took_s": dt,
                        "error": err, "rows": list(_ROWS)})
    meta = bench_environment()
    written = []
    for res in results:
        path = _artifact_path(out_dir, res["bench"])
        with open(path, "w") as f:
            json.dump(dict(res, meta=meta), f, indent=1)
        written.append(path)
    stray = sorted(p for d in scan_dirs
                   for p in set(glob.glob(os.path.join(d, "BENCH_*.json")))
                   - before[d])
    if stray:
        raise RuntimeError(
            f"bench run dropped BENCH_*.json artifacts outside the "
            f"artifact dir {out_real!r}: {stray} — these would never be "
            f"uploaded; route them through --out-dir/BENCH_OUT_DIR")
    return written


def list_benches() -> list[tuple[str, str]]:
    """(name, one-line summary) for every registered bench — what
    ``--list`` prints, so the names are discoverable without reading
    source."""
    out = []
    for bench in BENCHES:
        doc = (bench.__doc__ or "").strip().splitlines()
        out.append((bench.__name__, doc[0] if doc else ""))
    return out


def select_benches(names: list[str]) -> list:
    """Resolve user-supplied names (with or without the ``bench_`` prefix)
    to bench functions; unknown names fail loudly with the full menu."""
    by_name = {b.__name__: b for b in BENCHES}
    picked = []
    for raw in names:
        name = raw if raw.startswith("bench_") else f"bench_{raw}"
        if name not in by_name:
            known = ", ".join(sorted(by_name))
            raise SystemExit(f"unknown bench {raw!r}; known: {known}")
        picked.append(by_name[name])
    return picked


def main(argv: list[str] | None = None) -> None:
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--list" in argv:
        for name, summary in list_benches():
            print(f"{name:28s} {summary}")
        return
    out_dir = None
    if "--out-dir" in argv:
        i = argv.index("--out-dir")
        if i + 1 >= len(argv):
            sys.exit("--out-dir requires a directory argument")
        out_dir = argv[i + 1]
        del argv[i:i + 2]
    unknown = [a for a in argv if a.startswith("-")]
    if unknown:
        # a typo'd flag must not silently fall through to "run everything"
        sys.exit(f"unknown option(s): {' '.join(unknown)} "
                 "(supported: --list, --out-dir DIR, bench names)")
    names = list(argv)
    benches = select_benches(names) if names else BENCHES
    from repro.runtime import enable_compile_cache

    enable_compile_cache()
    print("name,us_per_call,derived")
    run_benches(benches, out_dir)


if __name__ == "__main__":
    main()
