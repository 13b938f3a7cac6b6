#!/usr/bin/env python3
"""Smoke run on a TPU: sparse HOOI end to end through the user entry points.

    python chip_smoke.py                # one chip: kernel, full-size, stream
    python chip_smoke.py --four-chips   # four chips: P=4 vs P=1, nothing else

Phases on one chip (one process):

* kernel — the nell2-s mirror through ``dist_hooi(P=1)`` with the Z-build
  kernel on auto and off, plain and with the fused Z-build/oracle kernels;
  the Pallas kernels run compiled for the modes the VMEM gate admits, fits
  agree with the jnp reference, and the decomposition is checked in float64
  on the host.
* full-size — a nell-2 mirror (FROSTT mode lengths 12,092 x 9,184 x
  28,818, nell2-s skew) through ``dist_hooi(scheme="auto", P=1)`` twice; the
  second call, on the cached plan, compiles and uploads nothing.
* stream — the same tensor through ``StreamScheduler``: a first submit, an
  unchanged rerun (0 compilations, 0 uploads), a 1% append at existing
  coordinates and a submit of the new version.

``--four-chips`` runs the published-size mirror through ``dist_hooi(P=4,
scheme="lite")`` on the psum and boundary comm backends and compares both
with the P=1 run of the same tensor and seed.

Every tensor is generated from ``--seed``. Each check raises on failure, so
a failed phase exits non-zero. The last line printed is one JSON object
naming the device; the script refuses to run without a TPU. Times printed
are one-off wall times of this smoke run (after ``block_until_ready``),
compilation included where a call compiles — not benchmark numbers.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

CORE = (10, 10, 10)  # the paper's K per mode
NELL2_SHAPE = (12_092, 9_184, 28_818)  # FROSTT nell-2 mode lengths
NELL2_NNZ = 76_879_419  # FROSTT nell-2 published nonzeros
NELL2_ALPHAS = (0.9, 0.9, 1.0)  # nell2-s Zipf skew (repro.data.tensors)
# One chip: the run's 1200 s limit, not the 16 GB of HBM, cuts the
# full-size phase (see PERF.md, "Cells"). Device memory at P=1 is about
# 104 B per nonzero (80 B resident uploads, 24 B step scratch), so the
# published size would fit; host partitioning and the stream's second
# plan would not fit the time.
ONE_CHIP_NNZ = 16_000_000
FIT_TOL = 1e-3  # fit agreement across Z-build variants and rank counts
ORTH_TOL = 1e-4  # max |F^T F - I|
CORE_RTOL = 1e-3  # returned core vs float64 host recompute (relative)


def log(msg: str) -> None:
    print(msg, flush=True)


def nell2_mirror(nnz: int, seed: int):
    from repro.data.tensors import synth_tensor

    t0 = time.perf_counter()
    t = synth_tensor(NELL2_SHAPE, nnz, NELL2_ALPHAS, seed=seed)
    log(f"[data] nell-2 mirror: {nnz:,} nonzeros drawn, {t.nnz:,} after "
        f"dedup, host {time.perf_counter() - t0:.1f} s")
    return t


def timed_dist_hooi(t, P: int, **kw):
    import jax
    from repro.distributed.dist_hooi import dist_hooi

    t0 = time.perf_counter()
    dec, st = dist_hooi(t, CORE, P, **kw)
    jax.block_until_ready((dec.core, dec.factors))
    return dec, st, time.perf_counter() - t0


def describe(tag: str, st, wall: float) -> None:
    modes = sorted(st.z_kernel)
    log(f"{tag} scheme={st.scheme} z_kernel={st.z_kernel} "
        f"comm_backends={st.comm_backends} "
        f"fits={[round(float(f), 6) for f in st.fits]} "
        f"step_compilations={st.step_compilations} uploads={st.uploads} "
        f"r_pad={[st.r_pad[n] for n in modes]} "
        f"e_pad={[st.e_pad[n] for n in modes]} wall={wall:.2f} s")


def check_fits(tag: str, fits) -> None:
    fits = np.asarray(fits, np.float64)
    assert fits.size and np.all(np.isfinite(fits)), f"{tag}: fits {fits}"
    assert np.all((fits >= 0.0) & (fits <= 1.0)), f"{tag}: fits {fits}"


def host_core(coords, values, factors, chunk: int = 1 << 18) -> np.ndarray:
    """float64 core ``sum_e v_e F0[i] x F1[j] x F2[k]`` on the host."""
    F = [np.asarray(f, np.float64) for f in factors]
    K = [f.shape[1] for f in F]
    G = np.zeros((K[0] * K[1], K[2]))
    for s in range(0, len(values), chunk):
        c = coords[s:s + chunk]
        v = np.asarray(values[s:s + chunk], np.float64)
        ab = (F[0][c[:, 0]] * v[:, None])[:, :, None] * F[1][c[:, 1]][:, None]
        G += ab.reshape(len(v), -1).T @ F[2][c[:, 2]]
    return G.reshape(K)


def check_decomposition(tag: str, t, dec) -> None:
    """Orthonormal factors and a core that matches a float64 recompute."""
    orth = max(float(np.abs(np.asarray(f, np.float64).T
                            @ np.asarray(f, np.float64)
                            - np.eye(f.shape[1])).max())
               for f in dec.factors)
    G = host_core(t.coords, t.values, dec.factors)
    rel = float(np.linalg.norm(np.asarray(dec.core, np.float64) - G)
                / np.linalg.norm(G))
    log(f"{tag} host float64 check: max|F^T F - I|={orth:.2e} "
        f"core rel err={rel:.2e}")
    assert orth < ORTH_TOL, f"{tag}: factors not orthonormal ({orth:.2e})"
    assert rel < CORE_RTOL, f"{tag}: core differs from host ({rel:.2e})"


# ------------------------------------------------------------------ phases
def kernel_phase(seed: int) -> None:
    """nell2-s at scale 1.0: compiled kernels vs the jnp reference."""
    from repro.data.tensors import SUITE_SPECS, synth_tensor

    i, spec = next((i, s) for i, s in enumerate(SUITE_SPECS)
                   if s.name == "nell2-s")
    # paper_suite(scale=1.0, seed=seed)'s nell2-s, built alone
    t = synth_tensor(spec.shape, spec.nnz, spec.alphas,
                     hub_fraction=spec.hub_fraction,
                     hub_modes=spec.hub_modes, seed=seed + i)
    log(f"[kernel] nell2-s {t.shape} nnz={t.nnz:,}")
    variants = {
        "plain": {},
        "fused": dict(fused_zbuild=True, use_fused_oracle=True,
                      lanczos_block=4),
    }
    for name, kw in variants.items():
        dec, st, wall = timed_dist_hooi(t, 1, seed=seed, use_kernel=None,
                                        **kw)
        describe(f"[kernel] {name} use_kernel=auto", st, wall)
        ref_kw = dict(kw, use_fused_oracle=False)
        _, st_ref, wall = timed_dist_hooi(t, 1, seed=seed, use_kernel=False,
                                          **ref_kw)
        describe(f"[kernel] {name} use_kernel=False", st_ref, wall)
        check_fits(f"[kernel] {name}", st.fits)
        assert st.z_kernel == {0: True, 1: True, 2: False}, st.z_kernel
        assert not any(st_ref.z_kernel.values()), st_ref.z_kernel
        assert st.fused_oracle == bool(kw.get("use_fused_oracle"))
        assert st.fused_zbuild == bool(kw.get("fused_zbuild"))
        gap = abs(float(st.fits[-1]) - float(st_ref.fits[-1]))
        log(f"[kernel] {name} final-fit gap kernel vs reference: {gap:.2e}")
        assert gap < FIT_TOL, f"[kernel] {name}: fit gap {gap:.2e}"
        check_decomposition(f"[kernel] {name}", t, dec)


def full_size_phase(t, seed: int) -> None:
    """``dist_hooi(scheme="auto", P=1)`` twice; the rerun is fully cached."""
    runs = []
    for call in (1, 2):
        dec, st, wall = timed_dist_hooi(t, 1, scheme="auto",
                                        n_invocations=2, seed=seed)
        describe(f"[full] call {call}", st, wall)
        check_fits(f"[full] call {call}", st.fits)
        runs.append((dec, st))
    (dec, st1), (_, st2) = runs
    assert st2.step_compilations == 0 and st2.uploads == 0, \
        (st2.step_compilations, st2.uploads)
    assert np.allclose(st1.fits, st2.fits, rtol=0, atol=1e-6), \
        (st1.fits, st2.fits)
    check_decomposition("[full]", t, dec)


def stream_phase(t, seed: int) -> None:
    """StreamScheduler on the full-size tensor: plan, reuse, append."""
    from repro.distributed.executor import HooiExecutor
    from repro.engine.scheduler import StreamScheduler
    from repro.streaming import StreamingTensor

    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    stream = StreamingTensor.from_tensor(t, name="nell2")
    log(f"[stream] stream seeded with {stream.nnz:,} nonzeros, host "
        f"{time.perf_counter() - t0:.1f} s")

    def submit(sched, tag):
        t0 = time.perf_counter()
        r = sched.submit(stream, seed=seed).result()
        st = r.stats
        log(f"[stream] {tag}: decision={r.decision} "
            f"version={r.stream_version} z_kernel={st.z_kernel} "
            f"comm_backends={st.comm_backends} "
            f"fits={[round(float(f), 6) for f in st.fits]} "
            f"step_compilations={st.step_compilations} "
            f"uploads={st.uploads} prepare={r.prepare_s:.1f} s "
            f"sweeps={r.run_s:.2f} s wall={time.perf_counter() - t0:.1f} s")
        check_fits(f"[stream] {tag}", st.fits)
        return r

    with StreamScheduler(HooiExecutor(1), CORE) as sched:
        first = submit(sched, "submit")
        rerun = submit(sched, "rerun, unchanged")
        assert first.decision == "plan", first.decision
        assert rerun.decision == "reuse", rerun.decision
        assert rerun.stats.step_compilations == 0 \
            and rerun.stats.uploads == 0, \
            (rerun.stats.step_compilations, rerun.stats.uploads)
        idx = rng.choice(t.nnz, size=t.nnz // 100, replace=False)
        stream.append(t.coords[idx], 0.1 * rng.standard_normal(len(idx)))
        after = submit(sched, f"after appending {len(idx):,}")
        assert after.stream_version == first.stream_version + 1


def four_chip_phase(t, seed: int) -> None:
    """P=4 (lite) on both comm backends vs P=1 on the same tensor/seed."""
    from repro.core.plan import plan
    from repro.distributed.executor import shared_executor

    _, ref, wall = timed_dist_hooi(t, 1, scheme="lite", n_invocations=2,
                                   seed=seed)
    describe("[4chip] P=1", ref, wall)
    check_fits("[4chip] P=1", ref.fits)
    # one plan costed for path="auto" serves both backend families, so the
    # partitions are built and uploaded once
    t0 = time.perf_counter()
    pl = plan(t, "lite", 4, core_dims=CORE, path="auto")
    log(f"[4chip] P=4 lite plan host {time.perf_counter() - t0:.1f} s")
    for path in ("baseline", "liteopt"):
        _, st, wall = timed_dist_hooi(t, 4, scheme=pl, path=path,
                                      n_invocations=2, seed=seed)
        describe(f"[4chip] P=4 path={path}", st, wall)
        check_fits(f"[4chip] P=4 {path}", st.fits)
        gap = float(np.max(np.abs(np.asarray(st.fits)
                                  - np.asarray(ref.fits))))
        log(f"[4chip] P=4 {path} max fit gap vs P=1: {gap:.2e}")
        assert gap < FIT_TOL, f"[4chip] {path}: fit gap {gap:.2e}"
        placed = shared_executor(4).shard_devices(pl)
        log(f"[4chip] P=4 {path} rank devices per mode: "
            f"{ {n: [d.id for d in ds] for n, ds in placed.items()} }")
        assert placed and all(len(set(ds)) == 4 for ds in placed.values()), \
            placed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the P=4 vs P=1 comparison")
    args = ap.parse_args(argv)

    from repro.runtime import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devs[0].platform}); "
              "refusing to run", file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devs) < need:
        print(f"chip_smoke: needs {need} TPU chips, found {len(devs)}",
              file=sys.stderr)
        return 2
    log(f"[env] jax {jax.__version__} devices={len(devs)} "
        f"kind={devs[0].device_kind} compile cache={cache}")

    t_start = time.perf_counter()
    if args.four_chips:
        four_chip_phase(nell2_mirror(NELL2_NNZ, args.seed), args.seed)
    else:
        log(f"[full] nonzeros: published {NELL2_NNZ:,}, drawn here "
            f"{ONE_CHIP_NNZ:,} (one-chip cut: the run's time limit)")
        t0 = time.perf_counter()
        kernel_phase(args.seed)
        log(f"[kernel] phase wall {time.perf_counter() - t0:.1f} s")
        t = nell2_mirror(ONE_CHIP_NNZ, args.seed)
        t0 = time.perf_counter()
        full_size_phase(t, args.seed)
        log(f"[full] phase wall {time.perf_counter() - t0:.1f} s")
        from repro.core.plan import plan_cache_clear

        plan_cache_clear()  # drop the full-size plans and their uploads
        t0 = time.perf_counter()
        stream_phase(t, args.seed)
        log(f"[stream] phase wall {time.perf_counter() - t0:.1f} s")
    log(f"[env] total wall {time.perf_counter() - t_start:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
