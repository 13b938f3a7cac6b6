"""Closed loop: one client decomposes the set-up's tensor again and again.

A mix of this kind (``"kind": "closed_loop"``) gives ``n_invocations``
(sweeps per call), ``check_calls`` (calls the check compares) and
``trace_calls`` (calls a traced run profiles).

* ``setup``: generate the tensor from the seed (``bench/gen.py``), clear the
  plan cache and ``plan()`` it, stage its upload and wait until the arrays
  are on their devices, and make one warm call with the window's shapes;
* ``window``: ``HooiExecutor.run(t, K, plan, n_invocations, seed=s_i)`` back
  to back on the set-up's plan, each call ended by ``block_until_ready`` on
  core and factors, until ``seconds`` have passed or ``max_calls`` were
  made. ``attempted`` counts calls, ``failed`` those that raised or gave a
  non-finite fit, ``n_sweeps`` the sweeps of the calls that did not fail;
* ``answers``: the calls the check compares, drawn from the seed, copied to
  the host with their factors after ``n_invocations - 1`` sweeps, read from
  a call of the same entry with the same seed and one sweep fewer.
"""

from __future__ import annotations

import math
import sys
import time

import numpy as np


def _span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def _call(ex, t, cfg, pl, n_inv: int, seed: int):
    import jax

    dec, st = ex.run(t, tuple(cfg["core_dims"]), pl, n_invocations=n_inv,
                     path=cfg["path"], seed=seed,
                     precision=cfg["precision"], **cfg["options"])
    jax.block_until_ready((dec.core, dec.factors))
    return dec, st


def setup(cfg: dict, mix: dict, seed: int, devices) -> dict:
    """Generate, plan, upload, warm: everything ``setup_s`` times."""
    import jax

    from bench import gen
    from repro.core.coo import SparseTensor
    from repro.core.plan import plan, plan_cache_clear
    from repro.distributed.executor import HooiExecutor, make_ranks_mesh

    P = int(cfg["P"])
    K = tuple(cfg["core_dims"])
    t0 = time.perf_counter()
    with _span("bench.setup.generate"):
        coords, values = gen.cell_tensor(cfg, seed)
        t = SparseTensor(coords, values, tuple(cfg["shape"]))
    t1 = time.perf_counter()
    with _span("bench.setup.plan"):
        plan_cache_clear()
        pl = plan(t, cfg["scheme"], P, core_dims=K, path=cfg["path"])
    t2 = time.perf_counter()
    ex = HooiExecutor(P, make_ranks_mesh(P, devices[:P]))
    with _span("bench.setup.upload"):
        ex.stage_upload(pl, t)
        jax.block_until_ready(jax.live_arrays())
    t3 = time.perf_counter()
    with _span("bench.setup.warm"):
        _, st = _call(ex, t, cfg, pl, int(mix["n_invocations"]),
                      seed ^ 0x5EED)
    t4 = time.perf_counter()
    print(f"[setup] generate {t1 - t0:.3f} s, plan {t2 - t1:.3f} s, upload "
          f"{t3 - t2:.3f} s, warm {t4 - t3:.3f} s ({st.step_compilations} "
          f"step compilations), nnz {t.nnz:,}", file=sys.stderr, flush=True)
    return {"tensor": t, "plan": pl, "executor": ex, "gen_s": t1 - t0,
            "plan_s": t2 - t1, "upload_s": t3 - t2, "warm_s": t4 - t3,
            "setup_s": t4 - t0, "warm_compiles": st.step_compilations}


def window(state: dict, cfg: dict, mix: dict, seed: int, seconds: float,
           max_calls: int | None = None) -> dict:
    """Calls back to back until ``seconds`` have passed (or ``max_calls``)."""
    ex, t, pl = state["executor"], state["tensor"], state["plan"]
    n_inv = int(mix["n_invocations"])
    k = int(mix["check_calls"])
    rng = np.random.default_rng([seed, 1])
    kept: list = []  # reservoir of (call index, call seed, dec)
    stats = []
    attempted = failed = 0
    t0 = time.perf_counter()
    while True:
        i = attempted
        call_seed = seed + 1 + i
        attempted += 1
        try:
            with _span("bench.call"):
                dec, st = _call(ex, t, cfg, pl, n_inv, call_seed)
        except Exception as e:  # a call that raises is a failed call
            print(f"call {i} raised {type(e).__name__}: {e}",
                  file=sys.stderr, flush=True)
            failed += 1
        else:
            stats.append(st)
            if not all(math.isfinite(float(f)) for f in st.fits):
                failed += 1
            else:
                j = i if i < k else int(rng.integers(0, i + 1))
                if j < k:
                    if len(kept) < k:
                        kept.append((i, call_seed, dec))
                    else:
                        kept[j] = (i, call_seed, dec)
        if max_calls is not None:
            if attempted >= max_calls:
                break
        elif time.perf_counter() - t0 >= seconds:
            break
    window_s = time.perf_counter() - t0
    return {"window_s": window_s, "attempted": attempted, "failed": failed,
            "stats": stats, "n_sweeps": n_inv * (attempted - failed),
            "kept": kept}


def answers(state: dict, cfg: dict, mix: dict, win: dict) -> list:
    """The sampled calls on the host, each with what the check needs."""
    ex, t, pl = state["executor"], state["tensor"], state["plan"]
    n_inv = int(mix["n_invocations"])
    out = []
    for i, call_seed, dec in win["kept"]:
        prev, _ = _call(ex, t, cfg, pl, n_inv - 1, call_seed)
        out.append({"call": i, "seed": call_seed, "n_sweeps": n_inv,
                    "before": [np.asarray(f) for f in prev.factors],
                    "factors": [np.asarray(f) for f in dec.factors],
                    "core": np.asarray(dec.core)})
    win["kept"] = []
    return out
