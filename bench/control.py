#!/usr/bin/env python3
"""Readings for the limits of a cell's check: the program and its control.

    python3 bench/control.py --workload enron.sweeps --seed 11 --calls 12

One set-up of the cell as a run makes it, then ``--calls`` calls of the
timed entry, each with a seed of its own, each compared with the reference
(``bench/reference.py``). Each comparison also reads the control's gap per
mode: the same step from the same inputs one precision below the float32
the configurations state. For the first ``--control-calls`` calls the
control is also put in the program's place (``reference.control_answer``,
the whole last sweep) and compared like an answer. Readings print as one
JSON line per call. A run of the benchmark does not run this; its readings
set the limits in the configuration files (``PERF.md``).
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import reference, run_cell  # noqa: E402


def readings(spec: dict, seed: int, calls: int, control_calls: int,
             devices):
    """Per-call check readings of the program and of its control."""
    cfg, mix, loop = spec["config"], spec["mix"], spec["loop"]
    state = loop.setup(cfg, mix, seed, devices)
    answers = []
    for i in range(calls):
        win = loop.window(state, cfg, mix, seed + 1000 * (i + 1), 0.0,
                         max_calls=1)
        answers += loop.answers(state, cfg, mix, win)
    t = state["tensor"]
    state.clear()
    gc.collect()
    for i, a in enumerate(answers):
        t0 = time.perf_counter()
        _, rows = run_cell.check(t, cfg, [a])
        out = {"seed": a["seed"], "program": rows[0],
               "check_s": time.perf_counter() - t0}
        if i < control_calls:
            ctl = reference.control_answer(t.coords, t.values, t.shape,
                                           seed=a["seed"],
                                           n_sweeps=a["n_sweeps"],
                                           before=a["before"])
            ctl.update(call=a["call"], seed=a["seed"], n_sweeps=a["n_sweeps"])
            _, rows = run_cell.check(t, cfg, [ctl])
            out["control"] = rows[0]
        yield out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--calls", type=int, default=12)
    ap.add_argument("--control-calls", type=int, default=0)
    args = ap.parse_args(argv)
    spec = run_cell.resolve_cell(args.workload)
    from repro.runtime import enable_compile_cache

    enable_compile_cache()
    devices, err = run_cell.accelerator(int(spec["cell"]["chips"]))
    if err:
        run_cell.log(f"refusing to measure: {err}")
        return 2
    for out in readings(spec, args.seed, args.calls, args.control_calls,
                        devices):
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
