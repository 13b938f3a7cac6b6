"""The five-mode cell ``chicago-geo.sweeps`` at a tiny size, on the CPU.

The cell's configuration with the shape cut to about a tenth per mode,
5,000 nonzeros drawn and K = (4, 3, 4, 4, 3), run through the harness
(``bench/run_cell.py``): the sound run reads correct; half of the nonzeros
left out of mode 0's Z build, and the core altered where it is produced,
read not correct.
"""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import run_cell  # noqa: E402

TINY = {"shape": [60, 24, 38, 40, 32], "nnz": 5000,
        "core_dims": [4, 3, 4, 4, 3]}
LIMIT = "step_gap_vs_bf16"


def _run():
    import jax

    spec = run_cell.resolve_cell("chicago-geo.sweeps")
    assert len(spec["config"]["shape"]) == 5
    spec["config"].update(TINY)
    return run_cell.run(spec, 2**31 + 29, 0.0, False, jax.devices())


def _fails(r):
    c = r["checks"][LIMIT]
    return not r["correct"] and (c["value"] is None or c["value"] > c["limit"])


def test_tiny_five_mode_cell_is_correct():
    r = _run()
    assert r["correct"], r["checks"]
    assert r["attempted"] == 1 and r["failed"] == 0
    assert r["checks"][LIMIT]["value"] <= 0.15


def test_half_the_nonzeros_left_out_of_mode_0(monkeypatch):
    from repro.engine import steps

    real = steps.build_local_z

    def faulty(coords, values, local_rows, factors, mode, *a, **kw):
        if mode == 0:
            keep = (np.arange(values.shape[0]) % 2 == 0).astype(np.float32)
            values = values * keep
        return real(coords, values, local_rows, factors, mode, *a, **kw)

    monkeypatch.setattr(steps, "build_local_z", faulty)
    r = _run()
    assert _fails(r), r["checks"]


def test_core_altered(monkeypatch):
    from repro.core import ttm

    real = ttm.core_from_factors

    def altered(*a, **kw):
        core = real(*a, **kw)
        return core.at[(0,) * core.ndim].add(1e-2 * float(
            np.abs(np.asarray(core)).max()))

    monkeypatch.setattr(ttm, "core_from_factors", altered)
    r = _run()
    assert _fails(r), r["checks"]
