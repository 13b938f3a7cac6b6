"""Column panels of the windowed Z build (``kernels/window_segsum.py``).

Where one launch's ``(W, K̂)`` window and K̂-row expansion matrices would
not fit VMEM, the build runs one launch per column panel of Z. Checked in
interpret mode: panelled builds over three panels and more (the last one
overhanging K̂) against the ``segment_sum`` build; a one-panel launch at
the cells' widths bit for bit against the launch as it was before panels;
the gate's choice of panel; the five-mode K = 10 build; and the executor's
``z_panels`` counter.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.coo import SparseTensor
from repro.core.hooi import random_factors
from repro.core.plan import plan
from repro.engine.zbuild import build_local_z, resolve_zbuild
from repro.kernels import ops, ref
from repro.kernels import window_segsum as ws


def _operands(R, E, Ka, Kb, seed=0):
    rng = np.random.default_rng(seed)
    rows = np.sort(rng.integers(0, R, E)).astype(np.int32)
    rows[-1] = R - 1
    a = rng.standard_normal((E, Ka)).astype(np.float32)
    b = rng.standard_normal((E, Kb)).astype(np.float32)
    return jnp.asarray(rows), jnp.asarray(a), jnp.asarray(b)


def _within_f32_sums(got, want, rows, a, b, z0):
    """``got`` equals ``want`` within f32 summation error: 1e-5 of each
    entry's sum of magnitudes (the two sum in different orders)."""
    contribs = np.abs(np.asarray(a, np.float64)[:, :, None]
                      * np.asarray(b, np.float64)[:, None, :]).reshape(
                          a.shape[0], -1)
    mag = np.zeros(want.shape)
    np.add.at(mag, np.asarray(rows), contribs)
    tol = 1e-5 * mag + 1e-6 * (1 + np.abs(np.asarray(z0)))
    got = np.asarray(got)
    assert np.isfinite(got).all()
    assert (np.abs(got - np.asarray(want)) <= tol).all()


# (Ka, Kb, panel): K̂ = 400 in four panels of 128 columns (32 rows of aᵀ
# each, 4 in the last), 320 in three (16 in the last), 2,000 in four of
# 640 (64 rows of aᵀ, 8 in the last)
PANELLED = [(100, 4, 128), (80, 4, 128), (200, 10, 640)]


@pytest.mark.parametrize("Ka,Kb,panel", PANELLED,
                         ids=["khat400", "khat320", "khat2000"])
def test_panelled_build_matches_segment_sum(Ka, Kb, panel):
    geom = ws.window_geometry(Ka, Kb, "f32", panel)
    assert geom.panels >= 3 and geom.panels * panel > Ka * Kb
    R = 2 * ws.WINDOW + 37
    rows, a, b = _operands(R, 3000, Ka, Kb)
    z0 = jnp.asarray(np.random.default_rng(1).standard_normal(
        (R, Ka * Kb)).astype(np.float32))
    got = ws.window_segsum(rows, a, b, z0, panel=panel)
    _within_f32_sums(got, z0 + ref.kron_segsum_ref(rows, a, b, R), rows, a,
                     b, z0)


def test_panelled_bf16_contract_matches_reference():
    R = 300
    rows, a, b = _operands(R, 1500, 100, 4, seed=3)
    z0 = jnp.zeros((R, 400), jnp.float32)
    got = ws.window_segsum(rows, a, b, z0, panel=128, precision="bf16")
    want = ref.kron_segsum_ref(rows, a, b, R, precision="bf16")
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=1e-5 * float(jnp.abs(want).max()))


def test_panel_must_be_aligned_and_narrower_than_khat():
    for bad in (100, 192, 512):  # not a multiple of 128, or past K̂
        with pytest.raises(ValueError):
            ws.window_geometry(100, 4, "f32", bad)


def _launch_before_panels(rows, a, b, z, precision="f32"):
    """The windowed launch as it was before column panels: one launch over
    all K̂ columns. Kept here to hold the one-panel launch to it."""
    E, Ka = a.shape
    Kb = b.shape[1]
    R, khat = z.shape
    geom = ws.window_geometry(Ka, Kb, precision)
    B, pieces = geom.block_e, geom.pieces
    E_pad = -(-E // B) * B
    at, bt = a.astype(jnp.float32).T, b.astype(jnp.float32).T
    if E_pad != E:
        pad = E_pad - E
        rows = jnp.concatenate([rows, jnp.full((pad,), rows[-1], rows.dtype)])
        at = jnp.pad(at, ((0, 0), (0, pad)))
        bt = jnp.pad(bt, ((0, 0), (0, pad)))
    rows = rows.astype(jnp.int32)
    blk, win, n_pairs = ws.window_pairs(rows, B, R)
    ea, eb = ws.expansion_matrices(Ka, Kb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_pairs,),
        in_specs=[
            pl.BlockSpec((1, B), lambda p, blk, win: (0, blk[p])),
            pl.BlockSpec((Ka, B), lambda p, blk, win: (0, blk[p])),
            pl.BlockSpec((Kb, B), lambda p, blk, win: (0, blk[p])),
            pl.BlockSpec((khat, Ka), lambda p, blk, win: (0, 0)),
            pl.BlockSpec((khat, Kb), lambda p, blk, win: (0, 0)),
            pl.BlockSpec((ws.WINDOW, khat), lambda p, blk, win: (win[p], 0)),
        ],
        out_specs=pl.BlockSpec((ws.WINDOW, khat),
                               lambda p, blk, win: (win[p], 0)),
        scratch_shapes=[pltpu.VMEM((pieces, khat, B), jnp.bfloat16)],
    )
    return pl.pallas_call(
        functools.partial(ws._kernel, pieces=pieces, block_e=B,
                          a_rows=None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, khat), jnp.float32),
        input_output_aliases={7: 0}, interpret=True,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=geom.vmem_limit_bytes),
        name="window_segsum",
    )(blk, win, rows[None, :], at, bt, ea, eb, z.astype(jnp.float32))


@pytest.mark.parametrize("Ka,Kb", [(10, 10), (100, 10)])
def test_one_panel_launch_is_the_launch_before_panels(Ka, Kb):
    """At the cells' widths (K̂ = 100, 1,000) the gate keeps one launch of
    the same block, and it gives the same bits of Z as before panels."""
    geom = ops.windowed_geometry(Ka, Kb)
    assert (geom.panels, geom.panel) == (1, Ka * Kb)
    assert geom.block_e == ws.window_geometry(Ka, Kb).block_e
    R = 3 * ws.WINDOW + 5
    rows, a, b = _operands(R, 2500, Ka, Kb, seed=Ka)
    z0 = jnp.asarray(np.random.default_rng(2).standard_normal(
        (R, Ka * Kb)).astype(np.float32))
    got = ws.window_segsum(rows, a, b, z0, panel=geom.panel)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(
        _launch_before_panels(rows, a, b, z0)))


def test_gate_takes_panels_at_five_modes_of_k10():
    """Ka × Kb = 1,000 × 10 (K̂ = 10⁴): one launch would need ~103 MiB of
    VMEM; the gate takes panels of 1,280 columns within the budget, and
    the sorted-rows build routes to the windowed kernel."""
    whole = ws.window_geometry(1000, 10)
    assert whole.vmem_bytes > ops.vmem_budget_bytes()
    geom = ops.windowed_geometry(1000, 10)
    assert (geom.panel, geom.panels, geom.panel_rows) == (1280, 8, 128)
    assert geom.vmem_bytes <= ops.vmem_budget_bytes()
    assert geom.vmem_limit_bytes <= 64 * 1024 * 1024
    assert resolve_zbuild(6185, (10,) * 5, 0, True) == "windowed"
    assert ws.panel_widths(1000, 10) == [1280, 640]


def test_five_mode_build_at_k10_takes_panels():
    """The chunked sorted-rows build of a five-mode tensor at K = 10 per
    mode (K̂ = 10⁴, eight launches a chunk) equals the ``segment_sum``
    build."""
    shape, E, mode = (30, 24, 38, 40, 32), 1500, 0
    rng = np.random.default_rng(5)
    coords = np.stack([rng.integers(0, L, E) for L in shape], 1)
    coords = coords[np.argsort(coords[:, mode], kind="stable")]
    _, rows = np.unique(coords[:, mode], return_inverse=True)
    values = jnp.asarray(rng.standard_normal(E).astype(np.float32))
    factors = random_factors(shape, (10,) * 5, jax.random.PRNGKey(5))
    coords = jnp.asarray(coords, jnp.int32)
    rows = jnp.asarray(rows.astype(np.int32))
    R = int(rows.max()) + 1
    got = build_local_z(coords, values, rows, factors, mode, R,
                        use_kernel=True)
    want = build_local_z(coords, values, rows, factors, mode, R,
                         use_kernel=False)
    assert got.shape == (R, 10 ** 4)
    np.testing.assert_allclose(got, want, rtol=2e-5,
                               atol=2e-5 * float(jnp.abs(want).max()))


def test_executor_counts_panels(monkeypatch):
    """``DistHooiStats.z_panels``: panels launched over the call's mode
    steps. With the gate held to its narrowest panel (128 columns of K̂ =
    256), the run takes two a mode step and matches the reference build's
    fits."""
    from repro.distributed.executor import HooiExecutor

    r = np.random.default_rng(9)
    shape, K = (40, 24, 30, 36, 32), (4, 4, 4, 4, 4)
    coords = np.stack([r.integers(0, L, 2500) for L in shape], axis=1)
    t = SparseTensor(coords, r.standard_normal(2500), shape).dedup()
    pl_ = plan(t, "lite", 1, core_dims=K, path="liteopt")
    _, s1 = HooiExecutor(1).run(t, K, pl_, n_invocations=2, seed=1,
                                use_kernel=True)
    assert s1.z_panels == 2 * 5  # one launch a mode step

    def narrowest(Ka, Kb, precision="f32", vmem_budget=None):
        return ws.window_geometry(Ka, Kb, precision,
                                  ws.panel_widths(Ka, Kb)[-1])

    monkeypatch.setattr(ops, "windowed_geometry", narrowest)
    ex = HooiExecutor(1)
    _, sk = ex.run(t, K, pl_, n_invocations=2, seed=1, use_kernel=True)
    _, sr = ex.run(t, K, pl_, n_invocations=2, seed=1, use_kernel=False)
    assert set(sk.z_build.values()) == {"windowed"}
    assert sk.z_panels == 2 * 5 * 2 and sr.z_panels == 0
    np.testing.assert_allclose(sk.fits, sr.fits, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(s1.fits, sk.fits, rtol=1e-4, atol=1e-6)
