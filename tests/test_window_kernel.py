"""The windowed sorted-rows Z-build kernel (``kernels/window_segsum.py``).

Interpret-mode differentials against ``ref.kron_segsum_ref`` and the jnp
``segment_sum`` build, the lane-packed C against the f32 Kronecker product,
the (element block, row window) pairs against their static bound, the gates
that route the sorted-rows build to the kernel, and a four-rank
boundary-backend run through ``HooiExecutor``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.coo import SparseTensor
from repro.core.hooi import random_factors
from repro.core.plan import plan
from repro.core.ttm import fold_elements
from repro.engine.zbuild import build_local_z, resolve_zbuild
from repro.kernels import ops, ref
from repro.kernels.window_segsum import (WINDOW, expansion_matrices,
                                         packed_ct, pair_count,
                                         split_pieces, window_geometry,
                                         window_pairs, window_segsum)


def _operands(rows, Ka, Kb, seed=0):
    rng = np.random.default_rng(seed)
    E = len(rows)
    a = rng.standard_normal((E, Ka)).astype(np.float32)
    b = rng.standard_normal((E, Kb)).astype(np.float32)
    return jnp.asarray(np.asarray(rows, np.int32)), jnp.asarray(a), \
        jnp.asarray(b)


def _check(rows, R, Ka=3, Kb=4, z0=None, precision="f32"):
    """The kernel against the reference within f32 summation error: the
    float64 sum, give or take 1e-5 of the sum of magnitudes per entry
    (the reference itself sums in another order)."""
    rows, a, b = _operands(rows, Ka, Kb)
    z0 = jnp.zeros((R, Ka * Kb), jnp.float32) if z0 is None else z0
    got = window_segsum(rows, a, b, z0, precision=precision)
    want = z0 + ref.kron_segsum_ref(rows, a, b, R, precision=precision)
    contribs = (np.asarray(a, np.float64)[:, :, None]
                * np.asarray(b, np.float64)[:, None, :]).reshape(len(rows), -1)
    mag = np.zeros((R, Ka * Kb))
    np.add.at(mag, np.asarray(rows), np.abs(contribs))
    tol = 1e-5 * mag + 1e-6 * (1 + np.abs(np.asarray(z0)))
    if precision == "bf16":  # the reference's own rounding of C
        tol = tol + 1e-2 * mag
    assert (np.abs(np.asarray(got) - np.asarray(want)) <= tol).all()
    return got


def _block(Ka=3, Kb=4):
    return window_geometry(Ka, Kb).block_e


# ------------------------------------------------------------ differential
def test_windows_no_element_touches_keep_z():
    """Rows in windows 0 and 3 only, one block spanning the gap: windows 1,
    2 and 4 are untouched and keep what Z held."""
    R = 4 * WINDOW + 60
    rows = [0, 1, 1, 5] + [3 * WINDOW + 2] * 3
    z0 = jnp.asarray(np.random.default_rng(1).standard_normal(
        (R, 12)).astype(np.float32))
    got = np.asarray(_check(rows, R, z0=z0))
    for w in (1, 2, 4):
        np.testing.assert_array_equal(got[w * WINDOW:(w + 1) * WINDOW],
                                      np.asarray(z0)[w * WINDOW:
                                                     (w + 1) * WINDOW])


def test_hub_row_longer_than_several_blocks():
    B = _block()
    rows = [0, 1] + [2] * (4 * B + 17) + [3, 200]
    _check(rows, 201)


def test_block_rows_span_several_windows():
    """One block holds rows from four windows (every row a few times)."""
    rows = np.repeat(np.arange(4 * WINDOW), 2)[:_block()]
    _check(rows, 4 * WINDOW)


def test_padding_elements_add_nothing():
    """The partition's padding: value 0 (a == 0), the last real row id."""
    rows, a, b = _operands([0, 0, 7, 9] + [9] * 300, 3, 4)
    a = a.at[4:].set(0.0)
    got = window_segsum(rows, a, b, jnp.zeros((10, 12), jnp.float32))
    want = ref.kron_segsum_ref(rows[:4], a[:4], b[:4], 10)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_no_elements_returns_z():
    z0 = jnp.ones((5, 12), jnp.float32)
    rows, a, b = _operands([], 3, 4)
    np.testing.assert_array_equal(window_segsum(rows, a, b, z0), z0)


def test_last_partial_window():
    """R not a multiple of the window; rows up to the last one."""
    R = 2 * WINDOW + 44
    rows = np.sort(np.random.default_rng(2).integers(0, R, 900))
    rows[-1] = R - 1
    _check(rows, R)


def test_bf16_contract_matches_reference():
    rows = np.sort(np.random.default_rng(3).integers(0, 300, 700))
    _check(rows, 300, Ka=5, Kb=6, precision="bf16")


def _tensor_args(shape, E, mode, seed=0):
    rng = np.random.default_rng(seed)
    coords = np.stack([rng.integers(0, L, E) for L in shape], 1)
    order = np.argsort(coords[:, mode], kind="stable")
    coords = coords[order]
    _, rows = np.unique(coords[:, mode], return_inverse=True)
    values = rng.standard_normal(E).astype(np.float32)
    factors = random_factors(shape, (10,) * len(shape),
                             jax.random.PRNGKey(seed))
    return (jnp.asarray(coords, jnp.int32), jnp.asarray(values),
            jnp.asarray(rows.astype(np.int32)), factors, int(rows.max()) + 1)


@pytest.mark.parametrize("shape,mode", [((40, 30, 900), 2),
                                        ((12, 10, 300, 11), 2)],
                         ids=["khat100", "khat1000"])
def test_build_matches_segment_sum_at_cell_widths(shape, mode):
    """K̂ = 100 from (10, 10, 10) and K̂ = 1,000 from (10, 10, 10, 10),
    through the chunked build (two chunks and a remainder)."""
    coords, values, rows, factors, R = _tensor_args(shape, 9000, mode)
    kw = dict(mode=mode, num_rows=R)

    def build(use_kernel):
        return build_local_z(coords, values, rows, factors, use_kernel=
                             use_kernel, **kw)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr("repro.engine.zbuild.fold_elements",
                   functools.partial(fold_elements, chunk=4096))
        got, want = build(True), build(False)
    assert got.shape == (R, 10 ** (len(shape) - 1))
    np.testing.assert_allclose(got, want, rtol=2e-5,
                               atol=2e-5 * float(jnp.abs(want).max()))


# ------------------------------------------------------------ lane packing
@pytest.mark.parametrize("Ka,Kb", [(10, 10), (100, 10), (3, 7)])
def test_packed_c_is_the_f32_kronecker_product(Ka, Kb):
    """Cᵀ from the split operands and the expansion matrices equals the f32
    product of ``_split_ab``'s a and b, bit for bit, K̂ = Ka·Kb rows deep
    with no padding of Kb."""
    if (Ka, Kb) == (3, 7):
        a, b = _operands(np.zeros(500), 3, 7, seed=5)[1:]
    else:
        shape = (20, 15, 12) if Ka == 10 else (20, 15, 12, 11)
        coords, values, _, factors, _ = _tensor_args(shape, 500, 0, seed=4)
        a, b = ops._split_ab(coords, values, factors, 0)
    assert a.shape[1] == Ka and b.shape[1] == Kb
    want = (a[:, :, None] * b[:, None, :]).reshape(-1, Ka * Kb)
    ea, eb = expansion_matrices(Ka, Kb)
    got = packed_ct(a.T, b.T, ea, eb)
    assert got.shape == (Ka * Kb, a.shape[0])
    np.testing.assert_array_equal(np.asarray(got).T, np.asarray(want))


def test_kernel_writes_c_exactly_for_one_element_rows():
    """One element per row: each row of Z is that element's C, exact."""
    rows, a, b = _operands(np.arange(300), 10, 10, seed=6)
    got = window_segsum(rows, a, b, jnp.zeros((300, 100), jnp.float32))
    want = (a[:, :, None] * b[:, None, :]).reshape(300, 100)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_split_pieces_sum_to_the_value():
    x = jnp.asarray(np.random.default_rng(7).standard_normal(
        (64, 10)).astype(np.float32) * 1e3)
    hi, mid, lo = (p.astype(jnp.float32) for p in split_pieces(x, 3))
    np.testing.assert_array_equal((hi + mid) + lo, x)


def test_split_pieces_are_cut_from_the_bit_pattern():
    """The pieces are the f32 pattern's leading bits, masked: no float
    round trip through bfloat16 that a compiler could drop as excess
    precision (on the TPU it would leave only ``hi``)."""
    x = np.random.default_rng(9).standard_normal(4096).astype(np.float32)
    hi, mid, lo = (np.asarray(p.astype(jnp.float32))
                   for p in split_pieces(jnp.asarray(x), 3))
    trunc = lambda v: (v.view(np.uint32) & np.uint32(0xFFFF0000)).view(
        np.float32)
    np.testing.assert_array_equal(hi, trunc(x))
    np.testing.assert_array_equal(mid, trunc(x - hi))
    np.testing.assert_array_equal(lo, (x - hi) - mid)
    assert np.count_nonzero(mid) > 0.9 * x.size


# ------------------------------------------------------------------ pairs
@pytest.mark.parametrize("alpha,R", [(1.1, 5000), (1.4, 800), (0.5, 20000)])
def test_pair_count_within_static_bound_on_zipf_rows(alpha, R):
    rng = np.random.default_rng(8)
    p = 1.0 / np.arange(1, R + 1) ** alpha
    raw = np.sort(rng.choice(R, size=6144, p=p / p.sum()))
    _, rows = np.unique(raw, return_inverse=True)
    R = int(rows.max()) + 1
    B = 512
    blk, win, n = window_pairs(jnp.asarray(rows, jnp.int32), B, R)
    n = int(n)
    bound = len(rows) // B + -(-R // WINDOW) - 1
    assert len(blk) == bound and n <= bound
    assert n == pair_count(rows, B)
    # the pairs are those of each block's window span, in order
    want = [(i, w) for i in range(len(rows) // B)
            for w in range(rows[i * B] // WINDOW,
                           rows[i * B + B - 1] // WINDOW + 1)]
    assert list(zip(np.asarray(blk)[:n].tolist(),
                    np.asarray(win)[:n].tolist())) == want
    # entries past n repeat the last pair
    assert (np.asarray(blk)[n:] == want[-1][0]).all()
    assert (np.asarray(win)[n:] == want[-1][1]).all()


def test_geometry_vmem_does_not_depend_on_rows():
    g100, g1000 = window_geometry(10, 10), window_geometry(100, 10)
    assert (g100.block_e, g1000.block_e) == (2048, 256)
    assert g1000.vmem_bytes <= ops.vmem_budget_bytes()
    assert ops.windowed_fits_vmem(100, 10)
    assert not ops.windowed_fits_vmem(1000, 100)  # K̂ = 100,000


def test_resolve_zbuild_routes_sorted_plain_builds_to_the_window():
    huge = 4_000_000  # far past the resident kernel's row gate
    core = (10, 10, 10)
    assert not ops.kernel_fits_vmem(huge, 10, 10)
    assert resolve_zbuild(huge, core, 2, True) == "windowed"
    assert resolve_zbuild(huge, core, 2, True, oracle_s=4) == "reference"
    assert resolve_zbuild(64, core, 2, True, oracle_s=4) == "resident"
    assert resolve_zbuild(huge, core, 2, False) == "reference"


# --------------------------------------------------------------- executor
def test_four_rank_boundary_run_matches_reference():
    """P=4, liteopt (boundary backend): every mode's Z build runs the
    windowed kernel and the fits match the reference path's."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 simulated devices (conftest sets XLA_FLAGS)")
    from repro.distributed.executor import HooiExecutor

    r = np.random.default_rng(11)
    shape = (300, 40, 260)
    coords = np.stack([r.integers(0, L, 3000) for L in shape], axis=1)
    t = SparseTensor(coords, r.standard_normal(3000), shape).dedup()
    ex = HooiExecutor(4)
    pl = plan(t, "lite", 4, core_dims=(3, 4, 3), path="liteopt")
    _, sk = ex.run(t, (3, 4, 3), pl, n_invocations=2, seed=3,
                   path="liteopt", use_kernel=True)
    _, sr = ex.run(t, (3, 4, 3), pl, n_invocations=2, seed=3,
                   path="liteopt", use_kernel=False)
    assert set(sk.comm_backends.values()) == {"boundary"}
    assert sk.z_build == {0: "windowed", 1: "windowed", 2: "windowed"}
    assert sr.z_build == {0: "reference", 1: "reference", 2: "reference"}
    assert sr.z_pairs_per_block is None
    assert all(v >= 1.0 for v in sk.z_pairs_per_block.values())
    np.testing.assert_allclose(sk.fits, sr.fits, rtol=1e-4, atol=1e-6)


def test_windowed_share_reader():
    from bench import run_cell

    read = run_cell.load_reader("zbuild_windowed_share")

    class St:
        def __init__(self, z_build):
            self.z_build = z_build

    win = {0: "windowed", 1: "windowed", 2: "windowed"}
    assert read({"stats": [St(win), St(win)]}) == 100.0
    assert read({"stats": [St(win), St({**win, 2: "reference"})]}) \
        == pytest.approx(500 / 6)
    assert read({"stats": [object()]}) is None  # a program without the stat
    assert read({"stats": []}) is None
