"""Compile the Pallas kernels for a described TPU v5e chip — no chip needed.

Interpret mode (every other kernel test) cannot see what the TPU compiler
refuses: unaligned tiles, too much VMEM, a kernel that cannot be lowered.
These tests compile ``kron_segsum``, ``kron_segsum_oracle`` and
``oracle_pair`` at nell2-s widths (E = 77,000 elements, K = 10, R rows per
rank at P = 1), and ``window_segsum`` at the benchmark cells' widths (one
65,536-element chunk into nell-2's and enron's largest mode, and in
column panels the chunked build of chicago-crime-geo's longest mode at
K̂ = 10⁴), for one device of a described ``v5e:2x2`` topology and check that the Pallas kernel
is in the compiled program. Keep them in this one
file: the topology is described in a module fixture, in the process that
runs the tests.
"""

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.kron_segsum import kron_segsum, kron_segsum_oracle
from repro.kernels.oracle_fused import oracle_pair
from repro.kernels.window_segsum import window_segsum

E = 77_000  # nell2-s nonzeros at scale 1.0
K = 10  # the paper's rank per mode
ROWS = (900, 1200)  # nell2-s mode-1 / mode-0 rows at P = 1


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
        from jax.experimental import topologies

        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but can never be read back without one; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel_compiles(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rows", ROWS)
@pytest.mark.parametrize("precision", ("f32", "bf16"))
def test_kron_segsum_compiles_for_v5e(one_chip, no_persistent_cache, rows,
                                      precision):
    fn = functools.partial(kron_segsum, num_rows=rows, interpret=False,
                           precision=precision)
    _assert_kernel_compiles(fn, _spec(one_chip, (E,), jnp.int32),
                            _spec(one_chip, (E, K)), _spec(one_chip, (E, K)))


@pytest.mark.parametrize("rows", ROWS)
@pytest.mark.parametrize("precision", ("f32", "bf16"))
def test_kron_segsum_oracle_compiles_for_v5e(one_chip, no_persistent_cache,
                                             rows, precision):
    def fn(rows_ids, a, b, X):
        return kron_segsum_oracle(rows_ids, a, b, rows, X, interpret=False,
                                  precision=precision)

    _assert_kernel_compiles(fn, _spec(one_chip, (E,), jnp.int32),
                            _spec(one_chip, (E, K)), _spec(one_chip, (E, K)),
                            _spec(one_chip, (K * K, 4)))


@pytest.mark.parametrize("rows", ROWS)
@pytest.mark.parametrize("panel", (1, 4))
def test_oracle_pair_compiles_for_v5e(one_chip, no_persistent_cache, rows,
                                      panel):
    fn = functools.partial(oracle_pair, interpret=False)
    _assert_kernel_compiles(fn, _spec(one_chip, (rows, K * K)),
                            _spec(one_chip, (K * K, panel)),
                            _spec(one_chip, (rows, panel)))


CHUNK = 65_536  # core.ttm.ELEMENT_CHUNK: one launch of the windowed kernel
# (rows, Ka, Kb) of the cells' largest modes: nell-2 mode 2 (K̂ 100) and
# enron mode 2 (K̂ 1,000)
CELL_MODES = {"nell2-m2": (28_818, 10, 10), "enron-m2": (244_268, 100, 10)}


@pytest.mark.parametrize("cell,precision", [("nell2-m2", "f32"),
                                            ("enron-m2", "f32"),
                                            ("nell2-m2", "bf16")])
def test_window_segsum_compiles_for_v5e(one_chip, no_persistent_cache, cell,
                                        precision):
    rows, Ka, Kb = CELL_MODES[cell]
    fn = functools.partial(window_segsum, interpret=False,
                           precision=precision)
    _assert_kernel_compiles(fn, _spec(one_chip, (CHUNK,), jnp.int32),
                            _spec(one_chip, (CHUNK, Ka)),
                            _spec(one_chip, (CHUNK, Kb)),
                            _spec(one_chip, (rows, Ka * Kb)))


def test_panelled_window_segsum_compiles_for_v5e(one_chip,
                                                no_persistent_cache,
                                                monkeypatch):
    """Five modes at K = 10 (Ka × Kb = 1,000 × 10, K̂ = 10⁴): the chunked
    build of chicago-crime-geo's longest mode (two chunks and a remainder)
    takes the gate's column panels, each a launch within its scoped VMEM
    limit, with Z the loop's carry and the launches' aliased output, never
    copied, padded or sliced."""
    from repro.engine.steps import f32_matmuls
    from repro.engine.zbuild import build_local_z
    from repro.kernels import ops

    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    geom = ops.windowed_geometry(1000, 10)
    assert geom.panels > 1
    shape = (6185, 24, 380, 395, 32)
    R, E = shape[0], 2 * CHUNK + 1000

    def fn(coords, values, local_rows, *factors):
        Z = f32_matmuls(build_local_z)(coords, values, local_rows, factors,
                                       0, R, use_kernel=True)
        return Z.sum(axis=0)

    compiled = jax.jit(fn).lower(
        _spec(one_chip, (E, 5), jnp.int32), _spec(one_chip, (E,)),
        _spec(one_chip, (E,), jnp.int32),
        *[_spec(one_chip, (L, K)) for L in shape]).compile()
    text = compiled.as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") \
        >= 2 * geom.panels
    z = re.compile(r"= f32\[6185,10000\]\S* (copy|pad|slice)\(")
    assert not [ln for ln in text.splitlines() if z.search(ln)]
    # Z (247 MB) and one chunk's temporaries, a few of its aᵀ (262 MB)
    at_bytes = CHUNK * 1000 * 4
    assert compiled.memory_analysis().temp_size_in_bytes \
        < R * 10_000 * 4 + 4 * at_bytes


def test_windowed_build_updates_z_in_place(one_chip, no_persistent_cache,
                                           monkeypatch):
    """The chunked build at enron mode 2 (three chunks and a remainder):
    Z is the loop's carry and the kernel's aliased output, never copied,
    padded or sliced (a copy would move 0.98 GB per chunk), and one buffer
    of it is all the build holds beside its chunk temporaries."""
    from repro.engine.steps import f32_matmuls
    from repro.engine.zbuild import build_local_z
    from repro.kernels import ops

    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    R, E = 244_268, 3 * CHUNK + 1000
    shape = (6066, 5699, R, 1176)

    def fn(coords, values, local_rows, *factors):
        Z = f32_matmuls(build_local_z)(coords, values, local_rows, factors,
                                       2, R, use_kernel=True)
        return Z.sum(axis=0)

    compiled = jax.jit(fn).lower(
        _spec(one_chip, (E, 4), jnp.int32), _spec(one_chip, (E,)),
        _spec(one_chip, (E,), jnp.int32),
        *[_spec(one_chip, (L, K)) for L in shape]).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    z_bytes = R * 1000 * 4
    assert compiled.memory_analysis().temp_size_in_bytes < 1.2 * z_bytes
    z = re.compile(r"= f32\[2443\d\d,1000\]\S* (copy|pad|slice)\(")
    assert not [ln for ln in text.splitlines() if z.search(ln)]
