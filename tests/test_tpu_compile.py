"""Compile the Pallas kernels for a described TPU v5e chip — no chip needed.

Interpret mode (every other kernel test) cannot see what the TPU compiler
refuses: unaligned tiles, too much VMEM, a kernel that cannot be lowered.
These tests compile ``kron_segsum``, ``kron_segsum_oracle`` and
``oracle_pair`` at nell2-s widths (E = 77,000 elements, K = 10, R rows per
rank at P = 1) for one device of a described ``v5e:2x2`` topology and check
that the Pallas kernel is in the compiled program. Keep them in this one
file: the topology is described in a module fixture, in the process that
runs the tests.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.kron_segsum import kron_segsum, kron_segsum_oracle
from repro.kernels.oracle_fused import oracle_pair

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
