"""Compile rehearsals of the main-path kernels for a TPU v5e chip that is
described, not attached.

Each test compiles one private jitted entry point (``interpret=False`` for
the Pallas kernels) at the shapes a real fleet or migration hands it, for
one chip of a described ``v5e:2x2`` topology. Nothing runs, so these say
nothing of results or speed; they catch what only the chip's compiler
refuses — tiling rules, unlowerable primitives, device memory — without a
chip. The topology is described inside a fixture, never at import, so only
the worker that runs this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import characterize
from repro.core import postpone as pp
from repro.kernels import autocorr, dft, dirty_delta

#: device memory the blocked NB classify may take at the 100k-job bucket
CLASSIFY_BYTES_MAX = 8e9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler can be loaded here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip, so keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("b,n", [(1024, 512), (1024, 2048)])
def test_dft_power_compiles(one_chip, b, n):
    x = _spec((b, n), jnp.float32, one_chip)
    compiled = dft._dft_power.lower(x, center=True, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_autocorr_score_compiles(one_chip):
    x = _spec((16384, 512), jnp.float32, one_chip)
    lags = _spec((64,), jnp.int32, one_chip)
    compiled = autocorr._autocorr_score.lower(x, lags,
                                              interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_max_abs_delta_compiles(one_chip, dtype):
    new = _spec((4096, 16384), dtype, one_chip)
    compiled = dirty_delta._max_abs_delta.lower(new, new,
                                                interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_nb_classify_fits_one_chip_at_100k_jobs(one_chip):
    f32 = jnp.float32
    args = (_spec((6, 15), f32, one_chip), _spec((4, 6, 16), f32, one_chip),
            _spec((4,), f32, one_chip),
            _spec((131072, 512, 6), f32, one_chip))
    compiled = characterize._nb_predict_lm.lower(
        *args, block=characterize.CLASSIFY_BLOCK).compile()
    mem = compiled.memory_analysis()
    used = mem.temp_size_in_bytes + mem.argument_size_in_bytes
    assert used <= CLASSIFY_BYTES_MAX, used


def test_postpone_batch_compiles(one_chip):
    compiled = pp.postpone_batch_jit.lower(
        _spec((131072, 256), jnp.int8, one_chip),
        _spec((131072,), jnp.int32, one_chip),
        _spec((131072,), jnp.int32, one_chip)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16e9
