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
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import characterize, precopy
from repro.core import postpone as pp
from repro.kernels import autocorr, backend, dft, dirty_delta

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


def _has_kernel(compiled, name):
    """The compiled program runs the Pallas kernel ``name`` (its
    ``pallas_call`` name) as a TPU custom call."""
    return re.search(rf"%{name}(\.\d+)? = [^\n]*tpu_custom_call",
                     compiled.as_text()) is not None


@pytest.mark.parametrize("b,n", [(1024, 512), (1024, 2048)])
def test_dft_power_compiles(one_chip, b, n):
    x = _spec((b, n), jnp.float32, one_chip)
    compiled = dft._dft_power.lower(x, center=True, interpret=False).compile()
    assert _has_kernel(compiled, "dft_power")


def test_autocorr_score_compiles(one_chip):
    x = _spec((16384, 512), jnp.float32, one_chip)
    lags = _spec((64,), jnp.int32, one_chip)
    compiled = autocorr._autocorr_score.lower(x, lags,
                                              interpret=False).compile()
    assert _has_kernel(compiled, "autocorr_score")


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Kernels that pick ``interpret`` from the running backend (the CPU
    here) compile for the described chip instead."""
    monkeypatch.setattr(backend, "resolve_interpret",
                        lambda interpret: False)


def _full_leaf_copies(compiled, size):
    """Instructions of the compiled program that write an array as large
    as a whole leaf: a copy, transpose, relayout reshape or fusion. Only
    parameters and bitcasts (views) may be that large."""
    ops = re.findall(r"= \w+\[([\d,]*)\]\S* ([\w-]+)\(",
                     compiled.as_text())
    return [(op, dims) for dims, op in ops
            if op not in ("parameter", "bitcast")
            and math.prod(int(d) for d in dims.split(",") if d) >= size]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_max_abs_delta_compiles(one_chip, compiled_kernels, dtype):
    """A leaf the kernel cannot read in place (its minor dim, as the
    hybrid's ``in_proj``, is no multiple of 128) goes into the same kernel
    through the padded block view."""
    new = _spec((4, 2048, 8512), dtype, one_chip)
    assert not dirty_delta.reads_in_place(new, 16384)
    compiled = precopy._leaf_dirty.lower(new, new, 16384).compile()
    assert _has_kernel(compiled, "max_abs_delta")


@pytest.mark.parametrize("shape", [(2048, 92544), (24, 4, 2048, 8, 128)],
                         ids=["head", "kv_cache"])
def test_leaf_dirty_reads_the_leaf_in_place(one_chip, compiled_kernels,
                                            shape):
    """The replica's LM head (blocks straddle its rows) and KV cache (8
    heads, under bfloat16's 16-row tile) are scanned without a relayout."""
    new = _spec(shape, jnp.bfloat16, one_chip)
    compiled = precopy._leaf_dirty.lower(new, new, 16384).compile()
    assert _has_kernel(compiled, "max_abs_delta")
    assert _full_leaf_copies(compiled, math.prod(shape)) == []
    leaf_bytes = 2 * math.prod(shape)
    assert compiled.memory_analysis().temp_size_in_bytes < leaf_bytes / 64


@pytest.fixture
def v5e_layouts(monkeypatch, one_chip):
    """The merge plans its boxes in the layouts the described chip gives,
    not the host's."""
    from jax.experimental.layout import Layout
    from repro.kernels import dirty_copy
    dev = next(iter(one_chip.device_set))

    def layout(shape, dtype):
        got = Layout.from_pjrt_layout(dev.client.get_default_layout(
            jnp.dtype(dtype), tuple(shape), dev))
        tiles = [t for t in got.tiling if len(t) == 2]
        return tuple(got.major_to_minor), tiles[0][0] if tiles else 8

    monkeypatch.setattr(dirty_copy, "layout", layout)


@pytest.mark.parametrize("shape,dtype", [
    ((24, 4, 2048, 8, 128), jnp.bfloat16), ((2048, 92544), jnp.bfloat16),
    ((9, 16, 64, 128, 64), jnp.float32), ((1, 16, 8192, 8, 64), jnp.bfloat16),
    ((18, 2048, 8512), jnp.bfloat16)],
    ids=["kv_cache", "head", "hybrid_ssm", "hybrid_kv", "in_proj"])
def test_leaf_merge_writes_the_shadow_in_place(one_chip, compiled_kernels,
                                               v5e_layouts, shape, dtype):
    """The cells' largest and oddest leaves merge by the kernel alone:
    no instruction but the kernel writes a whole-leaf array, the result
    aliases the donated shadow, and the temp stays under 1/1024 of the
    leaf, so no padded or full copy can come back."""
    blocks = -(-math.prod(shape) // 16384)
    leaf = _spec(shape, dtype, one_chip)
    compiled = precopy._leaf_merge.lower(
        leaf, leaf, _spec((blocks,), jnp.bool_, one_chip), 16384).compile()
    assert _has_kernel(compiled, "copy_dirty_boxes")
    assert [op for op, _ in _full_leaf_copies(compiled, math.prod(shape))
            if op != "custom-call"] == []
    leaf_bytes = math.prod(shape) * jnp.dtype(dtype).itemsize
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= leaf_bytes
    assert mem.temp_size_in_bytes < leaf_bytes / 1024


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


def test_hybrid_serve_step_compiles(one_chip):
    """The interleaved decode step (runs of Mamba2 layers and attention
    layers, a cache of both kinds) at a small size, without a copy of its
    cache: each run's cache goes through its scan layer by layer."""
    from repro.configs import get_config
    from repro.models import lm
    from repro.train import make_decode_step
    M, A = "mamba_mlp", "attn"
    cfg = get_config("granite_4_0_h_micro").smoke().replace(
        num_layers=4, block_pattern=(M, M, A, M))
    as_spec = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: _spec(a.shape, a.dtype, one_chip), tree)
    params = as_spec(jax.eval_shape(
        lambda: lm.init_params(cfg, jax.random.key(0))))
    cache = as_spec(jax.eval_shape(lambda: lm.init_cache(cfg, 16, 1024)))
    compiled = jax.jit(make_decode_step(cfg)).lower(
        params, _spec((16, 1), jnp.int32, one_chip), cache).compile()
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(cache))
    assert compiled.memory_analysis().temp_size_in_bytes < cache_bytes / 4
