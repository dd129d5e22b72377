"""Compile the main path's device programs for one TPU v5e chip, without a
chip: the TPU compiler compiles for a described topology. Nothing runs, so
these tests check only that the chip's compiler accepts each program and
that it fits the chip's memory. The topology is described inside a
fixture: only one process may load the TPU library, and only the worker
that runs this file does.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

V5E_HBM_LIMIT = 15.75e9  # bytes one v5e program may hold, as the compiler allows


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _abstract(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("d,s,p", [(2048, 64, 128), (2048, 512, 128), (2048, 512, 256)])
def test_minhash_kernel_compiles_for_v5e(one_chip, no_persistent_cache, d, s, p):
    """The streaming super-batch (2048 docs) at the smallest shingle bucket
    and the ``max_shingles`` cap, at 128 and 256 permutations."""
    from repro.kernels.minhash.minhash import minhash_pallas

    compiled = minhash_pallas.lower(
        _abstract((d, s), jnp.uint32, one_chip), _abstract((d, s), jnp.bool_, one_chip),
        _abstract((p,), jnp.uint32, one_chip), _abstract((p,), jnp.uint32, one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the kernel, not the interpreter


def test_scorer_step_fits_one_v5e(one_chip, no_persistent_cache):
    """``lm_perplexity_filter``'s batched step at mamba2-1.3b's full width,
    B=64 rows of T=256 tokens, built from abstract params."""
    from repro.configs import get_config
    from repro.models.model_zoo import build_model
    from repro.ops.model_ops import batch_scorer

    model = build_model(get_config("mamba2-1.3b"), remat_policy="none")
    params = jax.tree.map(
        lambda x: _abstract(x.shape, x.dtype, one_chip),
        jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))
    tokens = _abstract((64, 256), jnp.int32, one_chip)
    mask = _abstract((64, 256), jnp.float32, one_chip)
    compiled = batch_scorer(model).lower(params, tokens, tokens, mask).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 5e9  # the full-width f32 params
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM_LIMIT


def test_blake2b64_tile_compiles_for_v5e(one_chip, no_persistent_cache):
    """The shingle hash at the one tile shape the block shingler dispatches."""
    from repro.kernels.blake2b.blake2b import blake2b64_tile
    from repro.kernels.blake2b.ops import TILE

    compiled = blake2b64_tile.lower(
        _abstract((TILE // 4, 128), jnp.uint32, one_chip),
        _abstract((TILE // 128, 128), jnp.int32, one_chip),
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1e9
