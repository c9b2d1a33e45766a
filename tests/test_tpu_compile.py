"""Compile the serving path's kernels and its megastep program for one
TPU v5e chip, at smollm-135m's published widths, without a chip.

``jax.experimental.topologies`` describes a ``v5e:2x2`` host; the TPU
compiler installed with JAX then compiles for its first chip exactly as
it would on the machine, refusing what the chip's compiler refuses:
tiling, fast-memory limits, programs that do not fit. Nothing runs, so
these tests say nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file. Kernels are compiled from their modules with
``interpret=False`` — the ``kernels.ops`` wrappers would pick interpret
mode, because ``jax.default_backend()`` here is the CPU.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import duplex_stream as ds
from repro.kernels import vector_distance as vd
from repro.models import registry as R
from repro.serve.engine import _megastep_math

# smollm-135m: 30 layers x (K, V) x 3 kv heads x head_dim 64
KV_DIMS = 30 * 2 * 3 * 64
BLOCKS, TOKENS = 8, 16
BATCH, CACHE_LEN, PREFILL_CHUNK = 8, 1024, 4
N_QUERIES, VISITS = 4, 2              # VectorSearchTenant defaults


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_text(fn, *args, **static) -> str:
    text = fn.lower(*args, **static).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("stage_blocks", [1, 4])
def test_fused_duplex_stream(one_chip, stage_blocks):
    shape = (BLOCKS, TOKENS, KV_DIMS)
    _kernel_text(ds.duplex_kv_stream,
                 _sds(shape, jnp.int8, one_chip),
                 _sds(shape[:2] + (1,), jnp.float32, one_chip),
                 _sds(shape, jnp.bfloat16, one_chip),
                 interpret=False, fused=True, stage_blocks=stage_blocks)


def test_dequant_stream(one_chip):
    shape = (BLOCKS, TOKENS, KV_DIMS)
    _kernel_text(ds.dequant_stream,
                 _sds(shape, jnp.int8, one_chip),
                 _sds(shape[:2] + (1,), jnp.float32, one_chip),
                 interpret=False)


def test_quant_stream(one_chip):
    _kernel_text(ds.quant_stream,
                 _sds((BLOCKS, TOKENS, KV_DIMS), jnp.bfloat16, one_chip),
                 interpret=False)


def test_l2_distance(one_chip):
    _kernel_text(vd.l2_distance,
                 _sds((N_QUERIES, KV_DIMS), jnp.float32, one_chip),
                 _sds((VISITS, TOKENS, KV_DIMS), jnp.bfloat16, one_chip),
                 interpret=False)


def test_megastep_program_full_width(one_chip):
    """The fused K=1 megastep of full-width smollm-135m, paged, at the
    bring-up engine's batch and cache depth: compiles, and its arguments
    and temporaries fit one chip's 16 GB."""
    api = R.build("smollm-135m", smoke=False)
    assert api.cfg.d_model == 576

    def place(tree):
        return jax.tree.map(
            lambda x: _sds(x.shape, x.dtype, one_chip), tree)

    params = place(jax.eval_shape(api.init, jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(lambda: api.init_cache(BATCH, CACHE_LEN)))
    dev = {k: _sds((BATCH,), jnp.int32, one_chip)
           for k in ("state", "tok", "consumed", "n_gen", "prompt_len",
                     "max_new")}
    dev["prompt"] = _sds((BATCH, CACHE_LEN), jnp.int32, one_chip)
    mega = _megastep_math(api, PREFILL_CHUNK, 1, TOKENS)
    compiled = jax.jit(mega, donate_argnums=(1, 2)).lower(
        params, cache, dev).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert used < 16e9, mem
