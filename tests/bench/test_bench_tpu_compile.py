"""Each benchmark configuration's K=1 megastep, compiled for one described
TPU v5e chip at its cell's shapes: it compiles, and what the engine keeps
resident plus the program's temporaries fits the chip. Nothing runs.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""

import json
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bench.run import build_model
from repro.serve.engine import _megastep_math

ROOT = Path(__file__).resolve().parents[2]
CHIP_BYTES = 15.75 * 2 ** 30     # what the compiler lets one v5e chip hold
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIGS = {c["name"]: c["file"] for c in BENCH["configs"]}


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


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_megastep_fits_one_chip(one_chip, name):
    config = json.loads((ROOT / CONFIGS[name]).read_text())
    eng = config["engine"]
    B, W = eng["max_batch"], eng["cache_len"]
    api, _ = build_model(config)

    def place(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip), tree)

    params = place(jax.eval_shape(api.init, jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(lambda: api.init_cache(B, W)))
    dev = {k: jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
           for k in ("state", "tok", "consumed", "n_gen", "prompt_len",
                     "max_new")}
    dev["prompt"] = jax.ShapeDtypeStruct((B, W), jnp.int32,
                                         sharding=one_chip)
    paged = eng.get("paging", True)
    mega = _megastep_math(api, eng["prefill_chunk"], 1,
                          eng.get("block_tokens", 16) if paged else None)
    compiled = jax.jit(mega, donate_argnums=(1, 2)).lower(
        params, cache, dev).compile()
    mem = compiled.memory_analysis()
    program = (mem.argument_size_in_bytes + mem.output_size_in_bytes
               - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    # beside the program's own arguments the engine keeps a pristine copy
    # of the dense cache and, paged, the pool's two tiers
    m = config["memory"]
    resident = (program + m["pristine_cache_copy_bytes"]
                + m["pool_hbm_tier_bytes"] + m["pool_int8_tier_bytes"])
    assert resident < 0.95 * CHIP_BYTES, (name, mem)
