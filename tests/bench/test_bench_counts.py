"""The dense family's FLOP and byte counts against hand-computed values,
and pinned bit for bit at each configuration's published sizes."""

import json
from pathlib import Path

import pytest

from bench.families import dense_lm
from bench.families.dense_lm import Sizes

ROOT = Path(__file__).resolve().parents[2]
TINY = Sizes(layers=2, d_model=8, heads=2, kv_heads=1, head_dim=4, d_ff=16,
             vocab=32, tied=True, rope_theta=1e4, rope_pct=1.0,
             norm_eps=1e-6)


def config_of(name):
    return json.loads((ROOT / f"bench/configs/{name}.json").read_text())


def test_matmul_params_by_hand():
    # per layer: q,k,v,o 8*4*2*(2+1) = 192, swiglu 3*8*16 = 384; head 8*32
    assert dense_lm.matmul_params(TINY) == 2 * (192 + 384) + 256


def test_flops_by_hand():
    # 2 per weight, plus 4 * layers * heads * head_dim per attended key
    assert dense_lm.positions_flops(TINY, [0]) == 2 * 1408 + 4 * 2 * 2 * 4
    assert dense_lm.positions_flops(TINY, [3]) == \
        2 * 1408 + 4 * 2 * 2 * 4 * 4
    assert dense_lm.positions_flops(TINY, [0, 3]) == \
        dense_lm.positions_flops(TINY, [0]) \
        + dense_lm.positions_flops(TINY, [3])


def test_bytes_by_hand():
    # weights bf16: matmul weights plus 2 norms a layer and the final one
    assert dense_lm.weight_bytes(TINY) == 2 * (1408 + 5 * 8)
    # K and V, 2 layers, 1 kv head of 4, bf16
    assert dense_lm.kv_bytes_per_token(TINY) == 32
    assert dense_lm.context_kv_bytes(TINY, [0, 3]) == 32 * (1 + 4)


@pytest.mark.parametrize("name", ["smollm-135m", "stablelm-3b"])
def test_sizes_agree_with_the_program(name):
    from repro.configs import get_config
    config = config_of(name)
    sz = dense_lm.sizes(config)
    assert sz.param_count() == get_config(name).param_count()
    norms_and_embed = sz.param_count() - dense_lm.matmul_params(sz)
    lookup = 0 if sz.tied else sz.vocab * sz.d_model
    assert norms_and_embed == (2 * sz.layers + 1) * sz.d_model + lookup
    assert dense_lm.kv_bytes_per_token(sz) == \
        config["memory"]["kv_bytes_per_token"]
    assert dense_lm.weight_bytes(sz) <= 2 * sz.param_count()


# the counts the benchmark's readers took before they went through the
# family module; a change here moves every roofline and MFU reading
@pytest.mark.parametrize("name,weight_bytes,kv_per_token,flops", [
    ("smollm-135m", 269_030_016, 23_040, 883_118_592.0),
    ("stablelm-3b", 5_332_997_120, 327_680, 16_359_424_000.0),
])
def test_counts_are_pinned(name, weight_bytes, kv_per_token, flops):
    sz = dense_lm.sizes(config_of(name))
    assert dense_lm.weight_bytes(sz) == weight_bytes
    assert dense_lm.kv_bytes_per_token(sz) == kv_per_token
    assert dense_lm.context_kv_bytes(sz, [0, 100, 1000]) == \
        float(kv_per_token * 1103)
    assert dense_lm.positions_flops(sz, [0, 100, 1000]) == flops
