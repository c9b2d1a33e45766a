"""FLOP and byte counts against hand-computed values."""

import json
from pathlib import Path

import pytest

from bench import counts
from bench.weights import Sizes

ROOT = Path(__file__).resolve().parents[2]
TINY = Sizes(layers=2, d_model=8, heads=2, kv_heads=1, head_dim=4, d_ff=16,
             vocab=32, tied=True, rope_theta=1e4, rope_pct=1.0,
             norm_eps=1e-6)


def test_matmul_params_by_hand():
    # per layer: q,k,v,o 8*4*2*(2+1) = 192, swiglu 3*8*16 = 384; head 8*32
    assert counts.matmul_params(TINY) == 2 * (192 + 384) + 256


def test_flops_by_hand():
    # 2 per weight, plus 4 * layers * heads * head_dim per attended key
    assert counts.positions_flops(TINY, [0]) == 2 * 1408 + 4 * 2 * 2 * 4
    assert counts.positions_flops(TINY, [3]) == 2 * 1408 + 4 * 2 * 2 * 4 * 4
    assert counts.positions_flops(TINY, [0, 3]) == \
        counts.positions_flops(TINY, [0]) + counts.positions_flops(TINY, [3])


def test_bytes_by_hand():
    # weights bf16: matmul weights plus 2 norms a layer and the final one
    assert counts.weight_bytes(TINY) == 2 * (1408 + 5 * 8)
    # K and V, 2 layers, 1 kv head of 4, bf16
    assert counts.kv_bytes_per_token(TINY) == 32
    assert counts.context_kv_bytes(TINY, [0, 3]) == 32 * (1 + 4)


@pytest.mark.parametrize("name", ["smollm-135m", "stablelm-3b"])
def test_sizes_agree_with_the_program(name):
    from repro.configs import get_config
    config = json.loads((ROOT / f"bench/configs/{name}.json").read_text())
    sz = Sizes.of(config)
    assert sz.param_count() == get_config(name).param_count()
    norms_and_embed = sz.param_count() - counts.matmul_params(sz)
    lookup = 0 if sz.tied else sz.vocab * sz.d_model
    assert norms_and_embed == (2 * sz.layers + 1) * sz.d_model + lookup
    assert counts.kv_bytes_per_token(sz) == \
        config["memory"]["kv_bytes_per_token"]
    assert counts.weight_bytes(sz) <= 2 * sz.param_count()
