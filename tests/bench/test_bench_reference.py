"""The plain reference agrees with the served program's own full-sequence
forward at a tiny size, and its float8 control does not."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.references import dense_lm
from bench.run import build_model
from bench.families.dense_lm import make_params
from bench_fixtures import register_tiny, tiny_config
from repro.models import transformer


@pytest.fixture(scope="module")
def model():
    with pytest.MonkeyPatch.context() as mp:
        register_tiny(mp)
        api, sz = build_model(tiny_config())
    params = make_params(sz, 2 ** 31 + 7)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0, sz.vocab)
    return api, sz, params, tokens


def test_weights_are_the_programs_tree_and_seeded(model):
    api, sz, params, _ = model
    want = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    assert jax.tree.structure(want) == jax.tree.structure(params)
    again = make_params(sz, 2 ** 31 + 7)
    other = make_params(sz, 2 ** 31 + 8)
    assert all(bool((a == b).all()) for a, b in
               zip(jax.tree.leaves(params), jax.tree.leaves(again)))
    assert not bool((params["embed"] == other["embed"]).all())


def test_reference_logits_match_the_program(model):
    api, sz, params, tokens = model
    logits, _ = transformer.forward(params, api.cfg, tokens)
    served = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    gap, best = dense_lm.score(params, tokens, served, sz=sz)
    # the program's bf16 choice is the float32 reference's best, or within
    # bf16 rounding of it
    assert float(gap.max()) < 0.02
    assert float((best == served).mean()) > 0.9


def test_reference_keys_values_match_the_programs_cache(model):
    api, sz, params, tokens = model
    _, cache = transformer.prefill(params, api.cfg, tokens, cache_len=40)
    kv = np.asarray(dense_lm.keys_values(params, tokens, sz=sz))
    want = np.stack([np.asarray(cache["k"], np.float32),
                     np.asarray(cache["v"], np.float32)], axis=3)
    err = np.abs(kv - want).max() / np.abs(want).max()
    assert err < 0.02


def test_float8_control_is_further_off(model):
    api, sz, params, tokens = model
    logits, _ = transformer.forward(params, api.cfg, tokens)
    served = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    gap_prog, _ = dense_lm.score(params, tokens, served, sz=sz)
    _, top8 = dense_lm.score(params, tokens, served, sz=sz, quant="fp8")
    gap_ctl, _ = dense_lm.score(params, tokens, top8, sz=sz)
    assert float(gap_ctl.max()) > 3 * float(gap_prog.max())
    with pytest.raises(ValueError):
        dense_lm.score(params, tokens, served, sz=sz, quant="int3")
