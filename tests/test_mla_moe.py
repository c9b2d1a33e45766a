"""The latent-attention, sparse-expert decoder (``models/mla_moe.py``) on
its smoke config: absorbed decode against the plain forward, the expert
layer's chip shares against the uncut reference layer, routing, YaRN in
closed form, and the scopes its decode step carries."""

import dataclasses
import math
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.references import mla_moe as ref
from repro.configs import kimi_k2_1t
from repro.models import mla_moe as M
from repro.models import registry as R
from repro.serve.engine import _megastep_math

KEY = jax.random.PRNGKey(0)
SMOKE = kimi_k2_1t.SMOKE


def f32_model(cfg=SMOKE, bias=0.3):
    """The smoke model in float32, with a correction bias large enough to
    change which experts are chosen."""
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    params = M.init(jax.random.fold_in(KEY, 1), cfg)
    b = params["layers"]["moe"]["bias"]
    params["layers"]["moe"]["bias"] = bias * jax.random.normal(
        jax.random.fold_in(KEY, 2), b.shape)
    return cfg, params


def test_absorbed_decode_equals_plain_forward_in_float32():
    """Decode attends the latent cache with W_UK folded into the query and
    W_UV into the output; the forward decompresses K and V. In float32 at
    full matmul precision the two forms are the same sum."""
    cfg, params = f32_model()
    B, S = 2, 12
    toks = jax.random.randint(jax.random.fold_in(KEY, 3), (B, S), 0,
                              cfg.vocab)
    with jax.default_matmul_precision("highest"):
        full = M.forward(params, cfg, toks)
        cache = M.init_cache(cfg, B, S)
        outs = []
        for t in range(S):
            lg, cache = M.decode_step(params, cfg, cache, toks[:, t],
                                      jnp.full((B,), t, jnp.int32))
            outs.append(lg)
    np.testing.assert_allclose(np.asarray(full),
                               np.asarray(jnp.stack(outs, 1)), atol=1e-4)
    assert int((cache["pos"] >= 0).sum()) == cfg.num_layers * B * S


def _layer0(params):
    return jax.tree.map(lambda a: a[0], params["layers"])


def _share(layer, first, count):
    moe = dict(layer["moe"])
    for k in ("w_gate", "w_up", "w_down"):
        moe[k] = moe[k][first:first + count]
    return dict(layer, moe=moe)


def test_expert_shares_add_up_to_the_uncut_reference_layer():
    """Four chips each hold 4 of the 16 routed experts: what each share's
    layer adds, with the shared expert (every chip computes it alike)
    counted once, is what the uncut reference layer gives."""
    cfg, params = f32_model()
    layer = _layer0(params)
    x = jax.random.normal(jax.random.fold_in(KEY, 4), (2, 5, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        shared = M.nn.swiglu(layer["shared"], x)
        total = shared
        for first in range(0, cfg.n_routed_experts, 4):
            c = dataclasses.replace(cfg, held_experts=(first, 4))
            total = total + (M.moe(_share(layer, first, 4), x, c) - shared)
    sz = types.SimpleNamespace(top_k=cfg.top_k, norm_topk=True,
                               scaling=cfg.routed_scaling_factor,
                               held=cfg.n_routed_experts)
    want = (ref._routed(layer["moe"], x, sz, None)[0]
            + ref._swiglu(layer["shared"], x, None))
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-5)
    # the shares are not all alike: each adds a part of its own
    one = M.moe(_share(layer, 0, 4),
                x, dataclasses.replace(cfg, held_experts=(0, 4))) - shared
    assert float(jnp.abs(one).max()) > 0
    assert float(jnp.abs(total - shared - one).max()) > 0


def test_bias_picks_the_experts_but_does_not_weight_them():
    cfg, params = f32_model(bias=0.0)
    p = _layer0(params)["moe"]
    x = jax.random.normal(jax.random.fold_in(KEY, 5), (64, cfg.d_model))
    idx0, w0 = M.route(p, x, cfg)
    scores = jax.nn.sigmoid(x @ p["router"])
    p = dict(p, bias=jnp.zeros_like(p["bias"]).at[3].set(10.0))
    idx, w = M.route(p, x, cfg)
    assert bool((idx == 3).any(axis=1).all())        # chosen for every row
    assert not bool((idx0 == 3).any(axis=1).all())
    picked = jnp.take_along_axis(scores, idx, axis=1)
    np.testing.assert_allclose(
        np.asarray(w), np.asarray(picked / picked.sum(1, keepdims=True)
                                  * cfg.routed_scaling_factor), rtol=1e-5)


def test_held_experts_lie_within_the_router():
    with pytest.raises(ValueError, match="held experts"):
        dataclasses.replace(SMOKE, held_experts=(12, 8))


def test_yarn_matches_the_closed_form():
    """Kimi-K2's rope_scaling: beta_fast = beta_slow = 1 over 4096
    positions keeps the first 20 of 32 frequencies and divides the rest by
    32; the softmax scale carries (0.1 ln 32 + 1) squared; cos and sin are
    not scaled."""
    cfg = kimi_k2_1t.FULL
    assert M.yarn_correction_range(cfg) == (19, 20)
    i = np.arange(32)
    plain = 50000.0 ** (-2.0 * i / 64)
    want = np.where(i <= 19, plain, plain / 32)
    np.testing.assert_allclose(M.yarn_inv_freq(cfg), want, rtol=1e-6)
    m = 0.1 * math.log(32) + 1
    assert M.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m,
                                                 rel=1e-12)
    assert M.rotary_mscale(cfg) == 1.0


def test_megastep_carries_the_mla_and_moe_scopes():
    api = R.build("kimi-k2-1t-a32b", smoke=True)
    params = jax.eval_shape(api.init, KEY)
    B, W = 2, 16
    cache = jax.eval_shape(lambda: api.init_cache(B, W))
    dev = {k: jax.ShapeDtypeStruct((B,), jnp.int32)
           for k in ("state", "tok", "consumed", "n_gen", "prompt_len",
                     "max_new")}
    dev["prompt"] = jax.ShapeDtypeStruct((B, W), jnp.int32)
    text = jax.jit(_megastep_math(api, 2, 2, None)).lower(
        params, cache, dev).compile().as_text()
    for scope in ("mla/project", "mla/attend", "moe/route", "moe/experts",
                  "moe/shared"):
        assert f"/{scope}/" in text, scope
