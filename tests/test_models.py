"""Per-arch smoke tests (reduced configs) + decode/forward consistency."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as configs_lib
from repro.models import layers as nn
from repro.models import registry as R
from repro.models import transformer as T
from repro.models.layers import MoESpec

KEY = jax.random.PRNGKey(0)
ALL_ARCHS = list(configs_lib.ARCH_IDS)


def _batch(api, B=2, S=16):
    b = {"tokens": jax.random.randint(jax.random.fold_in(KEY, 1), (B, S),
                                      0, api.cfg.vocab),
         "labels": jax.random.randint(jax.random.fold_in(KEY, 2), (B, S),
                                      0, api.cfg.vocab)}
    if api.family == "audio":
        b["frames"] = 0.1 * jax.random.normal(
            jax.random.fold_in(KEY, 3),
            (B, S, api.cfg.d_model)).astype(jnp.bfloat16)
    if api.family == "vlm":
        b["prefix_embeds"] = 0.1 * jax.random.normal(
            jax.random.fold_in(KEY, 4),
            (B, api.cfg.prefix_len, api.cfg.d_model)).astype(jnp.bfloat16)
    return b


@pytest.mark.parametrize("arch", ALL_ARCHS)
class TestSmoke:
    def test_forward_shapes_and_finite(self, arch):
        api = R.build(arch, smoke=True)
        params = api.init(KEY)
        batch = _batch(api)
        logits = api.forward(params, batch)
        assert logits.shape == (2, 16, api.cfg.vocab)
        assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))

    def test_train_step_no_nans(self, arch):
        api = R.build(arch, smoke=True)
        from repro.launch.steps import make_train_step
        from repro.optim import adamw_init
        params = api.init(KEY)
        opt = adamw_init(params)
        step = jax.jit(make_train_step(api))
        params2, opt2, metrics = step(params, opt, _batch(api))
        assert bool(jnp.isfinite(metrics["loss"]))
        assert float(metrics["grad_norm"]) > 0.0
        # params actually moved
        moved = any(
            not np.array_equal(np.asarray(a, np.float32),
                               np.asarray(b, np.float32))
            for a, b in zip(jax.tree.leaves(params),
                            jax.tree.leaves(params2)))
        assert moved

    def test_decode_step_shapes(self, arch):
        api = R.build(arch, smoke=True)
        params = api.init(KEY)
        cache = api.init_cache(2, 32)
        logits, cache2 = api.decode_step(
            params, cache, jnp.zeros((2,), jnp.int32),
            jnp.zeros((2,), jnp.int32))
        assert logits.shape == (2, api.cfg.vocab)
        assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
        assert jax.tree.structure(cache) == jax.tree.structure(cache2)

    def test_full_config_values(self, arch):
        """The full config matches the assignment table exactly."""
        table = {
            "smollm-135m": (30, 576, 9, 3, 1536, 49152),
            "stablelm-3b": (32, 2560, 32, 32, 6912, 50304),
            "qwen2.5-14b": (48, 5120, 40, 8, 13824, 152064),
            "llama3.2-3b": (28, 3072, 24, 8, 8192, 128256),
            "mixtral-8x7b": (32, 4096, 32, 8, 14336, 32000),
            "whisper-base": (6, 512, 8, 8, 2048, 51865),
            "zamba2-7b": (81, 3584, 32, 32, 14336, 32000),
            "paligemma-3b": (18, 2048, 8, 1, 16384, 257216),
        }
        cfg = R.build(arch).cfg
        if arch == "kimi-k2-1t-a32b":
            # Kimi-K2-Instruct's config.json: MLA, 384 routed experts of
            # 2048 top 8 plus one shared, one leading dense layer of 18432
            assert (cfg.num_layers, cfg.d_model, cfg.num_heads,
                    cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                    cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.d_ff,
                    cfg.moe_d_ff, cfg.n_routed_experts, cfg.n_shared_experts,
                    cfg.top_k, cfg.first_k_dense, cfg.vocab) == \
                (61, 7168, 64, 1536, 512, 128, 64, 128, 18432, 2048, 384, 1,
                 8, 1, 163840)
            assert (cfg.routed_scaling_factor, cfg.rope_theta,
                    cfg.rope_factor, cfg.original_max_position) == \
                (2.827, 50000.0, 32.0, 4096)
            return
        if arch == "rwkv6-7b":
            assert (cfg.num_layers, cfg.d_model, cfg.d_ff, cfg.vocab) == \
                (32, 4096, 14336, 65536)
            return
        L, d, h, kv, ff, v = table[arch]
        assert (cfg.num_layers, cfg.d_model, cfg.num_heads,
                cfg.num_kv_heads, cfg.d_ff, cfg.vocab) == \
            (L, d, h, kv, ff, v)


class TestDecodeConsistency:
    """decode_step must reproduce the teacher-forced forward exactly."""

    @pytest.mark.parametrize("arch", ["smollm-135m", "qwen2.5-14b",
                                      "rwkv6-7b", "zamba2-7b"])
    def test_stepwise_equals_forward(self, arch):
        api = R.build(arch, smoke=True)
        params = api.init(jax.random.fold_in(KEY, 9))
        B, S = 2, 12
        toks = jax.random.randint(jax.random.fold_in(KEY, 10), (B, S), 0,
                                  api.cfg.vocab)
        full = api.forward(params, {"tokens": toks})
        cache = api.init_cache(B, S)
        outs = []
        for t in range(S):
            lg, cache = api.decode_step(params, cache, toks[:, t],
                                        jnp.full((B,), t, jnp.int32))
            outs.append(lg)
        dec = jnp.stack(outs, axis=1)
        np.testing.assert_allclose(np.asarray(full, np.float32),
                                   np.asarray(dec, np.float32),
                                   atol=1e-2, rtol=1e-2)

    def test_moe_with_capacity_headroom(self):
        api = R.build("mixtral-8x7b", smoke=True)
        cfg = dataclasses.replace(
            api.cfg, moe=MoESpec(num_experts=4, top_k=2,
                                 capacity_factor=4.0))
        params = T.init(KEY, cfg)
        B, S = 2, 12
        toks = jax.random.randint(jax.random.fold_in(KEY, 11), (B, S), 0,
                                  cfg.vocab)
        full, _ = T.forward(params, cfg, toks)
        cache = T.init_cache(cfg, B, S)
        outs = []
        for t in range(S):
            lg, cache = T.decode_step(params, cfg, cache, toks[:, t],
                                      jnp.full((B,), t, jnp.int32))
            outs.append(lg)
        np.testing.assert_allclose(
            np.asarray(full, np.float32),
            np.asarray(jnp.stack(outs, 1), np.float32), atol=1e-2)

    def test_prefill_then_decode_vlm(self):
        """PaliGemma: prefix-LM prefill -> decode continuation."""
        api = R.build("paligemma-3b", smoke=True)
        cfg = api.cfg
        params = T.init(jax.random.fold_in(KEY, 12), cfg)
        B, P = 2, cfg.prefix_len
        S = P + 6
        toks = jax.random.randint(jax.random.fold_in(KEY, 13), (B, S), 0,
                                  cfg.vocab)
        pe = (0.1 * jax.random.normal(jax.random.fold_in(KEY, 14),
                                      (B, P, cfg.d_model))
              ).astype(jnp.bfloat16)
        ext = jax.random.randint(jax.random.fold_in(KEY, 15), (B, 4), 0,
                                 cfg.vocab)
        full, _ = T.forward(params, cfg, jnp.concatenate([toks, ext], 1),
                            pe)
        lg, cache = T.prefill(params, cfg, toks, pe, cache_len=S + 4)
        np.testing.assert_allclose(np.asarray(full[:, S - 1], np.float32),
                                   np.asarray(lg[:, -1], np.float32),
                                   atol=1e-2)
        for i in range(4):
            lgd, cache = T.decode_step(params, cfg, cache, ext[:, i],
                                       jnp.full((B,), S + i, jnp.int32))
            np.testing.assert_allclose(
                np.asarray(full[:, S + i], np.float32),
                np.asarray(lgd, np.float32), atol=1e-2)

    def test_swa_ring_buffer_eviction(self):
        """Sliding-window cache: positions older than the window must not
        affect decode (ring overwrite is correct)."""
        api = R.build("mixtral-8x7b", smoke=True)
        cfg = dataclasses.replace(
            api.cfg, moe=MoESpec(num_experts=4, top_k=2,
                                 capacity_factor=4.0))   # window 16
        params = T.init(jax.random.fold_in(KEY, 16), cfg)
        B, S = 1, 24           # exceeds the 16-token window
        toks = jax.random.randint(jax.random.fold_in(KEY, 17), (B, S), 0,
                                  cfg.vocab)
        full, _ = T.forward(params, cfg, toks)
        cache = T.init_cache(cfg, B, S)    # width = window = 16
        assert cache["k"].shape[2] == 16
        outs = []
        for t in range(S):
            lg, cache = T.decode_step(params, cfg, cache, toks[:, t],
                                      jnp.full((B,), t, jnp.int32))
            outs.append(lg)
        np.testing.assert_allclose(
            np.asarray(full, np.float32),
            np.asarray(jnp.stack(outs, 1), np.float32),
            atol=2e-2, rtol=2e-2)


def _per_layer_decode(params, cfg, cache, tokens, pos):
    """The per-layer decode oracle: the layer scan takes each layer's cache
    as ``xs`` and stacks ``nn.attn_decode_step``'s new caches as ``ys``."""
    spec = cfg.attn_spec(prefix_len=0)
    x = params["embed"][tokens][:, None, :]

    def body(x, scanned):
        layer, lcache = scanned
        h = nn.rmsnorm(layer["ln1"], x)
        y, lcache = nn.attn_decode_step(layer["attn"], h, lcache, pos, spec)
        x = x + y
        h = nn.rmsnorm(layer["ln2"], x)
        if cfg.moe is not None:
            return x + nn.moe_apply(layer["moe"], h, cfg.moe), lcache
        return x + nn.swiglu(layer["mlp"], h), lcache

    x, cache = jax.lax.scan(body, x, (params["layers"], cache))
    x = nn.rmsnorm(params["ln_f"], x)
    return T._unembed(params, cfg, x[:, 0, :]), cache


def _variant(name):
    if name == "swa_wrap":
        return dataclasses.replace(R.build("smollm-135m", smoke=True).cfg,
                                   window=8)
    arch = {"gqa": "smollm-135m", "mha": "stablelm-3b",
            "qkv_bias": "qwen2.5-14b", "moe": "mixtral-8x7b"}[name]
    return R.build(arch, smoke=True).cfg


class TestCarriedCacheDecode:
    """``decode_step`` carries the stacked cache through the layer scan and
    writes one token per layer in place; it must equal the per-layer scan
    bit for bit, logits and all three cache leaves, on every step."""

    @pytest.mark.parametrize("variant", ["gqa", "mha", "swa_wrap",
                                         "qkv_bias", "moe"])
    def test_bit_identical_to_per_layer_scan(self, variant):
        cfg = _variant(variant)
        if variant == "mha":
            assert cfg.num_heads == cfg.num_kv_heads
        else:
            assert cfg.num_heads > cfg.num_kv_heads
        params = T.init(jax.random.fold_in(KEY, 20), cfg)
        B, cache_len, steps = 4, 32, 20
        cache = T.init_cache(cfg, B, cache_len)
        W = cache["k"].shape[2]
        toks = jax.random.randint(jax.random.fold_in(KEY, 21), (steps, B),
                                  0, cfg.vocab)
        new = jax.jit(lambda c, t, p: T.decode_step(params, cfg, c, t, p))
        old = jax.jit(lambda c, t, p: _per_layer_decode(params, cfg, c, t,
                                                        p))
        # rows start at staggered positions; row 3 moves on even steps only
        # and otherwise is fed the megastep's dummy token at its next write
        # position, which its next move overwrites.
        written = np.array([0, 3, 5, 1], np.int32)
        c_new = c_old = cache
        for t in range(steps):
            moving = np.array([True, True, True, t % 2 == 0])
            tok = jnp.where(jnp.asarray(moving), toks[t], 0)
            pos = jnp.asarray(written)
            lg_new, c_new = new(c_new, tok, pos)
            lg_old, c_old = old(c_old, tok, pos)
            np.testing.assert_array_equal(np.asarray(lg_new, np.float32),
                                          np.asarray(lg_old, np.float32))
            for leaf in ("k", "v", "pos"):
                np.testing.assert_array_equal(
                    np.asarray(c_new[leaf], np.float32),
                    np.asarray(c_old[leaf], np.float32))
            written = written + moving
        if variant == "swa_wrap":      # the ring wrapped more than twice
            assert W == 8 and int(np.asarray(c_new["pos"]).max()) >= 2 * W


class TestParamCounts:
    @pytest.mark.parametrize("arch,expected_b", [
        ("smollm-135m", 0.135), ("qwen2.5-14b", 14.8),
        ("rwkv6-7b", 7.5), ("mixtral-8x7b", 46.7),
        ("kimi-k2-1t-a32b", 1041.0), ("whisper-base", 0.071),
        ("zamba2-7b", 6.8), ("paligemma-3b", 2.5),
    ])
    def test_published_sizes(self, arch, expected_b):
        api = R.build(arch)
        assert api.param_count / 1e9 == pytest.approx(expected_b, rel=0.1)

    def test_kimi_active_params(self):
        # published as 1.04T total, 32B activated
        api = R.build("kimi-k2-1t-a32b")
        assert api.active_param_count / 1e9 == pytest.approx(32.0, rel=0.1)
