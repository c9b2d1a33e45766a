"""The latent-attention, sparse-expert family at a tiny size on the CPU:
served through ``ServeEngine`` it agrees with the plain float32
reference, its float8 control does not, the harness runs its cell end to
end, and its counts are pinned at the configuration's sizes."""

import copy
import dataclasses
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check
from bench import run as R
from bench.families import mla_moe as F
from bench.references import mla_moe as ref
from bench_fixtures import CPU_PEAKS, ROOT, tiny_cell
from repro.configs import kimi_k2_1t
from repro.models import mla_moe as M
from repro.models import registry
from repro.serve import EngineConfig, ServeEngine

ARCH = "kimi-k2-1t-a32b"
CONFIG = json.loads((ROOT / "bench/configs/kimi-k2-instruct.json")
                    .read_text())
#: the tiny model's widths, as the configuration file names them; the
#: router keeps 32 experts of which the chip holds the first 8
TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
            q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128,
            moe_intermediate_size=32, num_hidden_layers=3,
            n_routed_experts=8, vocab_size=512)
TINY_PUBLISHED = {"num_hidden_layers": 5, "n_routed_experts": 32,
                  "vocab_size": 1024}
#: above the widest logit gap the program reads here (under 0.05) and
#: below what the float8 control reads, with the held experts always
#: chosen (``held_first``)
TINY_LIMIT = 0.1
SEED = 2 ** 31 + 11


def tiny_config() -> dict:
    cfg = copy.deepcopy(CONFIG)
    cfg.update(TINY)
    cfg["published"] = dict(TINY_PUBLISHED)
    cfg["engine"] = dict(cfg["engine"], max_batch=4, cache_len=128,
                         max_queue=256)
    cfg["check"] = {"limits": {"logit_gap": TINY_LIMIT}}
    return cfg


@pytest.fixture(autouse=True)
def tiny_program(monkeypatch):
    """The program's registry entry for Kimi at the tiny widths, so that
    the family's check of the file against the program holds; the JAX
    settings of the process stay as they were."""
    import repro.configs as configs
    real = configs.get_config
    full = real(ARCH)
    tiny = dataclasses.replace(
        full, d_model=64, num_heads=4, q_lora_rank=32, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, d_ff=128,
        moe_d_ff=32, num_layers=5, n_routed_experts=32, vocab=1024)

    def get_config(arch_id, smoke=False):
        return tiny if arch_id == ARCH and not smoke else real(arch_id,
                                                               smoke)

    monkeypatch.setattr(configs, "get_config", get_config)
    monkeypatch.setattr(R, "set_compile_cache", lambda: None)


def held_first(params):
    """The weights with a correction bias that puts the held experts
    (0-7) above every other: each row then routes to exactly them, and no
    near-tie between a held expert and another decides a logit. A near
    tie flips between bfloat16 and float32 (a held expert's whole share
    comes or goes), and at this size that swings the widest logit gap
    over the float8 control's."""
    bias = params["layers"]["moe"]["bias"]
    params["layers"]["moe"]["bias"] = bias.at[:, :8].add(
        jnp.asarray(1.0, bias.dtype))
    return params


@pytest.fixture
def model():
    api, sz = R.build_model(tiny_config())
    return api, sz, F.make_params(sz, SEED)


def serve_all(api, params, prompts, n_new, max_batch=4):
    eng = ServeEngine(api, params, EngineConfig(
        max_batch=max_batch, cache_len=128, prefill_chunk=4, max_queue=64,
        megastep=8, pipeline_depth=2, paging=False))
    rids = [eng.submit(p, n_new).rid for p in prompts]
    out = eng.run()
    return [np.asarray(out[r], np.int32) for r in rids]


def prompts_of(sz, n, length, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, sz.vocab, length).astype(np.int32)
            for _ in range(n)]


def test_build_takes_the_chip_share(model):
    api, sz, params = model
    assert (sz.layers, sz.held, sz.router_experts, sz.vocab) == \
        (3, 8, 32, 512)
    assert api.cfg.held == (0, 8)
    assert params["layers"]["moe"]["router"].shape == (2, 64, 32)
    assert params["layers"]["moe"]["w_gate"].shape == (2, 8, 64, 32)
    assert float(jnp.abs(params["layers"]["moe"]["bias"]).max()) > 0


def test_the_queue_holds_the_warmup_batch():
    """The harness's warm-up queues a whole batch before the first
    megastep admits it (``bench/warmup.py``), and the closed loop keeps
    its clients beyond the batch waiting."""
    eng = CONFIG["engine"]
    traffic = json.loads((ROOT / "bench/traffic/decode-closed320.json")
                         .read_text())
    assert eng["max_queue"] >= eng["max_batch"]
    assert eng["max_queue"] >= traffic["clients"] - eng["max_batch"]
    assert traffic["clients"] == 1.25 * eng["max_batch"]


def test_unreduced_key_must_be_the_programs():
    cfg = tiny_config()
    cfg["moe_intermediate_size"] += 1
    with pytest.raises(ValueError, match="moe_d_ff"):
        R.build_model(cfg)
    cfg = tiny_config()
    cfg["reduced"] = ["num_hidden_layers", "n_routed_experts"]
    with pytest.raises(ValueError, match="vocab_size"):
        R.build_model(cfg)
    cfg = tiny_config()
    cfg["topk_method"] = "greedy"
    with pytest.raises(ValueError, match="topk_method"):
        R.build_model(cfg)


def decode_logits(api, params, toks, cache_len=64):
    """Logits of every position, token by token through the program's
    decode step (prefill and decode alike go through it in the engine)."""
    B, S = toks.shape
    step = jax.jit(api.decode_step)
    cache = api.init_cache(B, cache_len)
    got = []
    for t in range(S):
        lg, cache = step(params, cache, toks[:, t],
                         jnp.full((B,), t, jnp.int32))
        got.append(np.asarray(lg, np.float32))
    return np.stack(got, axis=1)


def test_decode_through_the_latent_cache_matches_the_reference(model):
    """The absorbed decode through the latent cache against the
    reference's plain full forward, logit for logit: in float32 at full
    matmul precision they are the same sums; in bfloat16 (as served) they
    agree to rounding but where a near-tie in routing flips."""
    api, sz, params = model
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 40), 0, sz.vocab)
    want = np.asarray(ref.logits(params, toks, sz=sz))
    f32 = registry._mla_moe_api(ARCH, dataclasses.replace(
        api.cfg, dtype=jnp.float32))
    with jax.default_matmul_precision("highest"):
        exact = decode_logits(f32, jax.tree.map(
            lambda a: a.astype(jnp.float32), params), toks)
    np.testing.assert_allclose(exact, want, atol=2e-4)
    got = decode_logits(api, params, toks)
    close = np.abs(got - want).max(axis=-1) < 0.1
    assert close.mean() > 0.9


def test_served_tokens_match_the_reference_and_fp8_does_not(model):
    api, sz, params = model
    params = held_first(params)
    prompts = prompts_of(sz, 6, 20)
    outs = serve_all(api, params, prompts, 24)
    seqs = list(zip(prompts, outs))
    gaps = check.logit_gaps(ref, params, sz, seqs)
    control = check.logit_gaps(ref, params, sz, seqs, quant="fp8")
    assert gaps.size == 6 * 24
    assert gaps.max() < TINY_LIMIT < control.max()
    assert control.max() > 3 * gaps.max()


def test_held_margin_is_the_distance_to_the_top_k_boundary():
    """Top 2 of six experts, the first two held: a chosen held expert's
    lead over the first left out, an unchosen one's shortfall from the
    last chosen, the least of the held experts'."""
    sz = types.SimpleNamespace(top_k=2, held=2)
    sel = jnp.asarray([[0.9, 0.1, 0.5, 0.7, 0.3, 0.8],
                       [0.75, 0.1, 0.5, 0.7, 0.3, 0.8],
                       [0.69, 0.1, 0.75, 0.7, 0.3, 0.8],
                       [0.8, 0.79, 0.5, 0.7, 0.3, 0.1]])
    np.testing.assert_allclose(np.asarray(ref.held_margin(sel, sz)),
                               [0.2, 0.05, 0.06, 0.09], atol=1e-6)


def test_the_gap_reads_zero_only_near_a_held_routing_tie(model):
    """A position where some MoE layer has a held expert within
    ``ROUTE_MARGIN`` of the top-k boundary reads a gap of 0; every other
    position reads the reference's best logit less the target's."""
    api, sz, params = model
    toks = jax.random.randint(jax.random.PRNGKey(6), (2, 64), 0, sz.vocab)
    tgts = jax.random.randint(jax.random.PRNGKey(7), (2, 64), 0, sz.vocab)
    lg = np.asarray(ref.logits(params, toks, sz=sz))
    margin = np.asarray(ref.route_margins(params, toks, sz=sz))
    gap, best = ref.score(params, toks, tgts, sz=sz)
    want = lg.max(-1) - np.take_along_axis(
        lg, np.asarray(tgts)[..., None], axis=-1)[..., 0]
    near = margin < ref.ROUTE_MARGIN
    assert near.any() and not near.all()
    np.testing.assert_allclose(np.asarray(gap), np.where(near, 0.0, want),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(best), lg.argmax(-1))
    # with the held experts far above every other, none is near a tie
    far = np.asarray(ref.route_margins(held_first(params), toks, sz=sz))
    assert far.min() > ref.ROUTE_MARGIN


def test_a_row_is_served_the_same_alone_or_beside_others(model):
    """Dropless: nothing a row computes depends on the other rows."""
    api, sz, params = model
    prompts = prompts_of(sz, 4, 24, seed=5)
    alone = serve_all(api, params, prompts[:1], 16)[0]
    beside = serve_all(api, params, prompts, 16)[0]
    np.testing.assert_array_equal(alone, beside)
    # and logit for logit, in one decode step at a fixed batch
    step = jax.jit(api.decode_step)
    toks = jnp.asarray([7, 1, 2, 3], jnp.int32)
    pos = jnp.zeros((4,), jnp.int32)
    a, _ = step(params, api.init_cache(4, 8), toks, pos)
    b, _ = step(params, api.init_cache(4, 8), toks.at[1:].set(
        jnp.asarray([400, 17, 99])), pos)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))


def test_the_harness_runs_the_cell(monkeypatch):
    from bench import trace_reduce
    from test_bench_families import with_a_device
    monkeypatch.setattr(trace_reduce, "load",
                        with_a_device(trace_reduce.load))
    make = F.make_params
    monkeypatch.setattr(F, "make_params",
                        lambda sz, seed: held_first(make(sz, seed)))
    cell = dataclasses.replace(
        tiny_cell("closed", name="kimik2-decode", paging=False),
        config=tiny_config())
    res = R.run_cell(cell, SEED, 3.0, True, require_accelerator=False,
                     peaks=CPU_PEAKS)
    assert res["correct"] is True
    assert res["compiles_in_window"] == 0
    assert {"megastep_hbm_roofline", "step_mfu"} <= set(res["metrics"])


def test_yarn_tables_agree_with_the_program():
    """The reference's YaRN, written after the published code, gives the
    program's rotation: cos and sin of the program's frequencies."""
    sz = F.sizes(CONFIG)
    cos, sin = ref.yarn_tables(sz, 300)
    inv = M.yarn_inv_freq(kimi_k2_1t.FULL)
    ang = np.arange(300)[:, None] * inv[None, :].astype(np.float64)
    np.testing.assert_allclose(np.asarray(cos)[:, :32], np.cos(ang),
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(sin)[:, 32:], np.sin(ang),
                               atol=2e-4)
    assert ref.softmax_scale(sz) == pytest.approx(
        M.softmax_scale(kimi_k2_1t.FULL), rel=1e-12)


# by hand at the configuration's sizes; a change here moves every
# roofline and MFU reading of the cell
def test_counts_are_pinned():
    sz = F.sizes(CONFIG)
    H, D = 64, 7168
    attn = (D * 1536 + 1536 * H * 192 + D * 576 + 512 * H * 256
            + H * 128 * D)
    assert F.attention_params(sz) == attn == 101_122_048
    expert = 3 * D * 2048
    norms = 5 * (2 * D + 1536 + 512) + D
    moe = D * 384 + 384 + 9 * expert
    assert F.weight_bytes(sz) == 2 * (5 * attn + norms + 3 * D * 18432
                                      + 4 * moe + D * 20480)
    assert F.weight_bytes(sz) == 5_290_640_384
    assert F.latent_bytes_per_token(sz) == 2 * 576 * 5 == 5_760
    assert CONFIG["memory"]["kv_bytes_per_token"] == 5_760
    assert F.context_kv_bytes(sz, [0, 100, 1000]) == 5_760.0 * 1103
    per_token = (5 * attn + 3 * D * 18432
                 + 4 * (D * 384 + expert + 8 * 8 / 384 * expert)
                 + D * 20480)
    flops = 2.0 * per_token * 3 + 2.0 * 5 * H * (2 * 512 + 64) * 1103
    assert F.positions_flops(sz, [0, 100, 1000]) == pytest.approx(
        flops, rel=1e-15)
    assert F.positions_flops(sz, [0, 100, 1000]) == 8_359_862_272.0


def test_weight_bytes_cover_every_weight_but_the_embedding():
    sz = F.sizes(CONFIG)
    shapes = jax.eval_shape(lambda: F.make_params(sz, 1))
    total = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    assert total == CONFIG["memory"]["weights_bytes"]
    assert F.weight_bytes(sz) == total - 2 * sz.vocab * sz.d_model
