"""``chip_smoke.py`` on the CPU at the smoke config: its serve, tenant and
four-chip phases pass their own checks, and ``main`` refuses to run
anywhere but a TPU before it builds a model. Also the compile-cache
helper the entry points share."""

import dataclasses
from pathlib import Path

import jax
import pytest

import chip_smoke
from repro import configs as configs_lib
from repro.launch import compile_cache
from repro.models import registry as R
from repro.serve import EngineConfig

# 5 filled blocks per request, 4 slots, 12 HBM blocks: oversubscribed,
# with room for the tenants' 6 reserved blocks beside 4 LLM fills.
CFG = EngineConfig(max_batch=4, cache_len=64, block_tokens=4,
                   hbm_blocks=12, megastep=8, pipeline_depth=2)
LOAD = chip_smoke.Workload(requests=6, prompt_len=8, gen=14,
                           arrival_every=2, tenant_steps=16)


@pytest.fixture(scope="module")
def api():
    return R.build(chip_smoke.ARCH, smoke=True)


@pytest.fixture(scope="module")
def params(api):
    return api.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def prompts(api):
    return chip_smoke.prompts_for(api, LOAD)


@pytest.fixture(scope="module")
def ref(api, params, prompts):
    return chip_smoke.reference_tokens(api, params, CFG, prompts, LOAD.gen)


class TestPhases:
    def test_reference_pads_to_engine_width(self, prompts, ref):
        # 6 requests at width 4: two batches, the second padded
        assert ref.shape == (LOAD.requests, LOAD.gen)

    def test_serve_phase(self, api, params, prompts, ref):
        res = chip_smoke.serve_phase(api, params, CFG, LOAD, prompts, ref)
        assert res["tokens"] == LOAD.requests * LOAD.gen
        assert res["page_ins"] > 0 and res["page_outs"] > 0
        assert res["kernel_calls"] > 0

    def test_serve_phase_catches_a_wrong_token(self, api, params, prompts,
                                               ref, monkeypatch):
        real_run = chip_smoke.ServeEngine.run

        def run_with_a_wrong_token(engine):
            outs = real_run(engine)
            rid = sorted(outs)[3]
            outs[rid] = list(outs[rid])
            outs[rid][5] = (outs[rid][5] + 1) % api.cfg.vocab
            return outs

        monkeypatch.setattr(chip_smoke.ServeEngine, "run",
                            run_with_a_wrong_token)
        with pytest.raises(chip_smoke.SmokeFailure,
                           match="request 3 diverges .* at token 5 without "
                                 "a tie"):
            chip_smoke.serve_phase(api, params, CFG, LOAD, prompts, ref)

    def test_parting_at_an_exact_tie_passes(self, api, params, prompts, ref):
        """Give token ``b`` the embedding row of the token ``a`` a
        request emits: the tied unembedding makes their logits equal
        everywhere and feeding either one leaves the same cache, so a
        sequence with ``b`` in place of ``a`` parts from the reference at
        an exact tie and stays greedy — and one with any other token
        does not."""
        i, j = 3, 5
        a = int(ref[i, j])
        b = a + 1 if a + 1 < api.cfg.vocab else a - 1
        twin = dict(params)
        twin["embed"] = params["embed"].at[b].set(params["embed"][a])
        ref = chip_smoke.reference_tokens(api, twin, CFG, prompts, LOAD.gen)
        assert ref[i, j] == min(a, b)       # argmax takes the first
        rids = list(range(LOAD.requests))
        got = ref.copy()
        got[i, j] = max(a, b)
        outs = dict(zip(rids, got))
        assert chip_smoke.check_tokens(api, twin, CFG, prompts, outs, rids,
                                       ref, "tie") == 1
        got[i, j] = (max(a, b) + 1) % api.cfg.vocab
        with pytest.raises(chip_smoke.SmokeFailure,
                           match=f"request {i} diverges .* at token {j} "
                                 "without a tie"):
            chip_smoke.check_tokens(api, twin, CFG, prompts,
                                    dict(zip(rids, got)), rids, ref, "tie")

    def test_tenant_phase(self, api, params, prompts, ref):
        res = chip_smoke.tenant_phase(api, params, CFG, LOAD, prompts, ref)
        assert res["kv_ops"] > 0 and res["kv_blocks_checked"] > 0
        assert res["vec_queries"] > 0 and res["vec_blocks_visited"] > 0
        assert res["page_ins"] > 0 and res["page_outs"] > 0

    def test_four_chip_phase(self, api, params, prompts, capsys):
        if jax.device_count() < 4:
            pytest.skip("needs 4 devices (run under XLA_FLAGS="
                        "--xla_force_host_platform_device_count=4)")
        # halved to 8 HBM blocks a shard, under its one request's 12
        cfg = dataclasses.replace(CFG, hbm_blocks=16)
        load = dataclasses.replace(LOAD, gen=40)
        res = chip_smoke.four_chip_phase(api, params, cfg, load, prompts)
        assert res["requests"] == LOAD.requests
        assert res["page_ins"] > 0 and res["page_outs"] > 0
        out = capsys.readouterr().out
        for s in range(4):
            assert f"pool shard {s}: hbm on [{s}]" in out


class TestMain:
    def test_refuses_cpu_before_building_a_model(self, monkeypatch,
                                                 capsys):
        def no_build(*a, **kw):
            raise AssertionError("built a model off the TPU")

        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        monkeypatch.setattr(chip_smoke.R, "build", no_build)
        with pytest.raises(SystemExit) as exc:
            chip_smoke.main([])
        assert exc.value.code not in (0, None)
        assert "JAX_PLATFORMS='cpu'" in str(exc.value.code)
        assert capsys.readouterr().out == ""

    def test_default_config_is_full_width_and_oversubscribed(self):
        cfg = configs_lib.get_config(chip_smoke.ARCH, smoke=False)
        assert (cfg.num_layers, cfg.d_model, cfg.vocab) == (30, 576, 49152)
        e = chip_smoke.ENGINE
        load = chip_smoke.Workload()
        blocks = -(-(load.prompt_len + load.gen) // e.block_tokens)
        assert e.max_batch * blocks > e.hbm_blocks
        assert load.prompt_len + load.gen <= e.cache_len


class TestCompileCache:
    def test_env_var_wins(self, monkeypatch, tmp_path):
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
        assert compile_cache.compile_cache_dir() == str(tmp_path)

    def test_default_is_fixed_inside_the_checkout(self, monkeypatch):
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        first = compile_cache.compile_cache_dir()
        assert first == compile_cache.compile_cache_dir()
        root = Path(__file__).resolve().parents[1]
        assert Path(first) == root / ".jax_cache"
        ignored = (root / ".gitignore").read_text().split()
        assert ".jax_cache/" in ignored

    def test_enable_points_jax_at_it(self, monkeypatch, tmp_path):
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
        before = jax.config.jax_compilation_cache_dir
        try:
            assert compile_cache.enable_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
