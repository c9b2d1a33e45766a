"""Sharding rules: coverage, divisibility, cache fallbacks (abstract mesh,
no devices needed)."""

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import abstract_mesh

from repro import configs as configs_lib
from repro.launch import sharding as sh
from repro.models import registry as R


def _mesh(multi_pod=False):
    if multi_pod:
        return abstract_mesh((2, 16, 16), ("pod", "data", "model"))
    return abstract_mesh((16, 16), ("data", "model"))


def _params_shape(api):
    return jax.eval_shape(lambda: api.init(jax.random.PRNGKey(0)))


@pytest.mark.parametrize("arch", list(configs_lib.ARCH_IDS))
class TestParamSpecs:
    def test_all_big_leaves_sharded(self, arch):
        """Every leaf > 1M elements must have a non-trivial spec —
        except under the pure-DP policy, where replication IS the policy
        (§Perf iteration 5: sub-GB models)."""
        api = R.build(arch)
        mesh = _mesh()
        if sh.parallelism(api, mesh)[1] is None:   # pure-DP arch
            pytest.skip("pure-DP policy replicates params by design")
        specs, unmatched = sh.param_specs(api, _params_shape(api), mesh)
        flat_specs = jax.tree.leaves(
            specs, is_leaf=lambda x: isinstance(x, P))
        flat_shapes = jax.tree.leaves(_params_shape(api))
        for spec, leaf in zip(flat_specs, flat_shapes):
            n = 1
            for d in leaf.shape:
                n *= d
            if n >= (1 << 20):
                assert any(p is not None for p in spec), \
                    f"large leaf {leaf.shape} replicated"

    def test_unmatched_only_small(self, arch):
        """Unmatched (replicated) params are only norms/scalars."""
        api = R.build(arch)
        specs, unmatched = sh.param_specs(api, _params_shape(api),
                                          _mesh())
        for path in unmatched:
            assert any(t in path for t in
                       ("ln", "norm", "scale", "mu", "w0", "u", "A_log",
                        "dt_bias", "D", "w_b", "b_out", "conv")), path

    def test_divisibility(self, arch):
        """Every sharded dim divides the product of its mesh axes."""
        api = R.build(arch)
        mesh = _mesh()
        specs, _ = sh.param_specs(api, _params_shape(api), mesh)
        flat_specs = jax.tree.leaves(
            specs, is_leaf=lambda x: isinstance(x, P))
        flat_shapes = jax.tree.leaves(_params_shape(api))
        for spec, leaf in zip(flat_specs, flat_shapes):
            for dim, part in enumerate(spec):
                if part is None:
                    continue
                axes = part if isinstance(part, tuple) else (part,)
                size = 1
                for a in axes:
                    size *= mesh.shape[a]
                assert leaf.shape[dim] % size == 0, (leaf.shape, spec)


class TestCacheSpecs:
    @pytest.mark.parametrize("arch,shape", [
        ("qwen2.5-14b", "decode_32k"),     # kv=8 -> seq-parallel fallback
        ("stablelm-3b", "decode_32k"),     # kv=32 -> head sharding
        ("rwkv6-7b", "long_500k"),         # batch=1 -> replicated batch
        ("zamba2-7b", "long_500k"),
        ("whisper-base", "decode_32k"),
        ("mixtral-8x7b", "long_500k"),
        ("kimi-k2-1t-a32b", "decode_32k"),  # latent cache (L,B,W,r)
    ])
    def test_decode_cells_divisible(self, arch, shape):
        api = R.build(arch)
        mesh = _mesh()
        inputs = R.input_specs(api, shape)
        specs = sh.cache_specs(api, inputs["cache"], mesh)
        flat_specs = jax.tree.leaves(
            specs, is_leaf=lambda x: isinstance(x, P))
        flat_shapes = jax.tree.leaves(inputs["cache"])
        for spec, leaf in zip(flat_specs, flat_shapes):
            for dim, part in enumerate(spec):
                if part is None:
                    continue
                axes = part if isinstance(part, tuple) else (part,)
                size = 1
                for a in axes:
                    size *= mesh.shape[a]
                assert leaf.shape[dim] % size == 0, (leaf.shape, spec)

    def test_gqa_kv_falls_back_to_sequence(self):
        """qwen kv=8 on tp=16: the ring axis takes the model sharding."""
        api = R.build("qwen2.5-14b")
        inputs = R.input_specs(api, "decode_32k")
        specs = sh.cache_specs(api, inputs["cache"], _mesh())
        k_spec = specs["k"]
        assert k_spec[3] is None           # kv heads replicated
        assert k_spec[2] == "model"        # ring axis sharded

    def test_mha_kv_shards_heads(self):
        """stablelm kv=32 divides tp=16: heads shard, ring replicated."""
        api = R.build("stablelm-3b")
        inputs = R.input_specs(api, "decode_32k")
        specs = sh.cache_specs(api, inputs["cache"], _mesh())
        assert specs["k"][3] == "model"


class TestBatchSpecs:
    def test_divisible_batch_sharded(self):
        api = R.build("smollm-135m")
        inputs = R.input_specs(api, "train_4k")
        specs = sh.batch_specs(inputs, _mesh())
        assert specs["tokens"][0] in ("data", ("data",))

    def test_multipod_folds_pod_into_dp(self):
        api = R.build("smollm-135m")
        inputs = R.input_specs(api, "train_4k")
        specs = sh.batch_specs(inputs, _mesh(multi_pod=True))
        assert specs["tokens"][0] == ("pod", "data")

    def test_batch_one_replicates(self):
        api = R.build("rwkv6-7b")
        inputs = R.input_specs(api, "long_500k")
        dspecs = sh.decode_input_specs(inputs, api, _mesh())
        assert dspecs["tokens"] == P(None)


class TestFsdpOverPod:
    def test_kimi_params_span_pods(self):
        api = R.build("kimi-k2-1t-a32b")
        mesh = _mesh(multi_pod=True)
        specs, _ = sh.param_specs(api, _params_shape(api), mesh)
        gate = specs["layers"]["moe"]["w_gate"]   # (L, E, D, FF)
        assert gate[1] == "model"                  # experts over TP
        assert gate[2] == ("pod", "data")          # FSDP spans pods
        # latent attention: (L, D, q_rank) down, (L, q_rank, H*192) up
        attn = specs["layers"]["attn"]
        assert attn["wq_a"] == P(None, ("pod", "data"), None)
        assert attn["wq_b"] == P(None, ("pod", "data"), "model")
        assert attn["wkv_b"] == P(None, ("pod", "data"), "model")
        shared = specs["layers"]["shared"]["w_gate"]   # (L, D, 2048)
        assert shared == P(None, ("pod", "data"), "model")
        dense = specs["dense_layers"]["mlp"]["w_down"]  # (1, 18432, D)
        assert dense == P(None, "model", ("pod", "data"))

    def test_dense_params_replicate_over_pod(self):
        api = R.build("llama3.2-3b")
        mesh = _mesh(multi_pod=True)
        specs, _ = sh.param_specs(api, _params_shape(api), mesh)
        wq = specs["layers"]["attn"]["wq"]         # (L, D, H*hd)
        assert wq[1] in ("data", ("data",))        # pod = pure DP
