"""Dry-run tooling: HLO collective parser, roofline analysis, parallelism
policy (pure functions — no device state)."""

import pytest
from repro.launch.mesh import abstract_mesh

from benchmarks.roofline import analyse
from repro.launch.dryrun import _shape_bytes, parse_collectives
from repro.launch.sharding import parallelism
from repro.models import registry as R

HLO = """
ENTRY %main {
  %p = bf16[1024,512]{1,0} parameter(0)
  %ag = bf16[1024,8192]{1,0} all-gather(%p), dimensions={1}
  %ar = f32[256,128]{1,0} all-reduce(%x), to_apply=%sum
  %ars = f32[64]{0} all-reduce-start(%y), to_apply=%sum
  %cp = bf16[32,32]{1,0} collective-permute(%z), source_target_pairs={{0,1}}
  %a2a = (f32[16,16]{1,0}, f32[16,16]{1,0}) all-to-all(%a, %b), dimensions={0}
  %dot = bf16[1024,1024]{1,0} dot(%p, %p)
}
"""


class TestCollectiveParser:
    def test_shape_bytes(self):
        assert _shape_bytes("bf16[1024,512]") == 1024 * 512 * 2
        assert _shape_bytes("f32[64] pred[8]") == 64 * 4 + 8
        assert _shape_bytes("s8[]") == 1.0

    def test_parse(self):
        out = parse_collectives(HLO)
        assert out["bytes_by_op"]["all-gather"] == 1024 * 8192 * 2
        # all-reduce + all-reduce-start both counted
        assert out["bytes_by_op"]["all-reduce"] == 256 * 128 * 4 + 64 * 4
        assert out["bytes_by_op"]["collective-permute"] == 32 * 32 * 2
        assert out["bytes_by_op"]["all-to-all"] == 2 * 16 * 16 * 4
        assert out["counts"]["all-reduce"] == 2
        # the dot is not a collective
        assert out["total_bytes"] < 1024 * 1024 * 2 + 18_000_000


class TestRooflineAnalyse:
    def _rec(self, **kw):
        base = {
            "status": "ok", "arch": "x", "shape": "train_4k",
            "mesh": "pod", "n_devices": 256, "unroll": True,
            "model_flops": 1e15, "recurrence_flops": 0.0,
            "cost_analysis": {"flops": 1e13, "bytes accessed": 1e12},
            "collectives": {"total_bytes": 5e10},
        }
        base.update(kw)
        return base

    def test_terms(self):
        a = analyse(self._rec())
        assert a["compute_s"] == pytest.approx(1e13 / 197e12)
        assert a["memory_s"] == pytest.approx(1e12 / 819e9)
        assert a["collective_s"] == pytest.approx(1.0)
        assert a["dominant"] == "memory"   # 1.22s memory vs 1.0s coll

    def test_bound_mfu(self):
        a = analyse(self._rec(collectives={"total_bytes": 5e11}))
        # collective_s = 10s dominates; useful = 1e15/256/197e12
        useful = 1e15 / 256 / 197e12
        assert a["mfu_bound"] == pytest.approx(useful / 10.0)
        assert a["dominant"] == "collective"

    def test_recurrence_added(self):
        a = analyse(self._rec(recurrence_flops=2.56e15))
        assert a["compute_s"] == pytest.approx((1e13 + 1e13) / 197e12)

    def test_rolled_flagged(self):
        assert analyse(self._rec(unroll=False))["rolled"] is True

    def test_error_cells_skipped(self):
        assert analyse({"status": "error"}) is None


class TestParallelismPolicy:
    def test_pure_dp_for_small_models(self):
        mesh = abstract_mesh((16, 16), ("data", "model"))
        F, T, DP = parallelism(R.build("smollm-135m"), mesh)
        assert F is None and T is None
        assert DP == ("data", "model")

    def test_2d_for_big_dense(self):
        mesh = abstract_mesh((16, 16), ("data", "model"))
        F, T, DP = parallelism(R.build("qwen2.5-14b"), mesh)
        assert F == ("data",) and T == "model"

    def test_fsdp_over_pod_for_kimi(self):
        mesh = abstract_mesh((2, 16, 16), ("pod", "data", "model"))
        api = R.build("kimi-k2-1t-a32b")
        F, T, DP = parallelism(api, mesh)
        assert F == ("pod", "data")
        assert DP == ("pod", "data")
        # heads and the 384 routed experts split over the model axis
        assert T == "model"
        assert (api.cfg.num_heads, api.cfg.held[1]) == (64, 384)
