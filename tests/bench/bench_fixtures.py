"""A tiny cell for the benchmark's CPU tests: smollm-135m's configuration
file at toy widths, a small engine and short requests, with the metric
lists of ``BENCHMARK.json``'s chat cell."""

from __future__ import annotations

import copy
import dataclasses
import json
from pathlib import Path

from bench.spec import Cell

ROOT = Path(__file__).resolve().parents[2]
CHAT = "smollm135m-chat"
#: peaks for a CPU run, which has no published ones
CPU_PEAKS = {"bf16_flops": 1e12, "hbm_bytes_s": 1e11}
#: limits for the tiny model: above what the program reads here (a
#: widest logit gap under 0.01, pool rows under 0.03 of their largest
#: magnitude) and below what the float8 control reads
TINY_LIMITS = {"logit_gap": 0.05, "kv_err": 0.05}
#: the tiny model's widths, as the program's registry names them
TINY_WIDTHS = dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                   d_ff=128, vocab=512)


def register_tiny(monkeypatch) -> None:
    """Point the program's registry entry for smollm-135m at the tiny
    widths, so that the harness's check of a configuration file against
    the program holds for ``tiny_config``."""
    import repro.configs as configs
    real = configs.get_config
    tiny = dataclasses.replace(real("smollm-135m"), **TINY_WIDTHS)

    def get_config(arch_id, smoke=False):
        return tiny if arch_id == "smollm-135m" and not smoke else real(
            arch_id, smoke)

    monkeypatch.setattr(configs, "get_config", get_config)


def tiny_config(paging: bool = True) -> dict:
    cfg = json.loads((ROOT / "bench/configs/smollm-135m.json").read_text())
    w = TINY_WIDTHS
    cfg.update(hidden_size=w["d_model"], num_attention_heads=w["num_heads"],
               num_key_value_heads=w["num_kv_heads"],
               intermediate_size=w["d_ff"], vocab_size=w["vocab"],
               num_hidden_layers=w["num_layers"])
    cfg["engine"] = dict(cfg["engine"], max_batch=4, cache_len=128,
                         hbm_blocks=32, pool_blocks=64, max_queue=256)
    cfg["check"] = {"limits": dict(TINY_LIMITS)}
    if not paging:
        cfg["engine"]["paging"] = False
        del cfg["check"]["limits"]["kv_err"]
    return cfg


def tiny_traffic(loop: str = "open") -> dict:
    return {"loop": loop, "rate_per_s": 100.0, "clients": 6, "lead_s": 0.5,
            "prompt": {"median": 24, "sigma": 0.6, "min": 16, "max": 48},
            "output": {"median": 24, "sigma": 0.6, "min": 8, "max": 64}}


def tiny_cell(loop: str = "open", name: str = CHAT,
              paging: bool = True) -> Cell:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    return Cell(name=name, chips=1, config=tiny_config(paging),
                traffic=tiny_traffic(loop),
                end_to_end=tuple(copy.deepcopy(m) for m in bench["end_to_end"]
                                 if applies(m)),
                per_layer=tuple(copy.deepcopy(m) for m in bench["per_layer"]
                                if applies(m)))
