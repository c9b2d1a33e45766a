"""Paper §6.4 / Fig. 6 — LLM inference with weights/KV in the capacity tier.

Two phases, per the paper's layer traffic analysis:
  * prefill — compute-bound, ~95% reads: the policy detects unidirectional
    traffic and withdraws (paper: +1.8%);
  * decode  — memory-bound token loop alternating attention (85% read) and
    FFN (60/40) traffic, with KV paging against the host pool (paper:
    +71.6%, 1.41 -> 2.42 tok/s for DeepSeek-671B).

Throughput proxy: modelled memory-time per token from the policy A/B on
the layer-traffic stream mix (``core.scheduler``). The kimi-k2 (1T) config
supplies the real per-token byte volumes (active params). These are paper
figures from a simulator, not serving speed: that is measured on the chip
by ``bench/run.py``.
"""

from __future__ import annotations

import time

from repro.core import channel as ch
from repro.core import scheduler as sched
from repro.core.requests import StreamSpec
from repro.models import registry as R

from benchmarks.common import SIM, Bench, write_csv


def _decode_specs(offered: float = 60.0, n: int = 8) -> list[StreamSpec]:
    """§6.4 layer mix: attention 85% reads / FFN 60-40, alternating."""
    # one token's forward pass moves every serving thread through the
    # same layer type together -> phase-correlated streams
    return [StreamSpec(name=f"layer{i}", pattern="llm_decode",
                       offered_gbps=offered / n, phase_steps=32)
            for i in range(n)]


def _prefill_specs(offered: float = 80.0, n: int = 8) -> list[StreamSpec]:
    return [StreamSpec(name=f"chunk{i}", pattern="uniform",
                       offered_gbps=offered / n, read_fraction=0.95)
            for i in range(n)]


def run(smoke: bool = False) -> Bench:
    b = Bench("llm_inference", provenance=SIM)
    api = R.build("kimi-k2-1t-a32b")
    bytes_per_token = api.active_param_count * 2.0     # bf16 reads
    sim_steps = 256 if smoke else 768     # smoke trims the sweeps

    # -- prefill: withdrawal keeps it neutral ------------------------------
    t0 = time.monotonic()
    res_p = sched.compare_policies(ch.CXL_512, _prefill_specs(),
                                   ("cfs", "hinted"),
                                   sim=sched.SimConfig(steps=sim_steps))
    us = (time.monotonic() - t0) * 1e6
    imp_p = sched.improvement(res_p, "hinted", "cfs")
    b.row("prefill", us, f"imp={imp_p:+.1%} (paper +1.8%)")

    # -- decode: mixed layer traffic on the capacity link -------------------
    t0 = time.monotonic()
    res_d = sched.compare_policies(ch.CXL_512, _decode_specs(120.0),
                                   ("cfs", "hinted"),
                                   sim=sched.SimConfig(
                                       steps=max(512, sim_steps)))
    us = (time.monotonic() - t0) * 1e6
    imp_d = sched.improvement(res_d, "hinted", "cfs")
    toks_a = res_d["cfs"]["gbps"] * 1e9 / bytes_per_token
    toks_b = res_d["hinted"]["gbps"] * 1e9 / bytes_per_token
    b.row("decode/stream-mix", us,
          f"tok/s {toks_a:.2f}->{toks_b:.2f} ({imp_d:+.1%}; "
          f"paper +71.6%: 1.41->2.42)")

    write_csv("fig6_llm.csv",
              ["phase", "cfs_gbps", "cxlaimpod_gbps", "improvement"],
              [["prefill", round(res_p["cfs"]["gbps"], 2),
                round(res_p["hinted"]["gbps"], 2), round(imp_p, 4)],
               ["decode", round(res_d["cfs"]["gbps"], 2),
                round(res_d["hinted"]["gbps"], 2), round(imp_d, 4)]])
    return b.done(f"prefill={imp_p:+.1%} decode={imp_d:+.1%}")


if __name__ == "__main__":
    print(run().render())
