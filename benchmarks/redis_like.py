"""Paper §6.3 / Fig. 5 — Redis-style KV-store workload A/B, on the REAL
serving engine.

Five access patterns (read-heavy 1:10, write-heavy 10:1, pipelined,
sequential, gaussian) run as ``KVStoreTenant`` op streams through
``ServeEngine``: GET/SET block ops execute against the duplex-paged
``PagedKVPool`` (preloaded keyspace larger than the HBM working set, so
misses and evictions are real page traffic), admission is the A/B'd
policy (``cfs`` baseline vs the hint-seeded ``hinted`` policy), and the
withdrawal scopes (`/serve/redis/{read,write}_heavy`) keep the
unidirectional patterns off the fused duplex kernel.

Requests are *service-driven* (``n_ops``): every stream must deliver the
same op budget, with ops queued behind the per-direction duplex service
budget, and all streams arrive together into fewer tenant slots than
streams — so per-pattern ``latency_steps`` (arrival -> completion) is a
real measurement of how fast each pattern's direction mix drains under
each policy's admission pairing, and ``link_imp`` is the measured
hinted-vs-cfs delta of the modelled serial/duplex ratio (its
bandwidth-normalized exploitation of the full-duplex link). Paper: +7.4%
avg throughput (+150% sequential, +69% pipelined; read-heavy neutral
*with* withdrawal), -6% avg p99.

Known measured trade-off (committed knowingly): on ``sequential``,
hinted's balanced read/write pairing drains ops faster — the latency
A/B improves (``latency_imp`` > 0, the paper's serving metric) — but
its *paging* mix gets more write-dominated (balanced SET service means
more full-block invalidations, which suppress page-ins), so the link
overlap ratio ``link_imp`` goes negative. The two metrics answer
different questions; latency is the headline, link_imp is the honest
per-policy overlap measurement, and both are reported.
"""

from __future__ import annotations

import time

import jax

from repro.models import registry as R
from repro.serve import EngineConfig, KVStoreTenant, ServeEngine

from benchmarks.common import (ENGINE, Bench, aggregate_link_stats,
                               update_bench_json, write_csv)

PAPER_THROUGHPUT = {
    "read_heavy": 0.0, "write_heavy": 0.0, "pipelined": 0.69,
    "sequential": 1.50, "gaussian": 0.14,
}
#: patterns whose traffic is mixed-direction (duplex_speedup > 1 is the
#: acceptance signal); the two unidirectional patterns withdraw.
MIXED_PATTERNS = ("pipelined", "sequential", "gaussian")


def _drive(api, params, pattern: str, policy: str, n_streams: int,
           steps: int, seed: int = 0) -> dict:
    eng = ServeEngine(api, params, EngineConfig(
        max_batch=2, cache_len=64, block_tokens=4, hbm_blocks=10,
        pool_blocks=128, prefill_chunk=2,
        max_queue=max(16, n_streams + 2), policy=policy, megastep=8))
    kv = eng.add_tenant(KVStoreTenant(
        n_slots=4, ops_per_step=2, store_blocks=24, seed=seed))
    kv.preload(24)
    eng.pool.reset_stats()           # bill serving traffic only
    for i in range(n_streams):
        # sequential: readers first, then writers — the adversarial
        # submit order a fair FIFO baseline admits unbalanced.
        phase = ("read" if i < n_streams // 2 else "write") \
            if pattern == "sequential" else None
        # service-driven completion: every stream must deliver the same
        # op budget over a generous schedule horizon, with ops queued
        # behind the per-direction duplex service budget. All streams
        # arrive together and outnumber the tenant slots, so the
        # admission policy really chooses the running set: a
        # duplex-aware policy pairs opposite-direction streams (full
        # service rate), a direction-oblivious one admits in submit
        # order. Both the completion step and the link overlap are then
        # per-pattern, per-policy measurements rather than shared
        # schedule constants.
        kv.submit(pattern, n_steps=6 * steps, n_ops=steps,
                  arrival_step=0, phase=phase)
    eng.run(max_steps=10_000)
    link = aggregate_link_stats(eng.paging_stats(), "/serve/redis")
    # latency: mean queue-to-completion residency in engine steps
    # (arrival -> done), the serving analogue of the paper's p99 story —
    # measured per pattern from each request's actual completion step.
    done = list(kv.completed.values())
    lat = (sum(r.done_step - r.arrival_step for r in done)
           / max(len(done), 1))
    return {"ops": kv.ops_done, "link": link,
            "latency_steps": lat,
            "host_dispatches": eng.stats()["host_dispatches"],
            "steps": eng.step_count,
            "speedup": (link["serial_us"] / link["duplex_us"]
                        if link["duplex_us"] else 1.0)}


def run(smoke: bool = False) -> Bench:
    b = Bench("redis", provenance=ENGINE)
    steps = 16 if smoke else 64
    n_streams = 4 if smoke else 6
    api = R.build("smollm-135m", smoke=True)
    params = api.init(jax.random.PRNGKey(0))
    rows = []
    section = {}
    imps = []
    lat_imps = []
    for pattern in PAPER_THROUGHPUT:
        t0 = time.monotonic()
        res = {policy: _drive(api, params, pattern, policy, n_streams,
                              steps)
               for policy in ("cfs", "hinted")}
        us = (time.monotonic() - t0) * 1e6
        h, c = res["hinted"], res["cfs"]
        # bandwidth-normalized A/B: each policy's modelled effective link
        # bandwidth is (bytes moved / duplex-planned time), i.e. its
        # serial/duplex speedup — how much of the full-duplex channel the
        # policy's running set actually exploited. (Traffic volumes
        # differ across policies — different admission pairings,
        # different miss patterns — so raw link time is not comparable.)
        imp = h["speedup"] / c["speedup"] - 1.0
        lat_imp = (c["latency_steps"] - h["latency_steps"]) \
            / max(c["latency_steps"], 1e-9)
        imps.append(imp)
        lat_imps.append(lat_imp)
        rows.append([pattern, h["ops"], round(c["speedup"], 4),
                     round(h["speedup"], 4), round(imp, 4),
                     round(c["latency_steps"], 1),
                     round(h["latency_steps"], 1),
                     h["link"]["page_ins"], h["link"]["page_outs"]])
        section[pattern] = {"duplex_speedup": round(h["speedup"], 4),
                            "link_imp": round(imp, 4),
                            "latency_steps": round(h["latency_steps"], 1),
                            "latency_imp": round(lat_imp, 4)}
        b.row(pattern, us,
              f"{h['ops']} ops; duplex_speedup "
              f"cfs {c['speedup']:.2f}x -> hinted {h['speedup']:.2f}x "
              f"({imp:+.1%}; paper {PAPER_THROUGHPUT[pattern]:+.0%}); "
              f"latency {c['latency_steps']:.0f}->"
              f"{h['latency_steps']:.0f} steps ({lat_imp:+.1%}; paper "
              f"-6% p99); {h['link']['page_ins']} ins/"
              f"{h['link']['page_outs']} outs")
    update_bench_json("redis", section)
    write_csv("fig5_redis.csv",
              ["pattern", "hinted_ops", "cfs_duplex_speedup",
               "hinted_duplex_speedup", "improvement",
               "cfs_latency_steps", "hinted_latency_steps", "page_ins",
               "page_outs"], rows)
    avg = sum(imps) / len(imps)
    avg_lat = sum(lat_imps) / len(lat_imps)
    return b.done(f"avg link imp={avg:+.1%} (paper +7.4%), avg latency "
                  f"imp={avg_lat:+.1%} (paper -6% p99)")


if __name__ == "__main__":
    print(run().render())
