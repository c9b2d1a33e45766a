"""Paper §6.5 / Fig. 7 — vector database (HNSW) workload A/B, on the REAL
serving engine.

``VectorSearchTenant`` query streams run through ``ServeEngine``: the
dataset lives in duplex-paged pool blocks (built by a sequential write
stream while queries run), every step gathers the visited candidate
blocks and folds them through the Pallas ``l2_distance`` kernel, and the
distance-cache write-backs every few steps make the walk's traffic
mixed-direction. A/B: ``cfs`` vs the hint-seeded ``hinted`` admission
policy; the modelled serial/duplex ratio of the walk's real page traffic
is the paper's QPS lever. Paper: +9.1% QPS, -8.3% mean latency.
"""

from __future__ import annotations

import time

import jax

from repro.models import registry as R
from repro.serve import EngineConfig, ServeEngine, VectorSearchTenant

from benchmarks.common import (ENGINE, Bench, aggregate_link_stats,
                               update_bench_json, write_csv)


def _drive(api, params, policy: str, n_requests: int, steps: int) -> dict:
    eng = ServeEngine(api, params, EngineConfig(
        max_batch=2, cache_len=64, block_tokens=4, hbm_blocks=12,
        pool_blocks=128, prefill_chunk=2,
        max_queue=max(16, n_requests + 2), policy=policy, megastep=8))
    vec = eng.add_tenant(VectorSearchTenant(
        n_slots=2, n_queries=8, visits_per_step=3, data_blocks=24,
        load_per_step=2, result_every=4))
    for i in range(n_requests):
        vec.submit(n_steps=steps, arrival_step=2 * i)
    eng.run(max_steps=10_000)
    link = aggregate_link_stats(eng.paging_stats(), "/serve/vectordb")
    res = vec.result()
    # latency proxy: mean queue-to-completion residency in engine steps.
    done = list(vec.completed.values())
    lat = (sum(r.done_step - r.arrival_step for r in done)
           / max(len(done), 1))
    return {"queries": vec.queries_done, "link": link,
            "latency_steps": lat,
            "speedup": (link["serial_us"] / link["duplex_us"]
                        if link["duplex_us"] else 1.0),
            "checksum": res["checksum"]}


def run(smoke: bool = False) -> Bench:
    b = Bench("vectordb", provenance=ENGINE)
    steps = 12 if smoke else 32
    n_requests = 2 if smoke else 4
    api = R.build("smollm-135m", smoke=True)
    params = api.init(jax.random.PRNGKey(0))
    t0 = time.monotonic()
    res = {policy: _drive(api, params, policy, n_requests, steps)
           for policy in ("cfs", "hinted")}
    us = (time.monotonic() - t0) * 1e6
    h, c = res["hinted"], res["cfs"]
    imp = h["speedup"] / c["speedup"] - 1.0
    lat_imp = (c["latency_steps"] - h["latency_steps"]) \
        / max(c["latency_steps"], 1e-9)
    b.row("hnsw-search", us,
          f"{h['queries']} queries; duplex_speedup "
          f"cfs {c['speedup']:.2f}x -> hinted {h['speedup']:.2f}x "
          f"({imp:+.1%}; paper +9.1%); latency "
          f"{c['latency_steps']:.0f}->{h['latency_steps']:.0f} steps "
          f"({lat_imp:+.1%}; paper -8.3%); {h['link']['page_ins']} ins/"
          f"{h['link']['page_outs']} outs")
    update_bench_json("vectordb", {
        "duplex_speedup": round(h["speedup"], 4),
        "link_imp": round(imp, 4),
        "latency_steps": round(h["latency_steps"], 1)})
    write_csv("fig7_vectordb.csv",
              ["metric", "cfs", "cxlaimpod", "improvement"],
              [["duplex_speedup", round(c["speedup"], 4),
                round(h["speedup"], 4), round(imp, 4)],
               ["latency_steps", round(c["latency_steps"], 1),
                round(h["latency_steps"], 1), round(lat_imp, 4)]])
    return b.done(f"duplex_speedup={h['speedup']:.2f}x "
                  f"(paper +9.1%)")


if __name__ == "__main__":
    print(run().render())
