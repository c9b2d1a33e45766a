"""Bring-up check: serve full-width smollm-135m on a TPU, through the
normal entry points, and check every answer.

  python chip_smoke.py               # one chip: serve phase + tenant phase
  python chip_smoke.py --four-chips  # four chips: sharded serving only

Weights are random (``PRNGKey(0)``), prompts are seeded. The default run
builds ``smollm-135m`` at its published widths (``R.build(...,
smoke=False)``), serves 16 staggered requests through ``ServeEngine`` on
an oversubscribed KV pool and checks every request token for token
against ``serve.reference_decode`` at the engine's batch width, then
serves the same requests beside a KV-store and a vector-search tenant
and checks both tenants' results. ``--four-chips`` serves the same
requests on ``ShardedServeEngine`` over a (4, 1) data mesh and checks
them against the single-chip engine in the same process.

Random weights give flat bf16 logits with exact ties at the top (a few
percent of positions at smollm-135m's vocabulary), and two XLA programs
may break a tie differently. A request that parts from its reference is
accepted only where it parted at such a tie and every token it produced
is the reference model's greedy choice on its own sequence
(``check_tokens``).

The script refuses to run anywhere but a TPU: JAX is pinned to the
``tpu`` platform before anything is built, so a failed TPU start raises
instead of falling back to the CPU. Any failed check exits nonzero. The
last line of standard output is one JSON object naming the device; the
wall-clock seconds printed before it are bring-up figures (compile, then
a steady rerun), not benchmark numbers.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

# ruff: noqa: E402  (the repo's src/ must be on the path before importing it)
sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_debug_mesh
from repro.models import registry as R
from repro.serve import (EngineConfig, KVStoreTenant,
                         ServeEngine, ShardedServeEngine,
                         VectorSearchTenant, reference_decode)
from repro.serve.workloads import _synth_blocks, kv_value_seed

ARCH = "smollm-135m"

#: 12 KV blocks per request against 32 HBM blocks for 8 slots: the pool
#: is oversubscribed, so blocks page out and back in.
ENGINE = EngineConfig(max_batch=8, cache_len=1024, block_tokens=16,
                      hbm_blocks=32, megastep=8, pipeline_depth=2)


@dataclasses.dataclass(frozen=True)
class Workload:
    requests: int = 16
    prompt_len: int = 128
    gen: int = 64
    arrival_every: int = 2
    tenant_steps: int = 32


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def require_tpu():
    """Pin JAX to the TPU and return its first device; exit nonzero,
    naming what was found, on anything else. Touches no backend when the
    environment already holds JAX to other platforms."""
    env = os.environ.get("JAX_PLATFORMS", "")
    if env and "tpu" not in env.split(","):
        raise SystemExit(f"chip_smoke: JAX_PLATFORMS={env!r} holds JAX "
                         f"off the TPU; this check runs on a chip only")
    jax.config.update("jax_platforms", "tpu")
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: found {dev.platform} "
                         f"({dev.device_kind}), not a TPU")
    return dev


def prompts_for(api, load: Workload) -> np.ndarray:
    """(requests, prompt_len) seeded prompts, as ``launch/serve.py``."""
    key = jax.random.PRNGKey(1)
    return np.stack([np.asarray(jax.random.randint(
        jax.random.fold_in(key, i), (load.prompt_len,), 0, api.cfg.vocab))
        for i in range(load.requests)])


def reference_tokens(api, params, cfg: EngineConfig,
                     prompts: np.ndarray, gen: int) -> np.ndarray:
    """``reference_decode`` in batches of the engine's width (the last
    batch padded with copies), so a mismatch points at the program and
    not at a different matmul shape."""
    B = cfg.max_batch
    n = prompts.shape[0]
    padded = prompts[np.arange(n + -n % B) % n]
    outs = [np.asarray(reference_decode(
        api, params, jnp.asarray(padded[i:i + B]), gen,
        cache_len=cfg.cache_len)) for i in range(0, padded.shape[0], B)]
    return np.concatenate(outs)[:n]


def submit_requests(engine, prompts: np.ndarray, load: Workload) -> list:
    return [engine.submit(prompts[i], load.gen,
                          arrival_step=i * load.arrival_every).rid
            for i in range(prompts.shape[0])]


def teacher_forced(api, params, cfg: EngineConfig, prompts: np.ndarray,
                   toks: np.ndarray, *others: np.ndarray):
    """Feed each prompt and then ``toks`` through ``decode_step`` at the
    engine's batch width, as ``reference_decode`` does, and return the
    largest logit before every generated token and the logit of the token
    each of ``toks, *others`` put there: ``(top, at)``, shaped
    ``(n, gen)`` and ``(1 + len(others), n, gen)``."""
    B = cfg.max_batch
    n, gen = toks.shape
    P = prompts.shape[1]
    rows = np.arange(n + -n % B) % n
    seqs = np.concatenate([prompts, toks], 1)[rows]
    cand = np.stack([toks, *others])[:, rows]
    step = jax.jit(api.decode_step, donate_argnums=(1,))
    tops, ats = [], []
    for b in range(0, seqs.shape[0], B):
        cache = api.init_cache(B, cfg.cache_len)
        batch = jnp.asarray(seqs[b:b + B])
        top, at = [], []
        for t in range(P + gen - 1):
            logits, cache = step(params, cache, batch[:, t],
                                 jnp.full((B,), t, jnp.int32))
            if t >= P - 1:
                lf = logits.astype(jnp.float32)
                top.append(lf.max(-1))
                pick = jnp.asarray(cand[:, b:b + B, t - P + 1])
                at.append(jnp.take_along_axis(lf, pick.T, -1).T)
        tops.append(np.asarray(jnp.stack(top, 1)))
        ats.append(np.asarray(jnp.stack(at, 2)))
    return np.concatenate(tops)[:n], np.concatenate(ats, 1)[:, :n]


def check_tokens(api, params, cfg: EngineConfig, prompts: np.ndarray,
                 outs: dict, rids: list, ref: np.ndarray, label: str) -> int:
    """Every request's tokens must equal ``ref`` — or part from it where
    greedy decoding met an exact tie of the bf16 logits and then stay the
    reference model's greedy choice. Two XLA programs may break such a tie
    differently, so a request that parts from ``ref`` is checked by
    feeding its own tokens back through the reference (teacher forcing):
    at the first difference both its token and ``ref``'s must hold the
    largest logit, and from there on every token of its own must too.
    Returns how many requests parted at a tie."""
    got = []
    for i, rid in enumerate(rids):
        g = np.asarray(outs.get(rid, ()))
        if g.shape != ref[i].shape:
            raise SmokeFailure(f"{label}: request {i} returned {g.shape[0]} "
                               f"tokens, want {ref[i].shape[0]}")
        got.append(g)
    got = np.stack(got)
    parted = np.flatnonzero((got != ref).any(1))
    if not parted.size:
        return 0
    top, (at_got, at_ref) = teacher_forced(api, params, cfg, prompts[parted],
                                           got[parted], ref[parted])
    for k, i in enumerate(parted):
        j = int(np.argmax(got[i] != ref[i]))
        if not at_got[k, j] == at_ref[k, j] == top[k, j]:
            raise SmokeFailure(
                f"{label}: request {i} diverges from the reference at token "
                f"{j} without a tie: got {got[i, j]} (logit "
                f"{at_got[k, j]}), want {ref[i, j]} (logit {at_ref[k, j]}), "
                f"largest logit {top[k, j]}")
        off = np.flatnonzero(at_got[k] != top[k])
        if off.size:
            t = int(off[0])
            raise SmokeFailure(
                f"{label}: request {i} token {t} ({got[i, t]}, logit "
                f"{at_got[k, t]}) is not the reference's greedy choice "
                f"(largest logit {top[k, t]})")
    return int(parted.size)


def check_paging(st: dict, label: str) -> None:
    check(st["page_ins"] > 0, f"{label}: no page-ins")
    check(st["page_outs"] > 0, f"{label}: no page-outs")
    check(st["kernel_calls"] > 0, f"{label}: no stream-kernel calls")


def serve_phase(api, params, cfg: EngineConfig, load: Workload,
                prompts: np.ndarray, ref: np.ndarray) -> dict:
    """Serve the workload on ``ServeEngine``, check every request against
    the reference and that the pool paged through the stream kernels."""
    engine = ServeEngine(api, params, cfg)
    rids = submit_requests(engine, prompts, load)
    outs, run_s = timed(engine.run)
    tied = check_tokens(api, params, cfg, prompts, outs, rids, ref, "serve")
    st = engine.paging_stats()
    check_paging(st, "serve")
    return {"steps": engine.step_count,
            "tokens": sum(len(outs[r]) for r in rids),
            "parted_at_tie": tied, "run_s": run_s,
            **{k: st[k] for k in ("page_ins", "page_outs",
                                  "kernel_calls")}}


def stored_value(pool, block: int):
    """A pool block's current value as float32: its HBM row when
    resident, else its dequantized host copy (int8 tolerance), else
    None."""
    slot = pool.slot_of[block]
    if slot >= 0:
        return np.asarray(pool.hbm[slot], np.float32)
    hslot = pool.host.slot_of[block]
    if not pool._has_host[block] or hslot < 0:
        return None
    return (np.asarray(pool.host_q[hslot], np.float32)
            * np.asarray(pool.host_scale[hslot], np.float32))


def tenant_phase(api, params, cfg: EngineConfig, load: Workload,
                 prompts: np.ndarray, ref: np.ndarray) -> dict:
    """The serve phase with a KV-store and a vector-search tenant on the
    same pool (as ``launch/serve.py --tenants redis,vectordb``). LLM
    tokens must still pass ``check_tokens``; every value the KV store set
    must be in the pool, in HBM or in the host tier; and the vector
    walk's best distances must equal a brute-force scan of the blocks it
    visited."""
    engine = ServeEngine(api, params, cfg)
    kv = engine.add_tenant(KVStoreTenant(n_slots=2, ops_per_step=1,
                                         store_blocks=16))
    kv.preload(16)
    kv.submit("sequential", n_steps=load.tenant_steps)
    kv.submit("sequential", n_steps=load.tenant_steps)
    vec = engine.add_tenant(VectorSearchTenant(
        n_slots=1, visits_per_step=2, data_blocks=12))
    vreq = vec.submit(n_steps=load.tenant_steps)
    rids = submit_requests(engine, prompts, load)
    outs = engine.run()
    tied = check_tokens(api, params, cfg, prompts, outs, rids, ref,
                        "tenants")

    T, D = engine.pool.block_shape
    check(kv.ops_done > 0, "kv store: no ops served")
    check(kv.result() != 0.0, "kv store: GETs read no data")
    check(len(kv._version) > 0, "kv store: no value was set")
    for b, version in kv._version.items():
        want = np.asarray(_synth_blocks(
            jnp.asarray([kv_value_seed(b, version)], np.int32),
            tokens=T, dims=D)[0], np.float32)
        got = stored_value(engine.pool, b)
        check(got is not None, f"kv store: block {b} lost its value")
        err = float(np.abs(got - want).max())
        check(err <= 1.0 / 127.0 + 0.05,
              f"kv store: block {b} off its SET value by {err}")

    res = vec.result()
    check(vreq.rid in res["best"], "vector search: request unfinished")
    best = res["best"][vreq.rid]
    seeds = jnp.asarray([vec.data_seed(i)
                         for i in sorted(vreq.work.visited)], np.int32)
    check(seeds.size > 0, "vector search: no block visited")
    data = np.asarray(_synth_blocks(seeds, tokens=T, dims=D),
                      np.float32).reshape(-1, D)
    q = np.asarray(vreq.work.queries, np.float32)
    want = ((q[:, None, :] - data[None, :, :]) ** 2).sum(-1).min(1)
    if not np.allclose(best, want, rtol=1e-2, atol=0.05 * D / 32):
        raise SmokeFailure(f"vector search: best distances {best} != "
                           f"brute force {want}")
    check(res["checksum"] > 0, "vector search: empty checksum")
    st = engine.paging_stats()
    check_paging(st, "tenants")
    return {"parted_at_tie": tied,
            "kv_ops": kv.ops_done, "kv_blocks_checked": len(kv._version),
            "vec_queries": vec.queries_done,
            "vec_blocks_visited": int(seeds.size),
            **{k: st[k] for k in ("page_ins", "page_outs",
                                  "kernel_calls")}}


def four_chip_phase(api, params, cfg: EngineConfig, load: Workload,
                    prompts: np.ndarray) -> dict:
    """Serve on ``ShardedServeEngine`` over a (4, 1) data mesh and check
    every request (``check_tokens``), and its admission and completion
    steps, against the single-chip ``ServeEngine`` on device 0. Both
    engines get half of ``cfg``'s HBM blocks, so that a pool shard's
    quarter of the slots oversubscribes it too and every shard pages
    through the stream kernels; check that each shard did, and that it
    lives on its data rank's chip."""
    cfg = dataclasses.replace(cfg, hbm_blocks=cfg.hbm_blocks // 2)
    devices = jax.devices()
    check(len(devices) >= 4, f"--four-chips needs 4 chips, found "
                             f"{len(devices)}")
    single = ServeEngine(api, params, cfg)
    rids = submit_requests(single, prompts, load)
    want = single.run()
    want_steps = [(single.completed[r].admitted_step,
                   single.completed[r].done_step) for r in rids]
    ref = np.stack([np.asarray(want[r]) for r in rids])

    mesh = make_debug_mesh(1, devices=devices[:4])
    engine = ShardedServeEngine(api, params, cfg, mesh=mesh)
    srids = submit_requests(engine, prompts, load)
    outs = engine.run()
    tied = check_tokens(api, params, cfg, prompts, outs, srids, ref,
                        "four chips")
    got_steps = [(engine.completed[r].admitted_step,
                  engine.completed[r].done_step) for r in srids]
    check(got_steps == want_steps,
          "four chips: admission/completion steps diverge")

    def ids(x):
        return sorted(d.id for d in x.devices())

    for path, leaf in jax.tree_util.tree_leaves_with_path(engine.cache):
        print(f"cache{jax.tree_util.keystr(path)} {tuple(leaf.shape)} "
              f"on devices {ids(leaf)}")
    for s, sh in enumerate(engine.pool.shards):
        want_dev = [mesh.devices[s, 0].id]
        print(f"pool shard {s}: hbm on {ids(sh.hbm)}, host_q on "
              f"{ids(sh.host_q)}, host_scale on {ids(sh.host_scale)}; "
              f"{sh.stats['page_ins']} page-ins, {sh.stats['page_outs']} "
              f"page-outs, {sh.stats['kernel_calls']} kernel calls")
        for name in ("hbm", "host_q", "host_scale"):
            check(ids(getattr(sh, name)) == want_dev,
                  f"pool shard {s}: {name} not on device {want_dev}")
        check_paging(sh.stats, f"four chips, pool shard {s}")
    st = engine.paging_stats()
    return {"requests": len(srids), "steps": engine.step_count,
            "hbm_blocks": cfg.hbm_blocks, "parted_at_tie": tied,
            **{k: st[k] for k in ("page_ins", "page_outs",
                                  "kernel_calls")}}


def timed(fn, *args):
    t0 = time.perf_counter()
    out = fn(*args)
    return out, time.perf_counter() - t0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run only the sharded (4, 1) data-mesh phase on "
                        "four chips")
    args = p.parse_args(argv)

    dev = require_tpu()
    enable_compile_cache()
    api = R.build(ARCH, smoke=False)
    params = api.init(jax.random.PRNGKey(0))
    load = Workload()
    c = api.cfg
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    print(f"model: {c.name} layers={c.num_layers} d_model={c.d_model} "
          f"heads={c.num_heads}/{c.num_kv_heads} d_ff={c.d_ff} "
          f"vocab={c.vocab} (random weights, PRNGKey(0))")
    print(f"engine: {ENGINE}")
    print(f"workload: {load}")
    prompts = prompts_for(api, load)

    if args.four_chips:
        res, secs = timed(four_chip_phase, api, params, ENGINE, load,
                          prompts)
        print(f"four chips: every request equals the single-chip engine "
              f"or parted from it at an exact tie; "
              f"{res}; {secs:.1f}s wall")
    else:
        ref, secs = timed(reference_tokens, api, params, ENGINE, prompts,
                          load.gen)
        print(f"reference_decode: {ref.shape} tokens, {secs:.1f}s wall "
              f"(incl. compile)")
        res = serve_phase(api, params, ENGINE, load, prompts, ref)
        steady = serve_phase(api, params, ENGINE, load, prompts, ref)
        print(f"serve: every request equals reference_decode or parted "
              f"from it at an exact tie; {res}; engine.run {res['run_s']:.1f}"
              f"s wall (incl. compile), steady rerun {steady['run_s']:.1f}s "
              f"wall")
        res, secs = timed(tenant_phase, api, params, ENGINE, load, prompts,
                          ref)
        print(f"tenants: LLM tokens checked against reference_decode as "
              f"above, kv store and vector search checked; {res}; "
              f"{secs:.1f}s wall")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
