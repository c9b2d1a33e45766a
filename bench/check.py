"""The comparison that decides ``correct``.

Once the window has closed, a sample drawn from the seed is compared with
the configuration's plain reference (``bench/references/<name>.py``):

  ``logit_gap``   finished requests, the longest among them, until some
                  hundreds of served tokens: the reference runs once over
                  each prompt with its served tokens, and the number is the
                  widest gap by which a served token's logit lies below the
                  reference's best at that position (greedy decoding).
  ``kv_err``      pool blocks of requests still live at the close, read
                  back from the pool (its HBM tier, or the int8 host tier
                  dequantized): the widest difference from the reference's
                  post-rotary keys and values of those positions, over the
                  largest magnitude of the token's row (the unit the int8
                  host tier quantizes by).

The sample is read from the engine first; the reference runs after the
engine's state is freed. Each number has a limit in the configuration
file (``check``), set from the program's and the control's readings.
"""

from __future__ import annotations

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np

#: served tokens the logit sample aims at, and its most requests.
SAMPLE_TOKENS = 384
SAMPLE_MAX_REQUESTS = 8
#: live requests and pool blocks of each read back for ``kv_err``.
KV_REQUESTS = 2
KV_BLOCKS = 8
#: sequences one reference call takes.
REF_BATCH = 4


@dataclasses.dataclass
class Sample:
    seqs: list            # (prompt, served) int32 pairs, finished requests
    kv: list              # (tokens int32, [(block index, data f32)])


def stored_block(pool, block: int):
    """A pool block's value as float32: its HBM row when resident, else its
    dequantized host copy, else None."""
    slot = int(pool.slot_of[block])
    if slot >= 0:
        return np.asarray(pool.hbm[slot], np.float32)
    hslot = int(pool.host.slot_of[block])
    if hslot < 0 or not pool._has_host[block]:
        return None
    return (np.asarray(pool.host_q[hslot], np.float32)
            * np.asarray(pool.host_scale[hslot], np.float32))


def draw(window, engine, seed: int, block_tokens: int) -> Sample:
    """Read the sample off the engine: served tokens of finished requests,
    and pool blocks of live ones."""
    rng = np.random.default_rng([seed, 1])
    done = [w for w in window.records
            if w.request is not None and w.finished]
    seqs = []
    if done:
        longest = max(done, key=lambda w: w.delivered)
        rest = [done[i] for i in rng.permutation(len(done))
                if done[i] is not longest]
        total = 0
        for w in [longest] + rest:
            if total >= SAMPLE_TOKENS or len(seqs) >= SAMPLE_MAX_REQUESTS:
                break
            r = w.request
            seqs.append((np.asarray(r.prompt, np.int32),
                         np.asarray(r.generated, np.int32)))
            total += len(r.generated)
    live = [w for w in window.records
            if engine.paged and w.request is not None and not w.finished
            and w.request.blocks and not w.request.blocks_freed]
    kv = []
    for i in rng.permutation(len(live))[:KV_REQUESTS]:
        r = live[i].request
        tokens = np.concatenate([np.asarray(r.prompt, np.int32),
                                 np.asarray(r.generated, np.int32)])
        n = len(r.blocks)
        pick = sorted(set(rng.permutation(n)[:KV_BLOCKS - 1].tolist())
                      | {n - 1})
        blocks = []
        for bi in pick:
            data = stored_block(engine.pool, r.blocks[bi])
            blocks.append((bi, data))
        kv.append((tokens[:n * block_tokens], blocks))
    return Sample(seqs=seqs, kv=kv)


def reference_module(config: dict):
    return importlib.import_module(
        f"bench.references.{config['reference']}")


def _pad_len(n: int, step: int = 128) -> int:
    return -(-n // step) * step


def logit_gaps(ref, params, sz, seqs, *, quant=None):
    """Per served token, the reference's best logit minus the logit of the
    token compared: the served token itself, or, with ``quant``, the token
    that the quantized reference puts first at that position. Returns one
    float64 array over all served tokens of ``seqs``."""
    if not seqs:
        return np.zeros((0,))
    S = _pad_len(max(len(p) + len(g) - 1 for p, g in seqs))
    out = []
    for i in range(0, len(seqs), REF_BATCH):
        part = seqs[i:i + REF_BATCH]
        toks = np.zeros((REF_BATCH, S), np.int32)
        tgts = np.zeros((REF_BATCH, S), np.int32)
        for j, (p, g) in enumerate(part):
            seq = np.concatenate([p, g[:-1]])
            toks[j, :len(seq)] = seq
            tgts[j, len(p) - 1:len(p) - 1 + len(g)] = g
        if quant is not None:
            _, top = ref.score(params, jnp.asarray(toks), jnp.asarray(tgts),
                               sz=sz, quant=quant)
            tgts = np.asarray(top)
        gap, _ = ref.score(params, jnp.asarray(toks), jnp.asarray(tgts),
                           sz=sz)
        gap = np.asarray(gap, np.float64)
        for j, (p, g) in enumerate(part):
            out.append(gap[j, len(p) - 1:len(p) - 1 + len(g)])
    return np.concatenate(out)


def kv_errors(ref, params, sz, kv, block_tokens: int, *, quant=None):
    """Per token of each sampled block: the widest difference between the
    block as stored (or, with ``quant``, the quantized reference's keys and
    values) and the reference's, over the row's largest magnitude."""
    out = []
    for tokens, blocks in kv:
        S = _pad_len(len(tokens))
        toks = np.zeros((1, S), np.int32)
        toks[0, :len(tokens)] = tokens
        want = np.asarray(ref.keys_values(params, jnp.asarray(toks), sz=sz),
                          np.float32)[:, 0]                # (L, S, 2, KV, hd)
        want = np.moveaxis(want, 1, 0).reshape(S, -1)      # (S, kv_dims)
        if quant is not None:
            got_all = np.asarray(ref.keys_values(
                params, jnp.asarray(toks), sz=sz, quant=quant),
                np.float32)[:, 0]
            got_all = np.moveaxis(got_all, 1, 0).reshape(S, -1)
        for bi, data in blocks:
            lo = bi * block_tokens
            w = want[lo:lo + block_tokens]
            if quant is not None:
                got = got_all[lo:lo + block_tokens]
            elif data is None:
                out.append(np.full((block_tokens,), np.inf))
                continue
            else:
                got = data
            den = np.maximum(np.abs(w).max(axis=1), 1e-30)
            out.append(np.abs(got - w).max(axis=1) / den)
    return np.concatenate(out) if out else np.zeros((0,))


def readings(config: dict, params, sz, sample: Sample, block_tokens: int,
             *, quant=None) -> dict:
    """The numbers the configuration compares (its ``check.limits``), for
    the program (``quant=None``) or for the control. A missing sample reads
    as infinitely wrong: an answer that never came."""
    ref = reference_module(config)
    names = config["check"]["limits"]
    out = {}
    if "logit_gap" in names:
        gaps = logit_gaps(ref, params, sz, sample.seqs, quant=quant)
        out["logit_gap"] = float(gaps.max()) if gaps.size else float("inf")
        out["served_tokens"] = int(gaps.size)
    if "kv_err" in names:
        errs = kv_errors(ref, params, sz, sample.kv, block_tokens,
                         quant=quant)
        out["kv_err"] = float(errs.max()) if errs.size else float("inf")
        out["kv_rows"] = int(errs.size)
    return out


def verdict(values: dict, limits: dict) -> tuple[bool, dict]:
    """Each number beside its limit, and whether all are within."""
    checks = {name: {"value": values[name], "limit": limits[name]}
              for name in limits}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    # a reading that is not a number (nan) is no pass either
    ok = ok and all(c["value"] == c["value"] for c in checks.values())
    return ok, checks


def free_device_memory() -> None:
    """Drop compiled programs and collect garbage, so that arrays the
    engine held are returned before the reference runs."""
    import gc
    gc.collect()
    jax.clear_caches()
