"""ServeEngine — continuous-batching decode over the duplex-paged KV pool.

The step loop (``ServeEngine.step``):

  1. **admission** — free batch slots are offered to the ``RequestQueue``;
     the queue's ``core.policies`` policy picks which arrived prefills join
     the running batch (the scheduler stack serving real traffic);
  2. **fused micro-steps** — ONE jitted, buffer-donated XLA program runs
     the whole step's token loop on device: up to ``prefill_chunk``
     micro-steps advance every active slot (prompt token while prefilling,
     last sampled token while decoding) inside a ``lax.scan``, with the
     argmax of each micro-step's logits fed straight back into the next
     micro-step on device. The host syncs exactly once per engine step —
     a single packed (B, 4) readback of per-slot (state, consumed, n_gen,
     newest token) — to learn completions and drive paging;
  3. **KV paging** — freshly filled KV blocks are written through to the
     ``PagedKVPool`` and the whole batch's block demand for the step is
     made resident in ONE pool transaction: one ``DuplexOffloadEngine``
     plan, one fused ``duplex_kv_stream`` kernel invocation, regardless of
     how many requests page.

Device-resident slot state: everything the micro-step loop reads lives in
int32 device arrays (``_dev``): per-slot state code (EMPTY/PREFILL/DECODE/
DONE), current feed token, consumed/generated counters, prompt length and
budget, and a fixed-width per-slot prompt buffer. ``Request`` objects are
host *mirrors*, refreshed from the once-per-step packed (B, 4) readback
(``Request.sync_from_device`` — a row emits at most one token per step,
so state | consumed | n_gen | newest-token is the complete delta). Admission writes slot rows
through two fused, donated programs (``_admit_rows`` for the state arrays,
``_reset_rows`` for the pristine cache rows) — no per-leaf dispatches, no
retracing across steps or engines: the compiled step program is cached
per ``(ModelAPI, prefill_chunk)`` and shared by every engine with that
shape, and caches are buffer-donated throughout so HBM holds one copy.

Correctness contract: the dense per-slot cache is the HBM working set the
model attends over, so generation is exact — a request decodes
token-for-token identically whether it ran in a static batch or arrived
mid-stream (tests assert this). The pool mirrors that working set at block
granularity against a *smaller* HBM budget: every filled block's real KV
round-trips the int8 host tier as the LRU streams it in and out, which is
the paper's capacity-tier traffic, measured on the actual request stream
(functional execution real, link timing modelled — channel-model doctrine).

Frozen-slot micro-steps: ``decode_step`` always advances the cache of
every batch row, so non-advancing slots see a dummy token. Dummy logits
are discarded. For the pure token-indexed transformer ring cache that is
already safe: the dummy K/V lands at the frozen row's *next* write
position and is overwritten by that row's next real token before any real
query attends it. Recurrent families (RWKV wkv/shift state, hybrid Mamba
state) are different — their state is irreversibly advanced by any token
they see — so for non-ring caches each micro-step keeps every non-mover
row's leaves from the pre-micro-step cache (a per-row masked select fused
*inside* the jitted step; no whole-cache copies, no host sync). A
prefill-only micro-step with no movers at all skips the model entirely
via ``lax.cond``. Either way frozen rows never contaminate generation.

Megasteps: ``cfg.megastep = K`` runs up to K consecutive engine steps as
ONE jitted, buffer-donated program — an outer ``lax.scan`` over the fused
step, per-slot device state threaded through the carry — with ONE packed
``(B, 3+K)`` readback per megastep instead of one per step, so the host
round-trip (and the dispatch tax it serializes) is paid once per K
tokens. The key enabler is that everything about an engine step *except
the token values* is deterministic host arithmetic: per-slot state
transitions, consumed/generated counters, block-fill schedules and
completion steps all follow from (prompt_len, max_new, prefill_chunk),
so the host pre-plans all K steps' KV paging without waiting for the
device (``_simulate_row``), and the readback is needed only to append
the sampled tokens to the host mirrors (cross-checked against the
prediction). Paging overlaps compute: the megastep program stages each
inner step's freshly filled blocks as a scan output (cursor arithmetic
is fixed-width, so extraction happens on device right after the step
that filled them), and the per-step gather/stream-kernel/commit
transactions are dispatched against those staging slabs while later
inner steps' compute is still in flight — no host sync anywhere between
two megastep boundaries. Admission, retirement, and policy
``schedule``/``update`` move to megastep boundaries; the K steps'
policy ``Feedback`` is folded in one scanned update
(``core.policies.fold_feedback``). ``megastep=1`` is bit-identical to
the classic per-step loop (``step()`` *is* ``megastep(1)``), and
``run()`` picks the megastep width adaptively so admission still
happens at exactly the steps the per-step loop would have used.

Pipelined boundaries: ``cfg.pipeline_depth = 2`` splits each megastep
into plan / dispatch / reconcile and keeps one dispatched megastep's
packed readback *deferred* while the next boundary is planned and
dispatched, so the device never drains between megasteps — XLA async
dispatch chains megastep t+1's donated programs behind t's while the
host does t+1's planning work. The enabler is the same determinism that
makes megasteps possible: token *values* never steer control (greedy
decode; completion counts from ``max_new_tokens``), so admission,
paging, tier migrations and the policy fold for t+1 are all computable
before t's readback lands. Planning reads the requests' *speculative*
mirrors (``Request.plan_*`` — advanced at dispatch time from the
trajectory; the real mirrors stay one boundary behind until the
deferred ``sync_megastep``), every speculative pool alloc/free is
journaled per in-flight megastep, and a readback that contradicts its
trajectory rolls the journal back (no leaked or double-freed blocks)
before raising. The sync budget is unchanged — still exactly one
packed readback per megastep, just consumed one boundary late — and
depth 2 is bit-exact with depth 1: same tokens, same admission steps,
same paging transactions. Depth > 2 buys nothing here: there is a
single donation chain (one cache, one slot-state tree), so a third
in-flight megastep would just queue behind the second in XLA's stream —
the host only ever needs one boundary of lookahead to stay off the
critical path. ``stats()['host_blocked']`` counts the boundaries where
the host consumed a readback with nothing dispatched ahead of it (the
pipeline-bubble count: == megasteps at depth 1; 1 per run — the final
drain — at depth 2).
"""

from __future__ import annotations

import dataclasses
import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import policies as policies_lib
from repro.core.faults import fresh_fault_stats
from repro.core.hints import HintTree, default_serving_hints
from repro.core.metrics import MetricsRegistry
from repro.core.telemetry import CaxRegistry
from repro.models.registry import ModelAPI
from repro.serve.kv_pool import PagedKVPool
from repro.serve.queue import (DECODE, DONE, FAILED, PREFILL,
                               STATE_OF_CODE, Request, RequestQueue,
                               S_DECODE, S_DONE, S_EMPTY, S_PREFILL)
from repro.serve.snapshot import SnapshotManager, fresh_snapshot_stats
from repro.serve.trace import Tracer, phase


class EngineStallError(RuntimeError):
    """``run()`` made no progress for ``cfg.stall_boundaries``
    consecutive megastep boundaries: nothing live, nothing admitted,
    nothing completing, no tenant work running — yet requests are still
    pending (e.g. a queued request whose tenant budget can never open).
    ``rids`` names the stuck requests."""

    def __init__(self, message: str, rids):
        super().__init__(message)
        self.rids = list(rids)


@dataclasses.dataclass(frozen=True)
class _RowStep:
    """One live row's predicted post-state for one inner step of a
    megastep (host-deterministic; see ``ServeEngine._simulate_row``)."""
    state: int          # S_* code after the step
    consumed: int       # prompt tokens consumed after the step
    n_gen: int          # tokens generated after the step
    written: int        # tokens resident in the dense cache after it
    emitted: bool       # did this step emit a sample?
    transition: bool    # was it the PREFILL->DECODE transition step?


@dataclasses.dataclass
class _InFlight:
    """One dispatched-but-unreconciled megastep — the pipeline's unit of
    speculation. ``_plan`` fills the deterministic fields, ``_dispatch``
    attaches the in-flight packed readback plus a journal of the
    speculative pool mutations (replayed backwards if the readback later
    contradicts the trajectory), ``_reconcile`` consumes it."""
    now: int            # first engine step covered by the megastep
    k: int              # inner steps fused into the dispatch
    admitted: int       # requests admitted at the boundary
    live: list          # LLM rows live at dispatch time
    traj: dict          # rid -> k predicted _RowSteps
    packed: object = None               # (B, 3+K) device readback future
    report: dict = dataclasses.field(default_factory=dict)
    journal: list = dataclasses.field(default_factory=list)
                        # ("alloc" | "free", request, [block ids])


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    max_batch: int = 4          # running decode slots
    cache_len: int = 128        # dense cache depth per slot
    block_tokens: int = 16      # KV page granularity (tokens)
    hbm_blocks: int = 8         # pool HBM slots, shared by the whole batch
    pool_blocks: int = 0        # logical pool capacity (0 = auto)
    prefill_chunk: int = 4      # prompt tokens consumed per engine step
    max_queue: int = 32
    policy: str = "hinted"      # admission policy (core.policies registry)
    paging: bool = True         # False: pure continuous batching, no pool
    megastep: int = 1           # engine steps fused per host dispatch (K);
                                # run() adapts K <= megastep between
                                # admission events. 1 = classic step loop.
    tiers: str | tuple | None = None
                                # host-memory channel set for the pool
                                # ("ddr5:2,cxl:2"); None = flat pool
    tier_migrate: bool = True   # rebalance host placement at megastep
                                # boundaries (tiered pools only)
    pipeline_depth: int = 1     # megastep boundaries in flight: 1 = plan,
                                # dispatch, block on the readback (classic
                                # loop); 2 = double-buffered — plan and
                                # dispatch t+1 before reconciling t's
                                # deferred readback. Bit-exact either way.
    faults: object = None       # core.faults.FaultInjector (or None) —
                                # deterministic fault plan serviced by
                                # the pool's transactions; requires
                                # paging. None = zero-cost, no fault
                                # machinery anywhere on the hot path.
    stall_boundaries: int = 64  # run(): consecutive zero-progress
                                # boundaries before EngineStallError
    snapshot_every: int = 0     # crash-consistent cut cadence in megastep
                                # boundaries (serve.snapshot); 0 = disabled,
                                # zero hooks anywhere on the hot path
    snapshot_dir: str | None = None
                                # snapshot + write-ahead-journal directory;
                                # required when snapshot_every > 0
    trace: object = None        # observability plane (serve.trace): a
                                # Tracer, True (in-memory), or a path str
                                # for Perfetto export. None = disabled,
                                # zero hooks anywhere on the hot path and
                                # bit-exact with an untraced engine.

    def resolved_pool_blocks(self) -> int:
        if self.pool_blocks:
            return self.pool_blocks
        per_seq = math.ceil(self.cache_len / self.block_tokens)
        return max(2 * self.hbm_blocks, per_seq * self.max_batch)


def _kv_cache_leaves(cache):
    """The transformer-family scanned cache dict, or None if the arch's
    cache has no token-indexed K/V (e.g. RWKV state) — paging is gated off
    for those."""
    if (isinstance(cache, dict) and {"k", "v", "pos"} <= set(cache)
            and cache["k"].ndim == 5):
        return cache
    return None


# ---------------------------------------------------------------------------
# jitted engine programs (module-level: engines sharing a (ModelAPI, config)
# cell share one compiled program; buffers are donated where the caller
# rebinds them, so HBM holds one cache, not two)
# ---------------------------------------------------------------------------

def _row_mask(mask, leaf):
    """Broadcast a (B,) slot mask over a (L, B, ...) cache leaf."""
    return mask.reshape((1, -1) + (1,) * (leaf.ndim - 2))


@functools.partial(jax.jit, donate_argnums=(0,))
def _reset_rows(cache, cache0, mask):
    """Restore pristine init rows for slots in ``mask`` — every cache
    family (attention K/V/pos rings, RWKV/Mamba recurrent state) stacks
    layers first, batch second. One fused program for the whole tree, not
    one dispatch per cache leaf; the old cache buffer is donated."""
    return jax.tree.map(
        lambda leaf, leaf0: jnp.where(_row_mask(mask, leaf), leaf0, leaf),
        cache, cache0)


@functools.partial(jax.jit, donate_argnums=(0,))
def _admit_rows(dev, mask, prompts, prompt_len, max_new):
    """Install admitted requests into their slots' device-resident state
    rows (fixed-width: ``mask``/``prompts`` always span the full batch, so
    admission never retraces on how many requests arrived)."""
    zero = jnp.int32(0)

    def sc(cur, new):
        return jnp.where(mask, new, cur)

    return {
        "state": sc(dev["state"], jnp.int32(S_PREFILL)),
        "tok": sc(dev["tok"], prompts[:, 0]),
        "consumed": sc(dev["consumed"], zero),
        "n_gen": sc(dev["n_gen"], zero),
        "prompt_len": sc(dev["prompt_len"], prompt_len),
        "max_new": sc(dev["max_new"], max_new),
        "prompt": jnp.where(mask[:, None], prompts, dev["prompt"]),
    }


def _extract_blocks_math(k, v, slot_idx, t0, *, block_tokens: int):
    """Gather KV blocks from the dense cache, batched over (slot, t0).

    k/v: (L, B, W, KV, hd). slot_idx/t0: (n,) int32 — callers always pass
    a fixed-width vector padded with dummy entries, so write-through
    never retraces on the number of freshly filled blocks. Returns
    (n, block_tokens, kv_dims) bf16 slabs with kv_dims = L * 2 * KV * hd
    — the block-table-indexed read the pool pages. Plain traceable math:
    the megastep program inlines it inside its scan (staging the filled
    blocks right after the inner step that filled them); the jitted
    ``_extract_blocks_impl`` wraps it for stand-alone use.

    Token j of row i is ring position ``(t0[i] + j) % W``, read as
    contiguous slices of the cache, so the rows lower to one slice gather
    per tensor and never to a gather of single elements. When W is a
    multiple of ``block_tokens``, a block-aligned ``t0`` (as every caller
    passes) never straddles the ring's end: each row is one slice of
    ``block_tokens`` tokens. Otherwise each token is a slice of its own."""
    L, _, W, KV, hd = k.shape
    piece = block_tokens if W % block_tokens == 0 else 1
    offs = jnp.arange(0, block_tokens, piece, dtype=jnp.int32)

    def row(arr, s, t):                                     # (L, bt, KV, hd)
        parts = jax.vmap(lambda p: lax.dynamic_slice(
            arr, (0, s, p, 0, 0), (L, 1, piece, KV, hd)))((t + offs) % W)
        return jnp.moveaxis(parts, 0, 1).reshape(L, block_tokens, KV, hd)

    def take(arr):                                          # (n, L, bt, KV, hd)
        return jax.vmap(row, in_axes=(None, 0, 0))(arr, slot_idx, t0)

    kv = jnp.stack([take(k), take(v)], axis=2)
    kv = jnp.moveaxis(kv, 3, 1)                             # (n, bt, L, 2, KV, hd)
    n = kv.shape[0]
    return kv.reshape(n, block_tokens, -1).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("block_tokens",))
def _extract_blocks_impl(k, v, slot_idx, t0, *, block_tokens: int):
    return _extract_blocks_math(k, v, slot_idx, t0,
                                block_tokens=block_tokens)


def _extract_blocks(cache, slot_idx, t0, block_tokens: int) -> jnp.ndarray:
    """Compat wrapper over ``_extract_blocks_impl`` accepting the cache
    dict and python index lists (tests use it; the engine inlines the
    math in its megastep). Each ``t0`` must be block-aligned."""
    return _extract_blocks_impl(
        cache["k"], cache["v"],
        jnp.asarray(np.asarray(slot_idx, np.int32)),
        jnp.asarray(np.asarray(t0, np.int32)),
        block_tokens=block_tokens)


def _written_of(dev):
    """Tokens whose KV is in the dense cache, per slot — the device twin
    of ``ServeEngine._written`` (all consumed prompt tokens, plus every
    generated token that has been fed back)."""
    return jnp.where(dev["state"] == S_PREFILL, dev["consumed"],
                     jnp.maximum(dev["consumed"] + dev["n_gen"] - 1, 0))


@functools.lru_cache(maxsize=64)
def _megastep_math(api: ModelAPI, n_micro: int, n_steps: int,
                   block_tokens: int | None):
    """The megastep's pure math: ``n_steps`` consecutive engine steps
    as one traceable function ``mega(params, cache, dev)`` — an outer
    ``lax.scan`` over the fused engine step (itself a ``lax.scan`` of up
    to ``n_micro`` micro-steps with on-device argmax feedback), per-slot
    device state threaded through the carry. Un-jitted so callers choose
    the staging: ``_fused_megastep_program`` jits it directly (the
    single-device engine), ``serve.shard`` wraps it in ``shard_map``
    over a data×model mesh first — every row's arithmetic is per-slot
    independent, so the same math is bit-exact under batch sharding.

    Returns ``fn(params, cache, dev) -> (cache, dev, packed[, staged])``
    where ``packed`` is the (B, 3+K) int32 completion readback
    (state | consumed | n_gen | tok_0 .. tok_{K-1}) — the megastep's
    single device->host sync reads exactly this one small array. A row
    emits at most one token per engine step (decode rows move only at
    micro-step 0; a prefill row emits once, on its transition), and
    after an emitting micro-step the feed token *is* the emitted sample,
    so the K per-step feed tokens plus the final counters are the
    complete host-mirror delta (the host knows *which* steps emitted
    deterministically).

    With ``block_tokens`` set (a paged engine), the program also stages
    KV write-through on device: right after inner step t it extracts the
    blocks that step filled — fixed-width cursor arithmetic over the
    pre-step write positions, ``max_fills`` candidate blocks per slot —
    and stacks them into ``staged`` (K, B*max_fills, block_tokens,
    kv_dims) bf16, the double-buffered staging stack the pool's
    per-inner-step paging transactions consume while later inner steps'
    compute is still in flight (padding rows are dropped by the pool's
    sentinel-id scatter).
    """
    ring = api.cache_kind == "ring"
    n_micro = max(1, n_micro)
    extract = block_tokens is not None
    if extract:
        max_fills = -(-n_micro // block_tokens)

    def engine_step(params, cache, dev):
        B = dev["state"].shape[0]
        P = dev["prompt"].shape[1]
        brange = jnp.arange(B)

        def micro(carry, m):
            cache, dev = carry
            prefilling = dev["state"] == S_PREFILL
            decoding = dev["state"] == S_DECODE
            # micro-step 0 advances every live row; later micro-steps only
            # the still-prefilling rows (chunked prefill without stalling
            # running decodes).
            movers = prefilling | (decoding & (m == 0))
            written = jnp.where(
                prefilling, dev["consumed"],
                jnp.maximum(dev["consumed"] + dev["n_gen"] - 1, 0))
            toks = jnp.where(movers, dev["tok"], 0)

            def advance(c):
                logits, new_cache = api.decode_step(params, c, toks,
                                                    written)
                if not ring:
                    # Recurrent state (RWKV wkv/shifts, Mamba) is
                    # irreversibly advanced by any token it sees: keep
                    # every non-mover row's pre-step leaves. Ring caches
                    # skip this — the dummy entry is overwritten before
                    # it is ever attended.
                    with jax.named_scope("megastep/row_keep"):
                        new_cache = jax.tree.map(
                            lambda new, old: jnp.where(
                                _row_mask(movers, new), new, old),
                            new_cache, c)
                picked = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return new_cache, picked

            # a micro-step with no movers (every live row already decoded
            # this step) skips the model entirely.
            with jax.named_scope("megastep/model"):
                cache, picked = lax.cond(
                    movers.any(), advance,
                    lambda c: (c, jnp.zeros((B,), jnp.int32)), cache)

            pref_mover = movers & prefilling
            consumed = dev["consumed"] + pref_mover.astype(jnp.int32)
            fin_pref = pref_mover & (consumed == dev["prompt_len"])
            emit = (movers & decoding) | fin_pref
            n_gen = dev["n_gen"] + emit.astype(jnp.int32)
            state = jnp.where(fin_pref, S_DECODE, dev["state"])
            state = jnp.where(emit & (n_gen >= dev["max_new"]),
                              S_DONE, state)
            nxt = dev["prompt"][brange, jnp.minimum(consumed, P - 1)]
            tok = jnp.where(
                movers, jnp.where(state == S_PREFILL, nxt, picked),
                dev["tok"])
            dev = dict(dev, state=state, tok=tok, consumed=consumed,
                       n_gen=n_gen)
            return (cache, dev), None

        (cache, dev), _ = lax.scan(micro, (cache, dev),
                                   jnp.arange(n_micro))
        return cache, dev

    def mega(params, cache, dev):
        def inner(carry, _):
            cache, dev = carry
            fill_base = _written_of(dev) // (block_tokens or 1)
            cache, dev = engine_step(params, cache, dev)
            # after an emitting micro-step, ``tok`` is exactly the
            # emitted sample (decode feedback), so it doubles as the
            # newest token for this inner step.
            if not extract:
                return (cache, dev), dev["tok"]
            B = dev["state"].shape[0]
            slot_idx = jnp.repeat(jnp.arange(B, dtype=jnp.int32),
                                  max_fills)
            t0 = (jnp.repeat(fill_base, max_fills)
                  + jnp.tile(jnp.arange(max_fills, dtype=jnp.int32),
                             B)) * block_tokens
            with jax.named_scope("megastep/writethrough"):
                staged = _extract_blocks_math(cache["k"], cache["v"],
                                              slot_idx, t0,
                                              block_tokens=block_tokens)
            return (cache, dev), (dev["tok"], staged)

        (cache, dev), ys = lax.scan(inner, (cache, dev), None,
                                    length=n_steps)
        toks = ys[0] if extract else ys          # (K, B)
        with jax.named_scope("megastep/pack"):
            packed = jnp.concatenate(
                [dev["state"][:, None], dev["consumed"][:, None],
                 dev["n_gen"][:, None], jnp.swapaxes(toks, 0, 1)], axis=1)
        if extract:
            return cache, dev, packed, ys[1]
        return cache, dev, packed

    return mega


@functools.lru_cache(maxsize=64)
def _fused_megastep_program(api: ModelAPI, n_micro: int, n_steps: int,
                            block_tokens: int | None):
    """The single-device megastep program: ``_megastep_math`` compiled as
    ONE jitted, buffer-donated XLA program. Cached per (ModelAPI,
    prefill_chunk, K, block_tokens): every engine sharing that cell
    reuses the compiled program (warm restarts, A/B engines, the
    benchmark's warmup engine); ``run()`` quantizes its adaptive K to
    powers of two so a serving run populates a handful of cells, not one
    per gap length. Donating ``cache`` and the slot-state arrays means
    the megastep updates in place — HBM holds one cache.
    """
    return jax.jit(_megastep_math(api, n_micro, n_steps, block_tokens),
                   donate_argnums=(1, 2))


class ServeEngine:
    """Continuous-batching serving engine for one ``ModelAPI``."""

    def __init__(self, api: ModelAPI, params, cfg: EngineConfig,
                 hints: HintTree | None = None):
        if not getattr(api, "fused_decode", True):
            raise ValueError(
                f"{api.arch_id}: ModelAPI.fused_decode is False — its "
                "decode_step does not satisfy the fused step-loop "
                "contract (pure, scan-safe, cache-donatable); the engine "
                "cannot serve it")
        if cfg.megastep < 1:
            raise ValueError("megastep must be >= 1")
        if cfg.pipeline_depth < 1:
            raise ValueError("pipeline_depth must be >= 1")
        self.api = api
        self.params = params
        self.cfg = cfg
        self.hints = hints or default_serving_hints()
        self.cache = api.init_cache(cfg.max_batch, cfg.cache_len)
        # pristine rows for slot recycling — a *separate* allocation: the
        # live cache's buffers are donated every step.
        self._cache0 = api.init_cache(cfg.max_batch, cfg.cache_len)
        self.slots: list[Request | None] = [None] * cfg.max_batch
        B = cfg.max_batch
        self._dev = {
            "state": jnp.full((B,), S_EMPTY, jnp.int32),
            "tok": jnp.zeros((B,), jnp.int32),
            "consumed": jnp.zeros((B,), jnp.int32),
            "n_gen": jnp.zeros((B,), jnp.int32),
            "prompt_len": jnp.zeros((B,), jnp.int32),
            "max_new": jnp.zeros((B,), jnp.int32),
            "prompt": jnp.zeros((B, cfg.cache_len), jnp.int32),
        }

        kv = _kv_cache_leaves(self.cache)
        # Token-indexed ring caches (declared per-arch on ModelAPI)
        # overwrite a frozen row's dummy K/V before it is ever attended;
        # recurrent families get the in-program frozen-row keep (see
        # module docstring). Paging additionally needs the extractable
        # top-level transformer K/V layout.
        self._ring_cache = api.cache_kind == "ring"
        self.paged = cfg.paging and kv is not None
        if cfg.faults is not None and not self.paged:
            raise ValueError(
                "fault injection targets the paged memory hierarchy; "
                "this engine has paging disabled (or a non-pageable "
                "cache family)")
        self._fx = cfg.faults if self.paged else None
        if self.paged:
            L, _, _, KV, hd = kv["k"].shape
            kv_dims = L * 2 * KV * hd
            self.pool = self._make_pool((cfg.block_tokens, kv_dims))
            kv_bytes = float(kv_dims * 2)
        else:
            self.pool = None
            kv_bytes = 4096.0
        self.queue = RequestQueue(cfg.max_queue, policy=cfg.policy,
                                  hints=self.hints,
                                  kv_bytes_per_token=kv_bytes)
        # the classic per-step program is the K=1 megastep cell; kept as
        # ``_step_fn`` so the perf-contract (one compile per cell,
        # engines share programs) is inspectable.
        self._step_fn = self._mega_fn(1)
        self.step_count = 0
        self.host_dispatches = 0   # fused step-program dispatches (the
                                   # per-token host round-trip tax)
        self.megasteps = 0         # megastep() invocations
        self.host_blocked = 0      # boundaries whose readback the host
                                   # consumed with nothing dispatched
                                   # ahead of it — the pipeline-bubble
                                   # count (== megasteps at depth 1; the
                                   # single final drain at depth 2)
        self._inflight: list[_InFlight] = []   # dispatched, unreconciled
        # one reusable zero vector for the megastep Feedback rows — the
        # boundary fold stacks (copies) its host leaves, so every zero
        # row of every boundary can share this one buffer.
        self._fb_zero = np.zeros((self.queue.capacity,), np.float32)
        self.completed: dict[int, Request] = {}
        self.failed: dict[int, Request] = {}     # FAILED terminal records
        self._scan_cursor: dict[int, int] = {}   # rid -> cold-block cursor
        # non-LLM tenants (WorkloadAPI) sharing the pool, the paging
        # transaction, and the admission queue with LLM decode.
        self.tenants: dict[str, "object"] = {}
        self._reserved_blocks = 0   # HBM headroom promised to tenants
        # crash consistency (serve.snapshot): None when disabled — every
        # hot-path hook is behind an ``is not None`` check, so a disabled
        # engine runs bit-identically to one built before this layer.
        self._snap = None
        if cfg.snapshot_every > 0:
            if cfg.snapshot_dir is None:
                raise ValueError("snapshot_every > 0 needs snapshot_dir")
            if not self.paged:
                raise ValueError(
                    "snapshot/restore covers the paged memory hierarchy; "
                    "this engine has paging disabled (or a non-pageable "
                    "cache family)")
            self._snap = SnapshotManager(cfg.snapshot_dir,
                                         cfg.snapshot_every)
        # observability (serve.trace / core.telemetry): the tracer is
        # None when disabled — same zero-cost contract as faults and
        # snapshots above. The CAX scope registry is per-engine and
        # always wired (host-side dict arithmetic off billing the pool
        # already does; it never touches tokens, timing, or a device
        # array) so ``--telemetry`` needs no mode flag.
        self.telemetry = CaxRegistry()
        if cfg.trace is None:
            self._tracer = None
        elif isinstance(cfg.trace, Tracer):
            self._tracer = cfg.trace
        elif cfg.trace is True:
            self._tracer = Tracer()
        else:
            self._tracer = Tracer(path=str(cfg.trace))
        if self.paged:
            self.pool.attach_telemetry(self.telemetry)
            if self._tracer is not None:
                self.pool.attach_trace(self._tracer)
        if self._fx is not None:
            self._fx.trace = self._tracer

    # -- sharding hooks (overridden by serve.shard.ShardedServeEngine) ------
    def _make_pool(self, block_shape) -> PagedKVPool:
        """Build the engine's KV pool; the sharded engine returns a
        per-device-pool facade with the same interface instead."""
        return PagedKVPool(
            self.cfg.resolved_pool_blocks(), self.cfg.hbm_blocks,
            block_shape, hints=self.hints, tiers=self.cfg.tiers,
            faults=self.cfg.faults)

    def _alloc_block(self, r: Request) -> list[int]:
        """Allocate the next KV block for one request's fill. The sharded
        engine routes this to the pool shard owning ``r.slot`` so slot
        ownership (and later migration/evacuation) stays shard-local."""
        return self.pool.alloc(1)

    def _stage_view(self, staged):
        """Adapt the megastep's staged write-through slab for the pool's
        consumption (identity here; the sharded engine lands the
        mesh-sharded slab on the pool device — a device-to-device copy,
        never a host sync)."""
        return staged

    def _place_device_state(self) -> None:
        """Re-establish device placement of params/cache/_dev after a
        snapshot restore rewrote them as host arrays. The flat engine
        needs nothing — ``jnp.asarray`` already landed them on the
        default device; the sharded engine re-runs its mesh placement."""

    def _snapshot_extra_state(self) -> dict:
        """Engine-subclass state for the snapshot tree (sharded engine:
        ICI meter totals). Must be JSON-serializable."""
        return {}

    def _load_extra_state(self, extra: dict) -> None:
        """Inverse of ``_snapshot_extra_state``."""

    # -- tenants -----------------------------------------------------------
    def add_tenant(self, workload):
        """Attach a ``WorkloadAPI`` tenant (KV store, vector search, ...).

        The tenant's requests go through the shared ``RequestQueue`` (one
        admission policy across every workload, per-request hint scopes)
        and its per-step block demand joins LLM KV paging in the same
        ``PagedKVPool.step_multi`` transaction. ``blocks_per_step`` HBM
        blocks are reserved so joint demand can never overflow the pool.
        """
        if not self.paged:
            raise ValueError(
                "tenants serve from the paged KV pool; this engine has "
                "paging disabled (or a non-pageable cache family)")
        if workload.name in self.tenants or workload.name == "llm":
            raise ValueError(f"tenant name {workload.name!r} already taken")
        reserved = self._reserved_blocks + workload.blocks_per_step
        if reserved >= self.pool.hbm_capacity:
            raise ValueError(
                f"tenants would reserve {reserved} of "
                f"{self.pool.hbm_capacity} HBM blocks; grow hbm_blocks or "
                f"shrink the tenant's per-step footprint")
        workload.bind(self)
        self.tenants[workload.name] = workload
        self._reserved_blocks = reserved
        return workload

    # -- intake ------------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int, arrival_step: int = 0,
               hint_path: str = "/serve/llm/prefill") -> Request:
        req = Request(prompt=np.asarray(prompt, np.int32),
                      max_new_tokens=max_new_tokens,
                      arrival_step=arrival_step, hint_path=hint_path)
        if req.prompt_len < 1:
            raise ValueError("prompt must contain at least one token")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        total = req.prompt_len + max_new_tokens
        if total > self.cfg.cache_len:
            raise ValueError(
                f"request needs {total} cache positions but cache_len is "
                f"{self.cfg.cache_len}")
        if self.paged:
            # write-through capacity check at submit time, not mid-step:
            # one engine step prefills up to prefill_chunk tokens, so a
            # single request can newly fill at most ceil(chunk/bt) blocks
            # per step — all of which must fit the pool's HBM for the
            # write-through.
            bt = self.cfg.block_tokens
            chunk = max(1, self.cfg.prefill_chunk)
            worst = min(math.ceil(total / bt), math.ceil(chunk / bt))
            if worst > self.cfg.hbm_blocks:
                raise ValueError(
                    f"request can fill {worst} KV blocks in one engine "
                    f"step but the pool holds {self.cfg.hbm_blocks} HBM "
                    f"blocks; grow hbm_blocks or shrink prefill_chunk/"
                    f"block_tokens")
        req.t_submit = time.perf_counter()
        self.queue.submit(req)
        if self._snap is not None:
            self._snap.note_submit(self, req)
        return req

    def active(self) -> list[Request]:
        return [r for r in self.slots if r is not None]

    def pending(self) -> int:
        return (len(self.queue) + len(self.active())
                + sum(t.pending() for t in self.tenants.values()))

    # -- the step loop -----------------------------------------------------
    def _mega_fn(self, n_steps: int):
        """The (ModelAPI, prefill_chunk, K, block_tokens) megastep cell
        this engine uses for a K-step dispatch."""
        bt = self.cfg.block_tokens if self.paged else None
        return _fused_megastep_program(self.api, self.cfg.prefill_chunk,
                                       n_steps, bt)

    def step(self) -> dict:
        """One engine step — the K=1 megastep (bit-identical to the
        classic admit / fused micro-steps / page / retire loop)."""
        return self.megastep(1)

    def megastep(self, n_steps: int | None = None) -> dict:
        """Run up to K consecutive engine steps as one host dispatch.

        One fused, donated program advances every slot K steps; the K
        per-step paging transactions are planned from the host's
        deterministic per-slot trajectories (``_simulate_row``) and
        dispatched against the program's staged write-through slabs, so
        nothing between two megastep boundaries blocks on the device.
        The single device->host sync is the packed (B, 3+K) completion
        readback at the end. Admission, LLM retirement, and the policy
        fold all happen at the boundary.

        This is the depth-1 composition of the pipelined dispatcher —
        plan, dispatch, reconcile, in that order, blocking on this
        boundary's readback before returning. ``run()`` at
        ``pipeline_depth > 1`` interleaves the same three phases across
        boundaries instead. Older in-flight megasteps (if any) are
        reconciled first, in dispatch order.
        """
        rec = self._dispatch(self._plan(n_steps))
        while self._inflight[0] is not rec:
            self._reconcile(self._inflight[0])
        return self._reconcile(rec)

    def _plan(self, n_steps: int | None = None) -> _InFlight:
        """Boundary planning: admission plus every live row's K-step
        trajectory — host-deterministic arithmetic over the *planning*
        view of the request mirrors (``Request.plan_*``: identical to
        the real mirrors at depth 1, one dispatched-but-unreconciled
        boundary ahead of them at depth 2). No device sync."""
        k = int(n_steps) if n_steps else max(1, self.cfg.megastep)
        now = self.step_count
        with phase(self._tracer, "plan", step=now, k=k) as span:
            with phase(self._tracer, "admit", step=now):
                admitted = self._admit(now)
            live = self.active()
            traj = {r.rid: self._simulate_row(r, k) for r in live}
            span.update(admitted=admitted, live=len(live))
        return _InFlight(now=now, k=k, admitted=admitted, live=live,
                         traj=traj)

    def _dispatch(self, rec: _InFlight) -> _InFlight:
        """Enqueue one planned megastep without consuming its readback:
        the fused K-step program, the per-inner-step paging transactions
        against its staged slabs, mid-megastep block frees, tenant
        compute/retirement, boundary tier migrations, and the policy
        fold. Dispatch-only — device work chains on donated buffers,
        host state advances along the deterministic trajectory
        (speculative mirrors, trajectory-driven retirement, step
        counters), and every pool alloc/free is journaled on ``rec`` so
        a later divergence can roll it back."""
        now, k, live, traj = rec.now, rec.k, rec.live, rec.traj
        with phase(self._tracer, "dispatch", step=now, k=k,
                   live=len(live)) as span:
            staged = None
            if live:
                out = self._mega_fn(k)(self.params, self.cache, self._dev)
                if self.paged:
                    self.cache, self._dev, rec.packed, staged = out
                    staged = self._stage_view(staged)
                else:
                    self.cache, self._dev, rec.packed = out
                self.host_dispatches += 1

            report = {"page_ins": 0, "page_outs": 0, "migrations": 0}
            feedbacks = []
            tenant_done = 0
            for t in range(k):
                rows = []
                for r in live:
                    if r.state == FAILED:
                        continue
                    st = traj[r.rid][t]
                    if st.state != S_DONE:
                        rows.append((r, st))
                if self.paged:
                    rep = self._page_kv_at(now + t, rows, staged, t,
                                           rec.journal)
                    report["page_ins"] += rep["page_ins"]
                    report["page_outs"] += rep["page_outs"]
                    if self._fx is not None:
                        self._service_fault_report(rep, now + t, rec)
                    # rows completing at this inner step release their pool
                    # blocks NOW (deterministic), exactly when the per-step
                    # loop would have — holding them to the boundary would
                    # force spurious evictions on later inner steps.
                    for r in live:
                        st = traj[r.rid][t]
                        if (st.state == S_DONE and r.blocks
                                and not r.blocks_freed
                                and (t == 0
                                     or traj[r.rid][t - 1].state != S_DONE)):
                            self.pool.free(r.blocks)
                            r.blocks_freed = True
                            rec.journal.append(("free", r, list(r.blocks)))
                for tn in self.tenants.values():
                    for r in tn.retire(now + t):
                        self.completed[r.rid] = r
                        tenant_done += 1
                if k > 1:
                    feedbacks.append(policies_lib.Feedback(
                        moved_read=self._fb_zero,
                        moved_write=self._fb_zero,
                        utilization=np.float32(
                            len(rows) / max(1, self.cfg.max_batch))))

            if self.paged and self.pool.tiered and self.cfg.tier_migrate:
                # boundary tier rebalance: planned from this megastep's
                # per-channel traffic window (host metadata only), executed
                # as one dispatched row copy riding the CXL links' idle
                # minor direction — before the readback is ever consumed, so
                # the move overlaps the still-in-flight compute. Plans may
                # cover planned-not-yet-reconciled residency; that is safe —
                # moves relocate verbatim host bytes and a divergence
                # rollback only needs ownership consistency, not placement
                # restoration.
                report["migrations"] = self.pool.migrate_tiers()["migrations"]

            if self._fx is not None and self.paged \
                    and self.pool.host.capacity_degraded:
                self._shed_over_capacity(rec)

            # the megastep's outcome — bar token values — is already decided,
            # so the planning view advances NOW: speculative mirrors jump to
            # the trajectory's final step and predicted-DONE rows leave their
            # slots (trajectory-driven retirement), letting the next _plan()
            # admit into the post-megastep batch before this readback lands.
            for r in live:
                if r.state == FAILED:
                    continue
                last = traj[r.rid][-1]
                r.speculate(STATE_OF_CODE[last.state], last.consumed,
                            last.n_gen)
            report["completed"] = tenant_done + self._retire_planned(rec)
            rec.report = report

            if feedbacks and len(self.queue):
                # megastep-boundary policy feedback: K per-step Feedbacks
                # folded through Policy.update as one scanned program, and
                # the megastep's mean slot utilization surfaced to the next
                # schedule() as Obs.prev_util (host float — no device sync;
                # this is what the oversubscription detector reads). The
                # engine has no per-waiting-slot service to report, so for
                # the registered policies the fold itself is state-invariant
                # (zero moved bytes) — it is the boundary *contract*: a
                # policy whose update reads utilization or cross-step
                # structure gets the full per-step sequence, not a lossy
                # sum. One small dispatch per boundary buys that. Only
                # worth dispatching while requests wait — with an empty
                # waiting room there is no admission ranking to influence.
                # Padded up to the configured megastep width so the fold
                # compiles once per engine config, not once per adaptive
                # gap length (a zero-service step is an update no-op for
                # every registered policy); an explicit megastep() call
                # wider than the config gets its own cell.
                util = float(np.mean([float(fb.utilization)
                                      for fb in feedbacks]))
                zero = policies_lib.Feedback(
                    moved_read=self._fb_zero, moved_write=self._fb_zero,
                    utilization=np.float32(0.0))
                pad = max(0, max(1, self.cfg.megastep) - len(feedbacks))
                self.queue.note_service(
                    policies_lib.stack_feedbacks(feedbacks + [zero] * pad),
                    mean_util=util)
            self.step_count += k
            self.megasteps += 1
            self._inflight.append(rec)
            span.update(in_flight=len(self._inflight),
                        page_ins=report["page_ins"],
                        page_outs=report["page_outs"],
                        migrations=report["migrations"])
        if self._tracer is not None:
            self._tracer.counter("in_flight", len(self._inflight))
        return rec

    def _retire_planned(self, rec: _InFlight) -> int:
        """Trajectory-driven LLM retirement at dispatch time: rows whose
        predicted final state is DONE leave their slots before the
        readback lands (their remaining inner steps are frozen on device;
        the sampled values arrive with the deferred readback). The
        completion step is deterministic, so this stamps the same
        ``done_step`` the classic post-readback retirement did."""
        n = 0
        for r in rec.live:
            if r.state == FAILED:
                continue
            steps_r = rec.traj[r.rid]
            if steps_r[-1].state != S_DONE:
                continue
            r.done_step = rec.now + next(
                t for t, st in enumerate(steps_r) if st.state == S_DONE)
            if self.paged and r.blocks and not r.blocks_freed:
                self.pool.free(r.blocks)
                r.blocks_freed = True
                rec.journal.append(("free", r, list(r.blocks)))
            self._scan_cursor.pop(r.rid, None)
            self.slots[r.slot] = None
            self.completed[r.rid] = r
            n += 1
        return n

    def _reconcile(self, rec: _InFlight) -> dict:
        """Consume one in-flight megastep's deferred packed readback:
        append the sampled token values to the real host mirrors,
        cross-check the device's final counters against the dispatched
        trajectory, and surface the boundary report. At depth 1 this
        runs right after its own dispatch (the classic blocking loop);
        at depth 2 it runs one boundary late, with t+1 already in
        flight. A readback that contradicts its trajectory rolls back
        every speculative pool mutation before raising."""
        with phase(self._tracer, "reconcile", step=rec.now,
                   k=rec.k) as span:
            self._inflight.remove(rec)
            bubble = bool(rec.live and not self._inflight)
            if bubble:
                # the host blocks on this readback with nothing dispatched
                # ahead of it — a pipeline bubble.
                self.host_blocked += 1
            advanced = 0
            tok_pairs = [] if self._snap is not None else None
            firsts, lasts = [], []   # rows given their first/last token
            if rec.live:
                with phase(self._tracer, "readback", step=rec.now):
                    rb = self._readback(rec.packed)
                try:
                    for r in rec.live:
                        if r.state == FAILED:
                            # failed mid-flight (poison/casualty/shed): the
                            # device row's readback is moot — the request
                            # already carries its structured error.
                            continue
                        steps_r = rec.traj[r.rid]
                        toks = [int(rb[r.slot, 3 + t])
                                for t, st in enumerate(steps_r) if st.emitted]
                        c0, g0 = r.consumed, len(r.generated)
                        dev_state = int(rb[r.slot, 0])
                        dev_consumed = int(rb[r.slot, 1])
                        dev_ngen = int(rb[r.slot, 2])
                        last = steps_r[-1]
                        exp_ngen = g0 + sum(st.emitted for st in steps_r)
                        fields = []
                        if STATE_OF_CODE.get(dev_state) != \
                                STATE_OF_CODE[last.state]:
                            fields.append(
                                f"state (host planned "
                                f"{STATE_OF_CODE[last.state]}, device "
                                f"reported {STATE_OF_CODE.get(dev_state, f'code {dev_state}')})")
                        if dev_consumed != last.consumed:
                            fields.append(
                                f"consumed (host planned {last.consumed}, "
                                f"device reported {dev_consumed})")
                        if dev_ngen != exp_ngen:
                            fields.append(
                                f"n_gen (host planned {exp_ngen}, device "
                                f"reported {dev_ngen})")
                        if fields:
                            raise RuntimeError(
                                f"rid {r.rid}: boundary at step {rec.now} "
                                f"(k={rec.k}): device readback diverged "
                                f"from the host trajectory on "
                                + "; ".join(fields))
                        r.sync_megastep(dev_state, dev_consumed,
                                        dev_ngen, toks)
                        advanced += ((last.consumed + last.n_gen) - (c0 + g0)
                                     - sum(st.transition for st in steps_r))
                        if tok_pairs is not None and toks:
                            tok_pairs.append((r.rid, toks))
                        if toks and g0 == 0:
                            firsts.append(r)
                        if toks and r.finished:
                            lasts.append(r)
                except RuntimeError:
                    if self._tracer is not None:
                        self._tracer.instant(
                            "engine", "divergence_rollback",
                            {"step": rec.now, "k": rec.k}, clock="host")
                    self._rollback_speculation(rec)
                    raise
            if self._snap is not None:
                self._snap.note_boundary(
                    self, rec.now, rec.k,
                    [r.rid for r in rec.live
                     if r.admitted_step == rec.now], tok_pairs)
            span.update(host_blocked=bubble, advanced=advanced)
        # one host-clock reading as the tokens are handed to the caller
        t_host = time.perf_counter()
        for r in firsts:
            r.t_first = t_host
        for r in lasts:
            r.t_done = t_host
            if self._tracer is not None:
                self._tracer.request(r)
        return {"step": rec.now, "steps": rec.k,
                "admitted": rec.admitted, "advanced": advanced,
                **rec.report}

    def _rollback_speculation(self, failed: _InFlight) -> None:
        """Divergence escape hatch: the device contradicted a dispatched
        trajectory, so every pool mutation made for not-yet-reconciled
        megasteps (the failed one and anything dispatched after it) is
        speculative garbage. Replay the journals backwards — newest
        boundary first, newest op first — to restore consistent block
        ownership: speculative allocs are freed again (and dropped from
        their request's tail — allocation order makes them the tail),
        speculative frees are reclaimed (ownership returns; the data
        round-trips already spent stay spent). The protected invariant
        is *ownership*, not bytes — no block leaks, none double-frees,
        and ``PagedKVPool.check_invariants()`` holds on exit; the engine
        itself is poisoned and the caller's RuntimeError propagates."""
        recs = [failed] + self._inflight
        self._inflight = []
        for rec in reversed(recs):
            for op, req, ids in reversed(rec.journal):
                if op == "alloc":
                    del req.blocks[len(req.blocks) - len(ids):]
                    self.pool.free(ids)
                else:
                    self.pool.reclaim(ids)
                    req.blocks_freed = False
            rec.journal = []

    # -- fault recovery (graceful degradation) -------------------------------
    def _total_blocks(self, r: Request) -> int:
        """Every KV block this LLM request will ever hold."""
        return math.ceil((r.prompt_len + r.max_new_tokens)
                         / self.cfg.block_tokens)

    def _committed_blocks(self) -> int:
        """Host-capacity commitment: live LLM rows' eventual full block
        footprint plus whatever else (tenants) holds pool blocks now —
        the steady-state demand surviving host capacity must cover."""
        live = [r for r in self.slots
                if r is not None and r.state != FAILED]
        need = sum(self._total_blocks(r) for r in live)
        other = (int(self.pool._allocated.sum())
                 - sum(len(r.blocks) for r in live))
        return need + max(0, other)

    def _fail_request(self, r: Request, error: dict, journal: list
                      ) -> None:
        """Move one request to the FAILED terminal state: structured
        ``error`` attached, pool blocks freed (journaled, so a later
        divergence rollback stays ownership-consistent), slot vacated.
        Partial output stays on the request (``engine.failed[rid]``);
        everyone else keeps being served."""
        if r.state == FAILED:
            return
        r.state = FAILED
        r.spec = None
        r.error = dict(error)
        r.done_step = int(error.get("step", self.step_count))
        if self.paged and r.blocks and not r.blocks_freed:
            self.pool.free(r.blocks)
            r.blocks_freed = True
            journal.append(("free", r, list(r.blocks)))
        self._scan_cursor.pop(r.rid, None)
        if 0 <= r.slot < len(self.slots) and self.slots[r.slot] is r:
            self.slots[r.slot] = None
        self.failed[r.rid] = r
        if self._fx is not None:
            self._fx.stats["failed"] += 1
        if self._tracer is not None:
            self._tracer.instant(
                "faults", "request_failed",
                {"rid": r.rid, "kind": error.get("kind")})

    def _service_fault_report(self, rep: dict, step_now: int,
                              rec: _InFlight) -> None:
        """Translate one pool transaction's fault report into request
        consequences: a poisoned (checksum-mismatched) or
        evacuation-casualty block fails its owning LLM request — and
        ONLY that request. Blocks owned by non-LLM tenants come back
        zero-installed (modelled data loss; KV-store semantics: the
        value is gone) — the tenant keeps running. Unowned blocks are
        already counted by the injector."""
        for kind, blocks in (("poisoned_block", rep.get("poisoned", ())),
                             ("evacuation_casualty",
                              rep.get("casualties", ()))):
            for b in blocks:
                owner = next(
                    (r for r in self.slots
                     if r is not None and r.state != FAILED
                     and b in r.blocks), None)
                if owner is not None:
                    self._fail_request(
                        owner, {"kind": kind, "block": int(b),
                                "step": int(step_now)}, rec.journal)

    def _shed_over_capacity(self, rec: _InFlight) -> None:
        """Deadline-based load shedding once host capacity degrades
        (channel offline / quarantined slots): while the committed block
        footprint exceeds surviving capacity, fail live rows — doomed
        deadlines first (they cannot finish in time anyway), then the
        largest footprints — and drop queued LLM requests that could
        never fit even alone, so ``run()`` drains cleanly instead of
        stalling on unservable work."""
        fx = self._fx
        cap_live = self.pool.host.live_capacity()
        committed = self._committed_blocks()
        now = self.step_count
        if committed > cap_live:
            live = [r for r in self.slots
                    if r is not None and r.state != FAILED]

            def doomed(r: Request) -> bool:
                return (r.deadline_step is not None
                        and now + self._steps_until_done(r)
                        > r.deadline_step)

            for r in sorted(live, key=lambda r: (not doomed(r),
                                                 -self._total_blocks(r),
                                                 r.rid)):
                if committed <= cap_live:
                    break
                committed -= self._total_blocks(r)
                self._fail_request(
                    r, {"kind": "shed", "step": now,
                        "committed_blocks": committed
                        + self._total_blocks(r),
                        "live_capacity": cap_live}, rec.journal)
                fx.stats["shed"] += 1
        for r in list(self.queue.waiting()):
            if r.tenant == "llm" and self._total_blocks(r) > cap_live:
                self.queue.remove(r)
                self._fail_request(
                    r, {"kind": "shed", "step": now,
                        "needed_blocks": self._total_blocks(r),
                        "live_capacity": cap_live}, rec.journal)
                fx.stats["shed"] += 1

    def run(self, max_steps: int | None = None) -> dict[int, np.ndarray]:
        """Drive megasteps until every submitted request completes.

        Between admission events the engine free-runs: ``_auto_megastep``
        picks the widest K <= ``cfg.megastep`` that cannot skip a step
        where admission could change the live set (an arrival, a slot
        freed by a completion, a write-through headroom change — all
        host-deterministic), so admission happens at exactly the steps
        the K=1 loop would have used while the host dispatches once per
        gap. ``stats()`` reports ``host_dispatches`` next to ``steps`` —
        the dispatch-tax ratio this loop exists to shrink.

        With ``cfg.pipeline_depth > 1`` the loop double-buffers the
        boundaries: it plans and dispatches megastep t+1 *before*
        reconciling t's deferred readback, so the host's planning work
        overlaps the device's still-in-flight compute and only the final
        drain blocks with nothing dispatched ahead (``host_blocked``
        counts those bubbles). Results are bit-exact across depths.

        Under fault injection the returned dict holds the *survivors*;
        requests failed by poisoned blocks, evacuation casualties or
        load shedding land in ``self.failed`` with a structured
        ``Request.error`` — partial results, not a dropped fleet. A
        boundary that makes no progress at all (nothing live, nothing
        admitted, no tenant running) ``cfg.stall_boundaries`` times in
        a row raises ``EngineStallError`` naming the stuck rids instead
        of spinning to the step limit."""
        limit = max_steps if max_steps is not None else 10_000
        depth = max(1, self.cfg.pipeline_depth)
        stall_cap = max(1, self.cfg.stall_boundaries)
        done_steps = 0
        stall = 0
        while done_steps < limit:
            if self._snap is not None:
                # journaled resubmits due at this boundary come back
                # BEFORE the pending() check — a restored engine whose
                # cut had nothing live still owes them a replay.
                self._snap.inject_resubmits(self)
            if not self.pending():
                break
            if self._snap is not None:
                # crash-consistent cut if one is due (drains the
                # pipeline; flushes dirty HBM through the billed path).
                self._snap.maybe_cut(self)
            k = self._auto_megastep(limit - done_steps)
            rec = self._plan(k)
            self._dispatch(rec)
            done_steps += k
            progress = (rec.admitted > 0 or bool(rec.live)
                        or any(tn.running()
                               for tn in self.tenants.values()))
            stall = 0 if progress else stall + 1
            if stall >= stall_cap:
                while self._inflight:
                    self._reconcile(self._inflight[0])
                stuck = sorted(
                    [r.rid for r in self.queue.waiting()]
                    + [r.rid for r in self.active()]
                    + [r.rid for t in self.tenants.values()
                       for r in t.running()])
                raise EngineStallError(
                    f"no progress for {stall_cap} consecutive megastep "
                    f"boundaries (step {self.step_count}): rids {stuck} "
                    f"are stuck (never admitted, never advancing)",
                    stuck)
            while len(self._inflight) >= depth:
                self._reconcile(self._inflight[0])
        while self._inflight:
            self._reconcile(self._inflight[0])
        if self.pending():
            stuck = sorted(
                [r.rid for r in self.queue.waiting()]
                + [r.rid for r in self.active()]
                + [r.rid for t in self.tenants.values()
                   for r in t.running()])
            raise RuntimeError(
                f"requests still pending after {limit} steps: "
                f"rids {stuck}")
        return {rid: np.asarray(r.generated, np.int32)
                for rid, r in sorted(self.completed.items())}

    # -- megastep planning (host-deterministic trajectories) ----------------
    def _simulate_row(self, r: Request, k: int) -> "list[_RowStep]":
        """Predict one live row's next ``k`` engine steps.

        Everything but the sampled token values is fixed-width counter
        arithmetic — the exact twin of the fused program's state machine:
        a PREFILL row consumes up to ``prefill_chunk`` prompt tokens per
        step and emits once on its transition micro-step; a DECODE row
        emits exactly one token per step; DONE rows freeze. The megastep
        path plans all K paging transactions from this and uses the
        readback only for token values (divergence raises). Reads the
        planning view (``plan_*``) so a pipelined boundary simulates
        from the dispatched-but-unreconciled predecessor's end state."""
        n_micro = max(1, self.cfg.prefill_chunk)
        state = {PREFILL: S_PREFILL, DECODE: S_DECODE,
                 DONE: S_DONE}[r.plan_state]
        consumed, n_gen = r.plan_consumed, r.plan_n_gen
        plen, mnew = r.prompt_len, r.max_new_tokens
        out = []
        for _ in range(k):
            emitted = transition = False
            if state == S_DECODE:
                n_gen += 1
                emitted = True
                if n_gen >= mnew:
                    state = S_DONE
            elif state == S_PREFILL:
                consumed = min(plen, consumed + n_micro)
                if consumed >= plen:
                    n_gen += 1
                    emitted = transition = True
                    state = S_DONE if n_gen >= mnew else S_DECODE
            written = (consumed if state == S_PREFILL
                       else max(consumed + n_gen - 1, 0))
            out.append(_RowStep(state=state, consumed=consumed,
                                n_gen=n_gen, written=written,
                                emitted=emitted, transition=transition))
        return out

    def _steps_until_done(self, r: Request) -> int:
        """Engine steps until this live row completes (deterministic;
        planning view)."""
        if r.plan_state == DONE:
            return 0
        n = 0
        if r.plan_state == PREFILL:
            n = self._steps_until_decode(r)
            gen_left = r.max_new_tokens - r.plan_n_gen - 1
        else:
            gen_left = r.max_new_tokens - r.plan_n_gen
        return max(1, n + gen_left)

    def _steps_until_decode(self, r: Request) -> int:
        """Steps until a prefilling row's PREFILL->DECODE transition."""
        if r.plan_state != PREFILL:
            return 0
        n_micro = max(1, self.cfg.prefill_chunk)
        return max(1, -(-(r.prompt_len - r.plan_consumed) // n_micro))

    def _auto_megastep(self, remaining: int) -> int:
        """Widest safe megastep from the current boundary: never skip a
        step where admission could change the live set. Event horizon =
        future arrivals, plus (while admissible work waits) the earliest
        deterministic completion or prefill->decode transition (slot and
        write-through headroom changes). Quantized down to a power of
        two so the adaptive loop populates O(log K) program cells."""
        cap = min(max(1, self.cfg.megastep), max(1, remaining))
        if cap == 1:
            return 1
        now = self.step_count
        live = self.active()
        waiting = self.queue.waiting()
        events = [r.arrival_step - now for r in waiting
                  if r.arrival_step > now]
        if any(r.arrival_step <= now for r in waiting):
            evs = []
            for r in live:
                evs.append(self._steps_until_done(r))
                if r.plan_state == PREFILL:
                    evs.append(self._steps_until_decode(r))
            for tn in self.tenants.values():
                for tr in tn.running():
                    ci = tn.completion_in(tr)
                    evs.append(1 if ci is None else max(1, ci))
            events.append(min(evs) if evs else 1)
        if events:
            k = min(cap, max(1, min(events)))
        else:
            # nothing can be admitted before the live set drains: free-run
            # to the end of the longest remaining work (or the cap).
            rem = [self._steps_until_done(r) for r in live]
            for tn in self.tenants.values():
                rem.extend(max(1, tn.completion_in(tr) or 1)
                           for tr in tn.running())
            k = min(cap, max(rem)) if rem else 1
        return 1 << (k.bit_length() - 1)

    # -- phase 1: admission -------------------------------------------------
    def _worst_step_blocks(self, prompt_len: int, max_new: int,
                           prefilling: bool) -> int:
        """Worst-case KV blocks one request can newly fill in one engine
        step: a prefilling row consumes up to prefill_chunk tokens
        (capped by its total), a decoding row writes one token per step
        and so crosses at most one block boundary."""
        if not prefilling:
            return 1
        bt = self.cfg.block_tokens
        chunk = max(1, self.cfg.prefill_chunk)
        return min(math.ceil((prompt_len + max_new) / bt),
                   math.ceil(chunk / bt))

    def _admission_budget(self, now: int, n_free: int) -> int:
        """Cap admissions on write-through headroom: the whole batch's
        worst-case newly filled blocks per step — plus the HBM blocks
        reserved for attached tenants — must fit the pool's HBM, so the
        mid-step overflow is unreachable; joint prefill demand throttles
        at admission instead of raising in ``_page_kv``. Requests left
        waiting are retried as running rows retire."""
        if not self.paged:
            return n_free
        running = sum(
            self._worst_step_blocks(r.prompt_len, r.max_new_tokens,
                                    r.plan_state == PREFILL)
            for r in self.active())
        headroom = (self.pool.hbm_capacity - self._reserved_blocks
                    - running)
        arrived = [r for r in self.queue.waiting(now)
                   if r.tenant == "llm"]
        if not arrived or headroom < 1:
            return 0 if arrived else n_free
        # conservative per-admission cost: the largest worst-case among
        # the requests the policy could pick (each is <= hbm_blocks by
        # the submit-time guard).
        per_adm = max(self._worst_step_blocks(r.prompt_len,
                                              r.max_new_tokens, True)
                      for r in arrived)
        budget = min(n_free, headroom // per_adm)
        if (self._fx is not None
                and self.pool.host.capacity_degraded):
            # degraded-capacity backpressure: never commit more eventual
            # host blocks than the surviving channels can hold — place()
            # is sticky, so every admitted block needs a live slot.
            per_total = max(self._total_blocks(r) for r in arrived)
            room = (self.pool.host.live_capacity()
                    - self._committed_blocks())
            budget = min(budget, max(0, room) // per_total)
        return budget

    def _admit(self, now: int) -> int:
        free = [i for i, r in enumerate(self.slots) if r is None]
        budget: int | dict[str, int] = self._admission_budget(
            now, len(free)) if free else 0
        if self.tenants:
            budget = {"llm": max(0, budget)}
            for t in self.tenants.values():
                budget[t.name] = t.free_slots()
        elif budget <= 0:
            return 0
        admitted = self.queue.dispatch(now, budget)
        if not admitted:
            return 0
        t_admit = time.perf_counter()
        for req in admitted:
            req.t_admit = t_admit
        llm = [r for r in admitted if r.tenant == "llm"]
        for req in admitted:
            if req.tenant != "llm":
                self.tenants[req.tenant].start(req, now)
        if not llm:
            return len(admitted)
        B = self.cfg.max_batch
        P = self.cfg.cache_len
        mask = np.zeros((B,), bool)
        prompts = np.zeros((B, P), np.int32)
        plen = np.zeros((B,), np.int32)
        mnew = np.zeros((B,), np.int32)
        for req in llm:
            slot = free.pop(0)
            req.slot = slot
            self.slots[slot] = req
            self._scan_cursor[req.rid] = 0
            mask[slot] = True
            prompts[slot, :req.prompt_len] = req.prompt
            plen[slot] = req.prompt_len
            mnew[slot] = req.max_new_tokens
        m = jnp.asarray(mask)
        self.cache = _reset_rows(self.cache, self._cache0, m)
        self._dev = _admit_rows(self._dev, m, jnp.asarray(prompts),
                                jnp.asarray(plen), jnp.asarray(mnew))
        return len(admitted)

    # -- phase 2: fused token micro-steps -----------------------------------
    def _written(self, r: Request) -> int:
        """Tokens whose KV is actually in the dense cache: all consumed
        prompt tokens, plus every generated token that has been fed back
        (the newest sampled token is only written on its next feed). Also
        the next write position — the cache is written densely in order."""
        if r.state == PREFILL:
            return r.consumed
        return r.consumed + len(r.generated) - 1

    def _readback(self, packed) -> np.ndarray:
        """The megastep's single device->host sync: one packed (B, 3+K)
        int32 array of per-slot (state | consumed | n_gen | K newest
        tokens)."""
        return np.asarray(packed)

    # -- batched KV paging (all tenants, one transaction per inner step) ----
    def _page_kv_at(self, now: int, rows: "list[tuple[Request, _RowStep]]",
                    staged, t: int, journal: list) -> dict:
        """One paging transaction for inner step ``t`` of a megastep:
        LLM KV traffic (planned from the host-deterministic trajectory,
        written through from the megastep program's staged slab) plus
        every tenant's block demand, grouped by hint scope, through a
        single ``PagedKVPool.step_multi`` call; then each tenant's device
        compute against the resident blocks. Dispatch-only — nothing here
        waits on the device. Every alloc is recorded in the dispatching
        megastep's ``journal`` so a divergence can roll it back."""
        bt = self.cfg.block_tokens
        new_pairs: list[tuple[Request, int, int]] = []  # (req, bi, stage_j)
        for r, st in rows:
            # invariant: entering inner step t, len(r.blocks) is the
            # block count before the step — the device staged this step's
            # fills at stage rows j = bi - fill_base.
            fill_base = len(r.blocks)
            n_filled = st.written // bt
            while len(r.blocks) < n_filled:
                bi = len(r.blocks)
                r.blocks.extend(self._alloc_block(r))
                journal.append(("alloc", r, [r.blocks[bi]]))
                new_pairs.append((r, bi, bi - fill_base))

        # tenant demand first: it is bounded by the per-tenant
        # reservations, and the LLM cold-scan budget shrinks to whatever
        # the tenants actually left unclaimed this step.
        tenant_groups: list[tuple[str, list[int]]] = []
        tenant_blocks = 0
        for tn in self.tenants.values():
            for path, ids in tn.block_demand(now):
                if ids:
                    tenant_groups.append((path, ids))
                    tenant_blocks += len(set(ids))

        new_ids = [r.blocks[bi] for r, bi, _ in new_pairs]
        budget = self.pool.hbm_capacity - tenant_blocks
        if len(new_ids) > budget:
            raise RuntimeError(
                f"{len(new_ids)} blocks filled in one step but pool HBM "
                f"holds {self.pool.hbm_capacity} ({tenant_blocks} claimed "
                f"by tenants); shrink prefill_chunk or grow hbm_blocks")
        # new blocks first — they must be resident for the write-through;
        # demand beyond capacity is advisory and may be trimmed.
        holders = [r for r, _ in rows]
        demand = self._block_demand(holders)
        needed = list(dict.fromkeys(new_ids + [b for _, b, _ in demand]))
        needed = needed[:budget]
        self._advance_cursors(holders, demand, set(needed))
        groups = ([("/serve/kv_cache", needed)] if needed else []) \
            + tenant_groups
        if not groups and not self.tenants:
            return {"page_ins": 0, "page_outs": 0}
        report = (self.pool.step_multi(groups) if groups
                  else {"page_ins": 0, "page_outs": 0})

        if new_pairs:
            # fixed-width write-through from the megastep staging stack:
            # stage row slot*max_fills + j holds the block the fused
            # program extracted right after this inner step; padding rows
            # carry an out-of-range sentinel id the pool's scatter drops,
            # so the program never retraces on the per-step block count.
            n_micro = max(1, self.cfg.prefill_chunk)
            max_fills = -(-n_micro // bt)
            ids = np.full((self.cfg.max_batch * max_fills,),
                          self.pool.n_blocks, np.int32)
            for r, bi, j in new_pairs:
                ids[r.slot * max_fills + j] = r.blocks[bi]
            self.pool.write_staged(ids, staged, t)
        for tn in self.tenants.values():
            tn.compute(self.pool, now)
        return report

    def _block_demand(self, live: list[Request]
                      ) -> list[tuple[int, int, bool]]:
        """The step's resident set as (rid, block, is_cold) triples:
        per-slot fair share of the pool's HBM, newest blocks pinned,
        remaining share cycling through the cold tail (attention re-reads
        the whole history every token; a smaller working set streams it
        block-at-a-time — the capacity-tier round-trip traffic). Cursors
        advance in ``_advance_cursors``, only for picks actually paged."""
        holders = [r for r in live if r.blocks]
        if not holders:
            return []
        budget = max(1, self.pool.hbm_capacity // len(holders))
        demand: list[tuple[int, int, bool]] = []
        for r in holders:
            demand.append((r.rid, r.blocks[-1], False))
            older = r.blocks[:-1]
            k = min(budget - 1, len(older))
            if k > 0:
                c = self._scan_cursor.get(r.rid, 0) % len(older)
                ring = older[c:] + older[:c]
                demand.extend((r.rid, b, True) for b in ring[:k])
        return demand

    def _advance_cursors(self, holders: list[Request],
                         demand: list[tuple[int, int, bool]],
                         kept: set[int]) -> None:
        """Move each request's cold-scan cursor past the cold picks that
        survived the capacity trim — trimmed blocks were never paged, so
        the round-robin scan must revisit them next step."""
        stepped: dict[int, int] = {}
        for rid, block, cold in demand:
            if cold and block in kept:
                stepped[rid] = stepped.get(rid, 0) + 1
        for r in holders:
            k = stepped.get(r.rid)
            if k and len(r.blocks) > 1:
                n = len(r.blocks) - 1
                c = self._scan_cursor.get(r.rid, 0) % n
                self._scan_cursor[r.rid] = (c + k) % n

    # -- reporting -----------------------------------------------------------
    def stats(self) -> dict:
        """Dispatch accounting: ``steps`` (engine steps run),
        ``host_dispatches`` (fused step-program launches — the per-token
        host round-trip tax megasteps amortize), ``megasteps`` (boundary
        count) and ``host_blocked`` (boundaries whose readback the host
        consumed with nothing dispatched ahead of it — the
        pipeline-bubble count the depth-2 dispatcher shrinks to the
        single final drain). steps / host_dispatches is the realized
        megastep width."""
        return {"steps": self.step_count,
                "host_dispatches": self.host_dispatches,
                "megasteps": self.megasteps,
                "host_blocked": self.host_blocked,
                "faults": (dict(self._fx.stats) if self._fx is not None
                           else fresh_fault_stats()),
                "snapshot": (dict(self._snap.stats)
                             if self._snap is not None
                             else fresh_snapshot_stats())}

    def reset_stats(self) -> None:
        """Zero the *counters* without touching the *clocks*:
        ``step_count``/``megasteps`` keep running (determinism — the
        snapshot journal, fault plan and admission timing key on them),
        while dispatch/bubble counters, pool billing, fault stats and
        snapshot stats restart. Benchmark plumbing for measuring a warm
        window."""
        self.host_dispatches = 0
        self.host_blocked = 0
        if self.paged:
            self.pool.reset_stats()
        if self._fx is not None:
            self._fx.stats.clear()
            self._fx.stats.update(fresh_fault_stats())
        if self._snap is not None:
            self._snap.reset_stats()
        self.telemetry.reset()

    def restore(self, step: int | None = None, *,
                disarm_crashes: bool = True) -> dict:
        """Load the newest valid snapshot (or ``step``) from
        ``cfg.snapshot_dir`` into this engine and arm deterministic
        journal replay; the next ``run()`` resumes bit-exactly. Returns
        the restore report (restored step, journal stats, casualties)."""
        if self._snap is None:
            raise ValueError(
                "restore needs snapshots enabled (snapshot_every > 0 "
                "and snapshot_dir)")
        return self._snap.restore_into(self, step,
                                       disarm=disarm_crashes)

    def paging_stats(self) -> dict:
        if not self.paged:
            return {"paged": False, **self.stats()}
        # pool stats carry their own "steps" (paging transactions); the
        # engine's dispatch accounting wins the shared key, the pool's
        # count survives as "paging_steps".
        stats = {"paged": True, **self.pool.stats,
                 "paging_steps": self.pool.stats["steps"], **self.stats(),
                 "duplex_speedup": self.pool.duplex_speedup()}
        # unified schema (core.metrics): tiers/tier_speedup are ALWAYS
        # present — flat pools report their single channel with the
        # tier fields zeroed, so consumers never key-guard.
        stats["tiers"] = self.pool.tier_stats()
        stats["tier_speedup"] = self.pool.tier_speedup()
        stats["by_path"] = {
            path: {**st, "duplex_speedup": self.pool.duplex_speedup(path)}
            for path, st in self.pool.stats["by_path"].items()}
        if self.tenants:
            stats["tenants"] = {t.name: t.stats()
                                for t in self.tenants.values()}
        return stats

    @property
    def tracer(self):
        """The engine's ``serve.trace.Tracer`` (None when disabled)."""
        return self._tracer

    def export_trace(self, path: str | None = None) -> str:
        """Write the Perfetto trace; needs ``cfg.trace`` enabled."""
        if self._tracer is None:
            raise ValueError("tracing is disabled; build the engine "
                             "with EngineConfig(trace=...)")
        return self._tracer.export(path)

    def metrics(self):
        """One typed ``core.metrics.MetricsRegistry`` snapshot of the
        whole engine: stats()/paging_stats() flattened into counters
        and gauges, the tracer's span histograms (when tracing), and
        the CAX scope tree under ``"cax"`` — the unified view BENCH,
        ``--telemetry`` and a future cluster router all read."""
        reg = MetricsRegistry()
        reg.ingest("engine", self.paging_stats())
        if self._tracer is not None:
            for name, _, dur, _ in self._tracer.spans:
                reg.observe(f"span.{name}.us", dur)
        snap = reg.snapshot()
        if self._tracer is not None:
            snap["trace"] = self._tracer.summary()
        snap["cax"] = self.telemetry.to_dict()
        return snap


def reference_decode(api: ModelAPI, params, prompts: jnp.ndarray,
                     num_tokens: int, cache_len: int = 128) -> jnp.ndarray:
    """Static-batch greedy decode — the token-for-token oracle the engine
    is tested against. prompts: (B, P) int32; returns (B, num_tokens).
    The cache buffer is donated through every step (the ModelAPI
    donation contract), matching the engine's memory behavior."""
    B, P = prompts.shape
    step = jax.jit(api.decode_step, donate_argnums=(1,))
    cache = api.init_cache(B, cache_len)
    logits = None
    for t in range(P):
        logits, cache = step(params, cache, prompts[:, t],
                             jnp.full((B,), t, jnp.int32))
    outs = []
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    for i in range(num_tokens):
        outs.append(tok)
        logits, cache = step(params, cache, tok,
                             jnp.full((B,), P + i, jnp.int32))
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jnp.stack(outs, axis=1)
