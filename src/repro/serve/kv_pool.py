"""Vectorized tiered KV block pool — the serving memory hierarchy.

One pool shared by every request in the batch:

  * residency, the slot map, and last-use clocks are **host numpy** arrays
    (``slot_of``, ``block_at``, ``last_use``) — they never participate in
    device compute, and every consumer (victim picking, invariant checks,
    the engine's write-through) reads them on the host, so keeping them in
    HBM only bought a device scatter per ``touch``/``free`` plus an
    ``np.asarray`` round-trip per read. Eviction choice is one ``argsort``
    over the clock array;
  * ``step(needed)`` ensures residency for the whole batch's block demand in
    one shot: ONE ``DuplexOffloadEngine`` plan co-issuing every page-in with
    the evictions it displaces, and ONE kernel invocation for all of the
    step's traffic — the fused ``duplex_kv_stream`` when both directions
    carry blocks (dequantizing arrivals while quantizing departures — both
    DMA directions busy), or the single-direction dequant-only /
    quant-only Pallas half when one stream is empty (no zero-block padding,
    no dead half of the fused grid; stats billing is identical);
  * HBM writes/reads are batched scatters/gathers over block id arrays.

Cold blocks live int8-quantized in the host pool (2x link-byte compression
on top of duplexing, per the paper's capacity-tier story). Modelled duplex
vs phase-separated link timings are accumulated in ``stats`` (functional
execution is real; timing is modelled per the channel model).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import channel as channel_lib
from repro.core.hints import HintTree, default_serving_hints
from repro.core.offload import (DuplexOffloadEngine,
                                phase_separated_time_us, plan_serial)
from repro.kernels import ops as kernel_ops
from repro.serve.tiers import TieredHostPool


def _fresh_stats() -> dict:
    return {"page_ins": 0, "page_outs": 0, "duplex_us": 0.0,
            "serial_us": 0.0, "kernel_calls": 0, "steps": 0,
            "tier_us": 0.0, "ddr5_us": 0.0, "migrations": 0,
            "migrate_us": 0.0, "by_path": {}}


def _fresh_path_stats() -> dict:
    return {"page_ins": 0, "page_outs": 0, "duplex_us": 0.0,
            "serial_us": 0.0, "fused_calls": 0}


# ---------------------------------------------------------------------------
# jitted data-plane programs — the per-step gather/commit halves around the
# (eagerly invoked, test-countable) stream kernel. Each is one dispatch
# instead of one per array; shapes are static per (n_in, n_out, n_fresh)
# so the handful of combos a serving run produces each compile once.
# ---------------------------------------------------------------------------

#: staging-buffer depth for the fused duplex kernel: each pipelined grid
#: step DMAs a slab of this many pages per direction while the previous
#: slab transforms (the kernel's double-buffer granularity; streams are
#: zero-padded up to a multiple and the padding is dropped at commit).
STAGE_BLOCKS = 2


@jax.jit
def _gather_duplex(host_q, host_scale, hbm, stale_ids, out_slot_ids):
    """Both directions busy: gather + pad both streams to a uniform grid
    (a multiple of the staging depth) for the fused kernel in one
    program."""
    m = max(stale_ids.shape[0], out_slot_ids.shape[0])
    m += -m % STAGE_BLOCKS

    def pad(a):
        if a.shape[0] == m:
            return a
        fill = jnp.zeros((m - a.shape[0],) + a.shape[1:], a.dtype)
        return jnp.concatenate([a, fill])

    return (pad(host_q[stale_ids]), pad(host_scale[stale_ids]),
            pad(hbm[out_slot_ids]))


@jax.jit
def _gather_in(host_q, host_scale, stale_ids):
    return host_q[stale_ids], host_scale[stale_ids]


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _commit_paging(hbm, host_q, host_scale, in_deq, out_q, out_scale,
                   out_ids, dst_stale, dst_fresh):
    """Apply one paging step's results: spill quantized departures to the
    host tier, install dequantized arrivals, zero-fill fresh installs.
    ``in_deq``/``out_q``/``out_scale`` are None on the empty direction;
    the live tier buffers are donated (one HBM copy, not two)."""
    n_out = out_ids.shape[0]
    if n_out:
        host_q = host_q.at[out_ids].set(out_q[:n_out])
        host_scale = host_scale.at[out_ids].set(out_scale[:n_out])
    n_stale = dst_stale.shape[0]
    if n_stale:
        hbm = hbm.at[dst_stale].set(in_deq[:n_stale])
    if dst_fresh.shape[0]:
        hbm = hbm.at[dst_fresh].set(jnp.zeros((), jnp.bfloat16))
    return hbm, host_q, host_scale


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _migrate_rows(host_q, host_scale, src, dst):
    """Host-tier rebalance: copy quantized rows ``src -> dst`` verbatim
    (int8 payload + scales — migrations are bit-exact by construction).
    Fixed width: padding rows carry ``dst == total_slots`` and drop, so
    the program compiles once per pool shape, never per move count."""
    return (host_q.at[dst].set(host_q[src], mode="drop"),
            host_scale.at[dst].set(host_scale[src], mode="drop"))


@functools.partial(jax.jit, donate_argnums=(0,))
def _write_blocks(hbm, dst, data):
    """Fixed-width write-through scatter; out-of-range dst rows (padding
    sentinels) are dropped."""
    return hbm.at[dst].set(data.astype(jnp.bfloat16), mode="drop")


@functools.partial(jax.jit, donate_argnums=(0,))
def _write_blocks_at(hbm, dst, staged, t):
    """Megastep write-through: scatter inner step ``t``'s slab out of the
    (K, W, tokens, kv_dims) staging stack the fused megastep program
    emitted. ``t`` is a device scalar — one compiled program per staged
    shape, not per step index — and the slab is sliced on device, so the
    staging stack never round-trips the host."""
    data = jax.lax.dynamic_index_in_dim(staged, t, axis=0, keepdims=False)
    return hbm.at[dst].set(data.astype(jnp.bfloat16), mode="drop")


class PagedKVPool:
    """Block-table KV pool: HBM working set + tiered int8 host side.

    ``n_blocks`` logical blocks of ``block_shape = (tokens, kv_dims)``;
    at most ``hbm_blocks`` are HBM-resident at a time. Logical block ids are
    allocated per request (``alloc``/``free``) or caller-managed.

    ``tiers`` backs the host side with heterogeneous memory channels
    (``serve.tiers.TieredHostPool``): a ``"ddr5:2,cxl:2"`` spec string or
    a (kind, ChannelModel) sequence. Spilled blocks get a host *slot*
    through the hint-driven weighted-interleave placement map, traffic is
    billed per channel, ``tier_speedup()`` compares against the all-DDR5
    serial counterfactual, and ``migrate_tiers()`` (called by the engine
    at megastep boundaries) rebalances mismatched blocks through the idle
    minor direction of the CXL links. ``tiers=None`` is the flat
    single-channel pool with identity placement — the pre-tiered layout
    and billing, bit-for-bit.

    ``device`` commits the pool's buffers (``hbm``, ``host_q``,
    ``host_scale``) to one device, so every paging program and kernel
    runs there; ``None`` leaves them on the default device.
    """

    def __init__(self, n_blocks: int, hbm_blocks: int, block_shape,
                 hints: HintTree | None = None,
                 link: channel_lib.ChannelModel = channel_lib.PCIE_HOST,
                 tiers=None, migrate_max: int = 8, faults=None,
                 device=None):
        if hbm_blocks < 1:
            raise ValueError("need at least one HBM block")
        self.n_blocks = n_blocks
        self.hbm_capacity = hbm_blocks
        self.block_shape = tuple(block_shape)        # (tokens, kv_dims)
        block_bytes = float(np.prod(self.block_shape) * 2)  # bf16
        if tiers is None:
            self.host = TieredHostPool.flat(n_blocks, link, block_bytes)
        else:
            self.host = TieredHostPool.from_spec(n_blocks, tiers,
                                                 block_bytes)
        self.tiered = self.host.tiered
        self.migrate_max = int(migrate_max)
        self.device = device
        self.hbm = jnp.zeros((hbm_blocks,) + self.block_shape, jnp.bfloat16,
                             device=device)
        self.host_q = jnp.zeros((self.host.total_slots,) + self.block_shape,
                                jnp.int8, device=device)
        self.host_scale = jnp.ones((self.host.total_slots,
                                    self.block_shape[0], 1),
                                   jnp.float32, device=device)
        # block table (host-resident residency metadata — never feeds
        # device compute, so it lives in numpy):
        self.slot_of = np.full((n_blocks,), -1, np.int32)    # block -> slot
        self.block_at = np.full((hbm_blocks,), -1, np.int32)  # slot -> block
        self.last_use = np.zeros((n_blocks,), np.int64)      # LRU clock
        self._clock = 0
        self._allocated = np.zeros((n_blocks,), bool)
        # blocks whose HBM copy is newer than host_q (dirty after write(),
        # clean after the eviction that quantizes it out) — evicting a
        # clean or never-written block carries no data and bills nothing.
        self._dirty = np.zeros((n_blocks,), bool)
        # blocks whose host_q copy is real (written by an eviction); a
        # never-evicted block has nothing to page in.
        self._has_host = np.zeros((n_blocks,), bool)
        self.engine = DuplexOffloadEngine(
            link=link, hints=hints or default_serving_hints())
        self.stats = _fresh_stats()
        # fault injection (core.faults.FaultInjector). With no injector
        # attached NONE of the fault machinery exists: no checksum
        # arrays, no per-transaction tick, no extra branches past a
        # single ``is None`` — the disabled layer is zero-cost.
        self._fx = faults
        self._csum_data = self._csum_stamp = None
        self._stamp = 0
        # observability: None/absent until the engine attaches them —
        # same zero-cost-when-disabled contract as the fault layer.
        self._trace = None
        self._trace_prefix = ""
        if faults is not None:
            self.host.attach_faults(faults)
            # per-block host-copy checksums, stamped at page-out and
            # verified at page-in (modelled: a poison bumps _csum_data
            # so the verify mismatches, exactly like a real CRC).
            self._csum_data = np.zeros((n_blocks,), np.int64)
            self._csum_stamp = np.zeros((n_blocks,), np.int64)

    # -- observability -----------------------------------------------------
    def attach_trace(self, tracer, prefix: str = "") -> None:
        """Attach a ``serve.trace.Tracer``: every billed transaction
        (paging, migrations, evacuations, flushes) additionally lays
        per-channel per-direction busy intervals on its modelled clock.
        ``prefix`` namespaces the channel tracks (pool shards)."""
        self._trace = tracer
        self._trace_prefix = prefix
        self.host.attach_trace(tracer, prefix)

    def attach_telemetry(self, registry) -> None:
        """Route CAX scope attribution (``core.telemetry``) into
        ``registry``: the flat planner records through the offload
        engine; the tiered hot path (which skips plan construction)
        attributes its byte volumes directly."""
        self.engine.telemetry = registry

    def _flat_bill_totals(self, read_blocks: int, write_blocks: int,
                          busy_us: float) -> None:
        """Mirror one flat-pool transaction into the single channel's
        per-channel totals so ``tier_stats()`` reports the same shape
        (and real traffic) for both pool flavors. The tiered path does
        this inside ``bill_transaction``."""
        t = self.host.totals[0]
        bb = self.host.block_bytes
        t["page_in_blocks"] += read_blocks
        t["page_out_blocks"] += write_blocks
        t["read_bytes"] += read_blocks * bb
        t["write_bytes"] += write_blocks * bb
        t["busy_us"] += busy_us

    def _flat_trace_txn(self, read_blocks: int, write_blocks: int,
                        duplex_us: float, co_issued: bool,
                        name: str) -> None:
        """Flat-pool twin of the tiered billing's timeline hook: one
        channel, per-direction pure times under the (possibly degraded)
        link model, the transaction's billed time as the advance."""
        link = self.engine.link
        if self._fx is not None:
            factor = self._fx.bandwidth_factor(0)
            if factor < 1.0:
                link = link.degraded(factor)
        bb = self.host.block_bytes
        rd_b, wr_b = read_blocks * bb, write_blocks * bb
        self._trace.channel_transaction(
            [(f"{self._trace_prefix}{self.host.kinds[0]}:0", rd_b, wr_b,
              phase_separated_time_us(link, rd_b, 0.0),
              phase_separated_time_us(link, 0.0, wr_b),
              duplex_us, co_issued)],
            duplex_us, name=name)

    # -- allocation (request lifecycle) ------------------------------------
    def alloc(self, k: int = 1) -> list[int]:
        free = np.flatnonzero(~self._allocated)
        if len(free) < k:
            raise RuntimeError(
                f"KV pool exhausted: {k} blocks requested, "
                f"{len(free)}/{self.n_blocks} free")
        ids = free[:k].tolist()
        self._allocated[ids] = True
        return ids

    def free(self, blocks) -> None:
        """Release logical blocks; drop their residency without writeback."""
        blocks = np.asarray(blocks, np.int32)
        if blocks.size == 0:
            return
        self._allocated[blocks] = False
        self._dirty[blocks] = False
        self._has_host[blocks] = False
        self.host.release(blocks)
        slots = self.slot_of[blocks]
        self.block_at[slots[slots >= 0]] = -1
        self.slot_of[blocks] = -1
        # a reused id must not inherit the old request's recency clock
        self.last_use[blocks] = 0

    def reclaim(self, blocks) -> None:
        """Undo a speculative ``free`` (the engine's pipelined-dispatch
        divergence rollback): re-mark the blocks allocated so ownership
        returns to their request and a later cleanup ``free`` is not a
        double-free. Residency, host copies and recency were dropped by
        the free and are *not* restored — the blocks come back cold,
        exactly like a fresh ``alloc`` — which keeps every block-table
        invariant intact without replaying data movement. Raises if any
        block was re-allocated in the meantime: the rollback replays
        journals newest-op-first, so hitting one means the journal is
        corrupt, not that the caller raced."""
        blocks = np.asarray(blocks, np.int32).reshape(-1)
        if blocks.size == 0:
            return
        taken = blocks[self._allocated[blocks]]
        if taken.size:
            raise RuntimeError(
                f"reclaim of blocks {taken.tolist()} that are already "
                f"allocated — speculative-free journal out of order")
        self._allocated[blocks] = True

    def invalidate(self, blocks) -> None:
        """Declare full-block overwrites: the caller rewrites these blocks
        entirely this step (a batched whole-value SET), so a non-resident
        block's host copy is dead data — it installs fresh instead of
        paging in. There is no read-modify-write to preserve; resident
        blocks are untouched (their overwrite is a plain ``write``)."""
        blocks = np.asarray(blocks, np.int32).reshape(-1)
        if blocks.size == 0:
            return
        nonres = blocks[self.slot_of[blocks] < 0]
        self._has_host[nonres] = False
        self._dirty[nonres] = False
        # the dead host copy's tier slot is reclaimed; the overwrite will
        # re-place the block under whatever scope spills it next.
        self.host.release(nonres)

    # -- residency ---------------------------------------------------------
    def resident_blocks(self) -> np.ndarray:
        return np.flatnonzero(self.slot_of >= 0)

    def is_resident(self, blocks) -> np.ndarray:
        return self.slot_of[np.asarray(blocks, int)] >= 0

    def check_invariants(self) -> None:
        """Raise if the block table is inconsistent (tests call this)."""
        slot_of = self.slot_of
        block_at = self.block_at
        res = np.flatnonzero(slot_of >= 0)
        slots = slot_of[res]
        if len(set(slots.tolist())) != len(slots):
            raise AssertionError("two blocks mapped to one HBM slot")
        if len(res) > self.hbm_capacity:
            raise AssertionError("more resident blocks than HBM slots")
        for b, s in zip(res.tolist(), slots.tolist()):
            if block_at[s] != b:
                raise AssertionError(
                    f"slot map out of sync: slot_of[{b}]={s} but "
                    f"block_at[{s}]={block_at[s]}")
        occupied = np.flatnonzero(block_at >= 0)
        for s in occupied.tolist():
            if slot_of[block_at[s]] != s:
                raise AssertionError(f"dangling slot {s}")
        # host-side placement-map invariants (tiered or identity):
        self.host.check_invariants()
        unplaced = np.flatnonzero(self._has_host
                                  & (self.host.slot_of < 0))
        if unplaced.size:
            raise AssertionError(
                f"blocks {unplaced.tolist()} have a host copy but no "
                f"host-tier slot")

    # -- the per-step batched paging transaction ---------------------------
    def step(self, needed, hint_path: str = "/serve/kv_cache") -> dict:
        """Ensure residency for the whole batch's block demand, in one shot.

        ``needed`` — logical block ids every request in the step reads or
        writes (deduplicated here). Plans all page-ins co-issued with the
        evictions they displace via ``DuplexOffloadEngine`` and executes
        them with a single kernel invocation. Brand-new blocks (no host
        copy yet — about to receive their first ``write``) are installed
        into slots directly: they carry no link traffic and are not billed
        as page-ins. Returns the step's paging counts.
        """
        return self.step_multi([(hint_path, needed)])

    def step_multi(self, groups) -> dict:
        """One paging transaction for a *multi-tenant* step.

        ``groups`` — ``[(hint_path, block_ids), ...]``, one entry per
        hint scope with demand this step (the serving engine merges each
        tenant's blocks under its hint path). Victims are picked jointly
        (no group ever evicts another group's demand) and each group's
        traffic is planned and billed under its own scope:

          * opted-in scopes ride the duplex plan — page-ins co-issued
            with the evictions they displace, one fused kernel pass when
            both directions carry blocks;
          * ``duplex_opt_in=False`` scopes (the paper's withdrawal, e.g.
            the Redis read-heavy pattern) are planned serially and
            executed through the single-direction dequant/quant halves
            only — their traffic never enters a fused duplex call, and
            their billed "duplex" time *is* the serial time (speedup 1).

        Per-scope counters accumulate in ``stats["by_path"]``.
        """
        seen: set[int] = set()
        per_group: list[tuple[str, np.ndarray]] = []
        for path, ids in groups:
            ids = np.asarray(ids, np.int32).reshape(-1)
            uniq = [int(b) for b in dict.fromkeys(ids.tolist())
                    if int(b) not in seen]
            seen.update(uniq)
            per_group.append((path, np.asarray(uniq, np.int32)))
        all_needed = np.asarray(sorted(seen), np.int32)
        if all_needed.size > self.hbm_capacity:
            raise ValueError(
                f"step demands {all_needed.size} blocks but HBM holds "
                f"{self.hbm_capacity}; cap the per-step working set")
        self.stats["steps"] += 1
        report = {"page_ins": 0, "page_outs": 0}
        if self._fx is not None:
            # quarantined blocks lose _has_host and fall through to the
            # fresh-install path below (zero-filled rows): reads stay
            # legal, the data loss is the modelled consequence, and the
            # engine fails the owning LLM request off this report.
            report.update(self._service_faults(all_needed))
        if all_needed.size:
            n_missing = int((self.slot_of[all_needed] < 0).sum())
            free_slots = np.flatnonzero(self.block_at < 0)
            n_evict = max(0, n_missing - free_slots.size)
            victims = self._pick_victims(n_evict, all_needed)
            fcur = vcur = 0
            for path, ids in per_group:
                if ids.size == 0:
                    continue
                missing = ids[self.slot_of[ids] < 0]
                if missing.size == 0:
                    continue
                stale = missing[self._has_host[missing]]   # real page-ins
                fresh = missing[~self._has_host[missing]]  # first installs
                n_free = min(missing.size, free_slots.size - fcur)
                g_free = free_slots[fcur:fcur + n_free]
                fcur += n_free
                n_vict = missing.size - n_free
                g_vict = victims[vcur:vcur + n_vict]
                vcur += n_vict
                r = self._execute(stale, fresh, g_vict, g_free,
                                  hint_path=path)
                report["page_ins"] += r["page_ins"]
                report["page_outs"] += r["page_outs"]
        self._touch(all_needed)
        return report

    # -- fault servicing (one pass per transaction, injector attached) ------
    def _service_faults(self, all_needed: np.ndarray) -> dict:
        """Advance the fault clock and service armed events: corrupt the
        host copies of newly poisoned blocks, hot-unplug newly offline
        channels (placement write-off + emergency evacuation), and
        verify checksums on every host copy this transaction is about to
        page in — mismatches quarantine the host slot and surface in the
        report for the engine to fail the owning request."""
        fx = self._fx
        fx.tick()
        rep = {"poisoned": [], "offline": [], "casualties": [],
               "evacuated": 0}
        for b in fx.drain_poison():
            if 0 <= b < self.n_blocks and self._has_host[b]:
                self._csum_data[b] += 1     # modelled media corruption
            else:
                fx.rearm_poison(b)          # nothing to corrupt yet
        for c in fx.drain_offline():
            if self.identity_host():
                raise RuntimeError(
                    "offline fault on a flat (single-channel) host pool "
                    "— configure tiers to model channel loss")
            self.host.set_offline(c)
            casualties, moved = self._evacuate_channel(c)
            rep["offline"].append(c)
            rep["casualties"].extend(casualties)
            rep["evacuated"] += moved
        if all_needed.size:
            cand = all_needed[(self.slot_of[all_needed] < 0)
                              & self._has_host[all_needed]]
            bad = cand[self._csum_data[cand] != self._csum_stamp[cand]]
            if bad.size:
                hs = self.host.slot_of[bad]
                self.host.quarantine(hs[hs >= 0])
                self._has_host[bad] = False
                self._dirty[bad] = False
                fx.stats["quarantined"] += int(bad.size)
                rep["poisoned"] = bad.tolist()
        return rep

    def identity_host(self) -> bool:
        return self.host.identity

    def _evacuate_channel(self, c: int) -> tuple[list[int], int]:
        """Move a dying channel's live host rows onto surviving channels
        (``TieredHostPool.evacuate`` picks destinations and bills the
        legs); the data copy is the same fixed-width jitted row program
        boundary migrations use. Blocks with no surviving slot lose
        their host copy — the engine fails their owners off the report.
        Returns ``(casualty_blocks, n_moved)``."""
        mig0 = self.host.migrate_us
        blocks, src, dst, casualties = self.host.evacuate(c)
        # the evacuation legs billed on the host channels also land in
        # the pool-level migration clock tier_stats() reports.
        self.stats["migrate_us"] += self.host.migrate_us - mig0
        n = int(blocks.size)
        if n:
            width = 1 << max(0, (n - 1).bit_length())
            s = np.zeros((width,), np.int32)
            d = np.full((width,), self.host.total_slots, np.int32)
            s[:n] = src
            d[:n] = dst
            self.host_q, self.host_scale = _migrate_rows(
                self.host_q, self.host_scale, jnp.asarray(s),
                jnp.asarray(d))
        lost = []
        if casualties:
            ca = np.asarray(casualties, np.int32)
            self._has_host[ca] = False
            # HBM-resident casualties still hold valid data on-device:
            # mark them dirty so the next eviction re-writes a host copy
            # (losing the slot, not the bytes). Non-resident casualties
            # ARE data loss — report them so the engine fails the owner.
            resident = ca[self.slot_of[ca] >= 0]
            gone = ca[self.slot_of[ca] < 0]
            self._dirty[resident] = True
            self._dirty[gone] = False
            lost = [int(b) for b in gone]
        self._fx.stats["evacuated"] += n
        self._fx.stats["recovered"] += n
        return lost, n

    def _pick_victims(self, k: int, keep: np.ndarray) -> np.ndarray:
        """k least-recently-used resident blocks outside ``keep``."""
        if k == 0:
            return np.zeros((0,), np.int32)
        evictable = self.slot_of >= 0
        evictable[keep] = False
        cand = np.flatnonzero(evictable)
        if cand.size < k:
            raise RuntimeError(
                f"need {k} evictions but only {cand.size} evictable blocks")
        order = cand[np.argsort(self.last_use[cand], kind="stable")]
        return order[:k].astype(np.int32)

    def _execute(self, stale: np.ndarray, fresh: np.ndarray,
                 victims: np.ndarray, free_slots: np.ndarray,
                 hint_path: str = "/serve/kv_cache") -> dict:
        """Make ``stale + fresh`` resident, evicting ``victims``.

        Only real data moves: ``stale`` blocks (host copies from earlier
        evictions) and *written* victims travel through the plan + kernel
        pass. ``fresh`` blocks are zero-installed, and victims that never
        received a ``write()`` just drop residency — neither carries
        modelled or billed traffic. When one direction is empty the pass
        is the single-direction dequant-only / quant-only kernel half —
        no zero blocks are streamed through the dead half of the fused
        grid (billing is unchanged: the plan already carries only the
        real transfers).

        ``hint_path`` scopes planning and billing: a scope resolving
        ``duplex_opt_in=False`` gets a *serial* plan (plan_kv_paging's
        withdrawal) and is executed through the single-direction halves
        even when both directions carry blocks — withdrawn traffic never
        rides the fused duplex kernel, and its billed duplex time equals
        its serial time.
        """
        victim_slots = self.slot_of[victims]
        outs = victims[self._dirty[victims]]       # real out traffic
        out_slots = self.slot_of[outs]
        silent_slots = self.slot_of[victims[~self._dirty[victims]]]
        block_bytes = self.host.block_bytes
        in_deq = out_q = out_scale = None
        out_hslots = np.zeros((0,), np.int32)
        if stale.size or outs.size:
            resolved = self.engine.hints.resolve(hint_path).resolved()
            duplex_ok = resolved.duplex_opt_in
            # host-tier placement: departures get (or keep) a host slot
            # under the scope's preferred tier; arrivals refresh their
            # preference (a scope change arms a boundary migration) but
            # evictions do not — the evicting scope may not own the
            # victim (victims are picked jointly across scopes).
            pref = self.host.preferred_kind(resolved)
            in_hslots = self.host.place(stale, pref)
            out_hslots = self.host.place(outs, pref, refresh=False)
            if self.tiered:
                # per-channel billing: each channel's share of the
                # transaction under ITS model (half-duplex DDR5 with
                # turnaround, duplex-overlapped CXL), channels parallel;
                # plus the all-DDR5 serial counterfactual tier_speedup
                # measures against. (The flat pool's transfer-plan
                # construction is skipped: its modelled times would be
                # discarded, and this is the per-transaction hot path.)
                ch_rd, ch_wr, duplex_us, serial_us = \
                    self.host.bill_transaction(in_hslots, out_hslots,
                                               co_issued=bool(duplex_ok))
                self.stats["tier_us"] += duplex_us
                self.stats["ddr5_us"] += self.host.ddr5_baseline_us(
                    ch_rd, ch_wr)
                if self.engine.telemetry is not None:
                    # the tiered path skips plan construction, so the
                    # CAX scope attribution the flat planner does in
                    # ``plan_kv_paging`` happens here instead.
                    self.engine.telemetry.attribute(
                        hint_path,
                        read_bytes=float(stale.size) * block_bytes,
                        write_bytes=float(outs.size) * block_bytes)
            else:
                plan = self.engine.plan_kv_paging(
                    needed_host_blocks=stale.tolist(),
                    evict_hbm_blocks=out_slots.tolist(),
                    free_hbm_blocks=np.concatenate(
                        [free_slots, silent_slots]).tolist(),
                    host_dst_blocks=outs.tolist(),
                    block_bytes=block_bytes,
                    hint_path=hint_path)
                serial = plan_serial(
                    [s.page_in for s in plan.slots if s.page_in],
                    [s.page_out for s in plan.slots if s.page_out],
                    self.engine.link)
                duplex_us = plan.modelled_time_us()
                serial_us = serial.modelled_time_us()
                if self._fx is not None:
                    # flat pool = one channel (index 0): a degrade window
                    # scales both modelled times inversely (pure
                    # bandwidth scaling) and transient retries bill their
                    # failed attempts + backoff into both views.
                    factor = self._fx.bandwidth_factor(0)
                    if factor < 1.0:
                        duplex_us /= factor
                        serial_us /= factor
                    extra = self._fx.retry_penalty_us(0, duplex_us)
                    duplex_us += extra
                    serial_us += extra
                self._flat_bill_totals(int(stale.size), int(outs.size),
                                       duplex_us)
                if self._trace is not None:
                    self._flat_trace_txn(int(stale.size), int(outs.size),
                                         duplex_us, duplex_ok, "paging")
            bp = self.stats["by_path"].setdefault(hint_path,
                                                  _fresh_path_stats())
            for st, key, val in (
                    (self.stats, "duplex_us", duplex_us),
                    (self.stats, "serial_us", serial_us),
                    (self.stats, "page_ins", int(stale.size)),
                    (self.stats, "page_outs", int(outs.size)),
                    (bp, "duplex_us", duplex_us),
                    (bp, "serial_us", serial_us),
                    (bp, "page_ins", int(stale.size)),
                    (bp, "page_outs", int(outs.size))):
                st[key] += val

            # ONE kernel pass per direction pair over this scope's real
            # traffic (fused when opted in and both directions are busy).
            if stale.size and outs.size and duplex_ok:
                # both directions busy: the fused duplex kernel, streams
                # padded to a uniform grid.
                in_q, in_scale, out_x = _gather_duplex(
                    self.host_q, self.host_scale, self.hbm,
                    jnp.asarray(in_hslots), jnp.asarray(out_slots))
                in_deq, out_q, out_scale = kernel_ops.duplex_kv_stream(
                    in_q, in_scale, out_x, stage_blocks=STAGE_BLOCKS)
                self.stats["kernel_calls"] += 1
                bp["fused_calls"] += 1
            else:
                # single-direction halves: exactly the real blocks per
                # direction, never the fused grid (withdrawn scopes take
                # this path even with both directions busy).
                if outs.size:
                    out_q, out_scale = kernel_ops.quant_kv_stream(
                        self.hbm[jnp.asarray(out_slots)])
                    self.stats["kernel_calls"] += 1
                if stale.size:
                    in_q, in_scale = _gather_in(
                        self.host_q, self.host_scale,
                        jnp.asarray(in_hslots))
                    in_deq = kernel_ops.dequant_kv_stream(in_q, in_scale)
                    self.stats["kernel_calls"] += 1

        if victims.size:
            self.block_at[victim_slots] = -1
            self.slot_of[victims] = -1

        # stale blocks take the leading dst slots (they consume in_deq);
        # fresh blocks zero-fill the rest pending their first write.
        missing = np.concatenate([stale, fresh]).astype(np.int32)
        dst = np.concatenate([free_slots, victim_slots])[:missing.size]
        dst = dst.astype(np.int32)
        self.hbm, self.host_q, self.host_scale = _commit_paging(
            self.hbm, self.host_q, self.host_scale, in_deq, out_q,
            out_scale, jnp.asarray(out_hslots),
            jnp.asarray(dst[:stale.size]),
            jnp.asarray(dst[stale.size:]))
        if outs.size:
            self._has_host[outs] = True
            self._dirty[outs] = False   # host copy now matches
            if self._fx is not None:
                # stamp the page-out checksum; verified at page-in.
                self._stamp += 1
                self._csum_data[outs] = self._stamp
                self._csum_stamp[outs] = self._stamp
        self.slot_of[missing] = dst
        self.block_at[dst] = missing
        return {"page_ins": int(stale.size), "page_outs": int(outs.size)}

    def _touch(self, blocks: np.ndarray) -> None:
        self._clock += 1
        self.last_use[blocks] = self._clock

    # -- batched data plane ------------------------------------------------
    def write(self, blocks, data: jnp.ndarray) -> None:
        """Write-through freshly produced blocks (must be resident).

        ``blocks``: (n,) logical ids; ``data``: (n, tokens, kv_dims).
        Ids outside [0, n_blocks) are fixed-width padding sentinels: their
        rows are dropped by the scatter, so callers can keep a static
        update shape across steps (no retrace per block count).
        """
        dst, real = self._write_dst(blocks)
        if dst is None:
            return
        self.hbm = _write_blocks(self.hbm, jnp.asarray(dst), data)
        self._dirty[real] = True
        self._touch(real)

    def write_staged(self, blocks, staged: jnp.ndarray, step: int) -> None:
        """Write-through one megastep inner step's freshly filled blocks
        straight from the (K, W, tokens, kv_dims) staging stack the
        fused megastep program emitted (see ``serve.engine``). The slab
        for ``step`` is selected on device — the staging stack is the
        double buffer between the megastep's compute scan and the K
        paging transactions, and it never touches the host. Ids follow
        ``write``'s sentinel-padding contract (out-of-range rows drop).
        """
        dst, real = self._write_dst(blocks)
        if dst is None:
            return
        self.hbm = _write_blocks_at(self.hbm, jnp.asarray(dst), staged,
                                    np.int32(step))
        self._dirty[real] = True
        self._touch(real)

    def _write_dst(self, blocks) -> tuple[np.ndarray | None, np.ndarray]:
        """Shared write-through validation: map logical ids to HBM slot
        destinations, sentinel-padding invalid rows."""
        blocks = np.asarray(blocks, np.int32)
        if blocks.size == 0:
            return None, blocks
        valid = (blocks >= 0) & (blocks < self.n_blocks)
        real = blocks[valid]
        if real.size == 0:
            return None, real
        slots = self.slot_of[real]
        if (slots < 0).any():
            raise ValueError("write to non-resident block; call step() first")
        dst = np.full(blocks.shape, self.hbm_capacity, np.int32)  # OOB pad
        dst[valid] = slots
        return dst, real

    def read(self, blocks) -> jnp.ndarray:
        """Gather resident blocks: (n, tokens, kv_dims) bf16."""
        blocks = np.asarray(blocks, np.int32)
        slots = self.slot_of[blocks]
        if (slots < 0).any():
            raise ValueError("read of non-resident block; call step() first")
        self._touch(blocks)
        return self.hbm[jnp.asarray(slots)]

    # -- host-tier migrations (megastep boundaries) -------------------------
    def migrate_tiers(self, max_moves: int | None = None) -> dict:
        """Rebalance host-tier placement at a megastep boundary.

        Planning is pure host metadata (the hotness clock ``last_use``,
        the placement map, the boundary window's per-channel traffic);
        execution is ONE fixed-width jitted row copy — dispatch-only, so
        a megastep with migrations still performs zero extra host syncs.
        CXL legs ride each link's idle minor direction (budgeted from
        the window the plan just closed); the half-duplex legs' modelled
        time lands in ``stats["migrate_us"]``. Data is moved verbatim
        (quantized rows + scales), so served results are bit-exact
        whether or not migrations run.
        """
        if not self.tiered:
            return {"migrations": 0}
        width = self.migrate_max if max_moves is None \
            else min(int(max_moves), self.migrate_max)
        plan = self.host.plan_migrations(self.last_use, self._has_host,
                                         width)
        if len(plan):
            src = np.zeros((self.migrate_max,), np.int32)
            dst = np.full((self.migrate_max,), self.host.total_slots,
                          np.int32)
            src[:len(plan)] = plan.src_slots
            dst[:len(plan)] = plan.dst_slots
            try:
                self.host_q, self.host_scale = _migrate_rows(
                    self.host_q, self.host_scale, jnp.asarray(src),
                    jnp.asarray(dst))
            except Exception:
                # the plan reserved its destination slots; hand them back
                # so a failed dispatch cannot leak host-tier capacity.
                self.host.abandon(plan)
                raise
        self.host.apply(plan)   # also closes the traffic window
        self.stats["migrations"] += len(plan)
        self.stats["migrate_us"] += plan.migrate_us
        if len(plan) and self.engine.telemetry is not None:
            bb = self.host.block_bytes
            self.engine.telemetry.attribute(
                "/serve/tier_migrate", read_bytes=len(plan) * bb,
                write_bytes=len(plan) * bb)
        return {"migrations": len(plan)}

    # -- snapshot/restore ---------------------------------------------------
    def flush_dirty(self, hint_path: str = "/serve/kv_cache") -> dict:
        """Page out every dirty resident block through the billed path,
        keeping residency — the durability barrier a snapshot cut takes
        so its host tier holds a copy of ALL live KV state.

        This is exactly ``_execute``'s departure leg with no arrivals:
        blocks get (or keep) a host-tier slot under the scope's
        preferred kind, the write traffic is billed per channel
        (``co_issued=False`` — there is no read stream to pair against,
        so snapshot bandwidth is honestly phase-separated, never free),
        the data moves through the real ``quant_kv_stream`` kernel, and
        checksums are stamped. The blocks stay resident AND become
        clean, so the bf16 HBM rows captured right after a flush are
        durable-equivalent: loss on crash is only what was written
        after the cut.
        """
        outs = np.flatnonzero(self._dirty
                              & (self.slot_of >= 0)).astype(np.int32)
        if outs.size == 0:
            return {"page_outs": 0, "flush_us": 0.0}
        out_slots = self.slot_of[outs]
        resolved = self.engine.hints.resolve(hint_path).resolved()
        pref = self.host.preferred_kind(resolved)
        out_hslots = self.host.place(outs, pref, refresh=False)
        if self.tiered:
            ch_rd, ch_wr, duplex_us, serial_us = \
                self.host.bill_transaction(np.zeros((0,), np.int32),
                                           out_hslots, co_issued=False)
            self.stats["tier_us"] += duplex_us
            self.stats["ddr5_us"] += self.host.ddr5_baseline_us(
                ch_rd, ch_wr)
            if self.engine.telemetry is not None:
                self.engine.telemetry.attribute(
                    hint_path, read_bytes=0.0,
                    write_bytes=float(outs.size) * self.host.block_bytes)
        else:
            plan = self.engine.plan_kv_paging(
                needed_host_blocks=[],
                evict_hbm_blocks=out_slots.tolist(),
                free_hbm_blocks=[],
                host_dst_blocks=outs.tolist(),
                block_bytes=self.host.block_bytes,
                hint_path=hint_path)
            serial = plan_serial(
                [], [s.page_out for s in plan.slots if s.page_out],
                self.engine.link)
            duplex_us = plan.modelled_time_us()
            serial_us = serial.modelled_time_us()
            if self._fx is not None:
                factor = self._fx.bandwidth_factor(0)
                if factor < 1.0:
                    duplex_us /= factor
                    serial_us /= factor
                extra = self._fx.retry_penalty_us(0, duplex_us)
                duplex_us += extra
                serial_us += extra
            self._flat_bill_totals(0, int(outs.size), duplex_us)
            if self._trace is not None:
                self._flat_trace_txn(0, int(outs.size), duplex_us,
                                     False, "flush")
        bp = self.stats["by_path"].setdefault(hint_path,
                                              _fresh_path_stats())
        for st in (self.stats, bp):
            st["duplex_us"] += duplex_us
            st["serial_us"] += serial_us
            st["page_outs"] += int(outs.size)
        out_q, out_scale = kernel_ops.quant_kv_stream(
            self.hbm[jnp.asarray(out_slots)])
        self.stats["kernel_calls"] += 1
        empty = jnp.zeros((0,), jnp.int32)
        self.hbm, self.host_q, self.host_scale = _commit_paging(
            self.hbm, self.host_q, self.host_scale, None, out_q,
            out_scale, jnp.asarray(out_hslots), empty, empty)
        self._has_host[outs] = True
        self._dirty[outs] = False
        if self._fx is not None:
            self._stamp += 1
            self._csum_data[outs] = self._stamp
            self._csum_stamp[outs] = self._stamp
        return {"page_outs": int(outs.size), "flush_us": duplex_us}

    def snapshot_state(self) -> dict:
        """Every mutable field as checkpoint-ready host values: the raw
        bf16 HBM rows (restoring from the int8 host copies would be
        ``dequant(quant(x))`` — lossy — and break bit-exact resume), the
        quantized host tier, the block table, and the accounting. The
        fault injector's own state is engine-level (sharded pools share
        one injector) and is not captured here; the per-block checksum
        arrays ARE pool state and ride along when attached."""
        state = {
            "hbm": np.asarray(self.hbm),
            "host_q": np.asarray(self.host_q),
            "host_scale": np.asarray(self.host_scale),
            "slot_of": self.slot_of.copy(),
            "block_at": self.block_at.copy(),
            "last_use": self.last_use.copy(),
            "allocated": self._allocated.copy(),
            "dirty": self._dirty.copy(),
            "has_host": self._has_host.copy(),
            "host": self.host.snapshot_state(),
            "meta": {
                "clock": self._clock,
                "stamp": self._stamp,
                "stats": {k: ({p: dict(v) for p, v in val.items()}
                              if k == "by_path" else val)
                          for k, val in self.stats.items()},
            },
        }
        if self._fx is not None:
            state["csum_data"] = self._csum_data.copy()
            state["csum_stamp"] = self._csum_stamp.copy()
        return state

    def load_state(self, state: dict) -> None:
        """Inverse of ``snapshot_state`` onto a pool built with the same
        config (shapes/tiers/faults come from construction)."""
        hbm = np.asarray(state["hbm"])
        if hbm.shape != (self.hbm_capacity,) + self.block_shape:
            raise ValueError(
                f"pool snapshot HBM shape {hbm.shape} does not match "
                f"this pool ({(self.hbm_capacity,) + self.block_shape})"
                " — restore needs the crashed run's pool config")
        self.hbm = jnp.asarray(hbm, jnp.bfloat16, device=self.device)
        self.host_q = jnp.asarray(state["host_q"], jnp.int8,
                                  device=self.device)
        self.host_scale = jnp.asarray(state["host_scale"], jnp.float32,
                                      device=self.device)
        self.slot_of = np.asarray(state["slot_of"], np.int32).copy()
        self.block_at = np.asarray(state["block_at"], np.int32).copy()
        self.last_use = np.asarray(state["last_use"], np.int64).copy()
        self._allocated = np.asarray(state["allocated"], bool).copy()
        self._dirty = np.asarray(state["dirty"], bool).copy()
        self._has_host = np.asarray(state["has_host"], bool).copy()
        self.host.load_state(state["host"])
        meta = state["meta"]
        self._clock = int(meta["clock"])
        self._stamp = int(meta["stamp"])
        self.stats = {k: ({p: dict(v) for p, v in val.items()}
                          if k == "by_path" else val)
                      for k, val in meta["stats"].items()}
        if self._fx is not None:
            self._csum_data = np.asarray(state["csum_data"],
                                         np.int64).copy()
            self._csum_stamp = np.asarray(state["csum_stamp"],
                                          np.int64).copy()

    # -- reporting ---------------------------------------------------------
    def tier_speedup(self) -> float:
        """Modelled all-DDR5-serial vs tiered link-time ratio for the
        pool's real paging traffic (1.0 for a flat pool — there is no
        counterfactual to beat)."""
        if self.stats["tier_us"] == 0:
            return 1.0
        return self.stats["ddr5_us"] / self.stats["tier_us"]

    def tier_stats(self) -> dict:
        """Per-channel placement/traffic/migration accounting plus the
        tier A/B summary. Flat pools emit the SAME keys (their single
        channel, zeroed tier fields) so consumers never key-guard on
        the pool flavor — the unified schema in ``core.metrics``."""
        return {"tiered": self.tiered,
                "channels": self.host.stats(),
                "migrations": self.stats["migrations"],
                "migrate_us": round(self.stats["migrate_us"], 3),
                "tier_us": round(self.stats["tier_us"], 3),
                "ddr5_us": round(self.stats["ddr5_us"], 3),
                "tier_speedup": round(self.tier_speedup(), 4)}

    def duplex_speedup(self, hint_path: str | None = None) -> float:
        """Modelled serial/duplex link-time ratio — overall, or for one
        hint scope's traffic (``stats["by_path"]``). Withdrawn scopes
        report exactly 1.0: their duplex time *is* the serial time."""
        st = (self.stats if hint_path is None
              else self.stats["by_path"].get(hint_path, _fresh_path_stats()))
        if st["duplex_us"] == 0:
            return 1.0
        return st["serial_us"] / st["duplex_us"]

    def reset_stats(self) -> None:
        self.stats = _fresh_stats()
        self.host.reset_stats()
