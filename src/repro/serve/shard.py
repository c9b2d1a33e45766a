"""Sharded multi-device serving over a ``data × model`` mesh.

``ShardedServeEngine`` runs the same continuous-batching loop as
``ServeEngine`` with the fused megastep program wrapped in ``shard_map``
over a ``launch.mesh.make_debug_mesh``-style mesh:

* **data axis** — batch rows are sharded: each data rank owns
  ``max_batch / data`` slots, computes only its rows' micro-steps, and
  holds its *own* ``PagedKVPool`` shard (block table, host placement
  map, tier channels). Every row's megastep arithmetic is per-slot
  independent, so batch sharding is bit-exact with the single-device
  engine — the differential lane in ``tests/test_shard_serve.py`` proves
  it token-for-token.
* **model axis** — ranks execute the decode replicated (bitwise
  identical math on identical inputs, so exactness is by construction)
  while the tensor-parallel collective traffic the
  ``launch.sharding`` PartitionSpec rules imply (one psum after the
  row-parallel attention output and MLP down projections per layer) is
  *modelled* and billed through the ``ici`` channel kind registered in
  ``core.channel`` — the repo's channel-model doctrine (functional
  execution real, link timing modelled) extended to the interconnect.
  One real collective does run per megastep: a ``lax.pmax`` over the
  packed readback, a bitwise no-op on replicas that moves real
  cross-device bytes and pins the model-axis replication.

Slot ownership is the routing key for everything host-side: request
``r``'s KV blocks come from the pool shard owning ``r.slot``, block ids
live in a global namespace (``global = shard * blocks_per_shard +
local``), and migrations / fault evacuation never cross a shard
boundary — each shard's tier channels fail and evacuate alone, exactly
like a real per-device CXL expander set.

Cross-device traffic accounting (``IciMeter``) lands in
``paging_stats()["ici"]`` and ``paging_stats()["by_path"]`` under
``/serve/ici/data`` and ``/serve/ici/model``, with the same
``channel_time_us`` duplex-vs-serial arithmetic the DDR5/CXL host
channels use — per-link accounting composes at scale only if every
link flows through the same model.

Each data rank's pool shard lives on that rank's device (the first
device of its row in the mesh), next to the cache rows it mirrors: the
rank's band of the staged write-through slab is already there, so
write-through moves no bytes between devices. The sync budget is
unchanged: ONE packed readback per megastep *per mesh* (not per device)
— ``np.asarray`` on the mesh-sharded packed array is the single
deferred device->host sync.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import channel as channel_lib
from repro.core import offload
from repro.core.hints import HintTree
from repro.serve.engine import ServeEngine, _megastep_math
from repro.serve.kv_pool import PagedKVPool
from repro.serve.queue import Request, S_DONE, S_PREFILL

@functools.lru_cache(maxsize=64)
def _sharded_megastep_program(api, n_micro: int, n_steps: int,
                              block_tokens: int | None, mesh):
    """The megastep program sharded over ``mesh``: ``_megastep_math``
    wrapped in ``shard_map`` (batch rows split over ``data``, compute
    replicated over ``model``) and jitted with the same buffer-donation
    contract as the single-device cell. Cached per (ModelAPI,
    prefill_chunk, K, block_tokens, mesh) — engines sharing a cell share
    one compiled program, exactly like ``_fused_megastep_program``.

    The packed readback is reduced with ``lax.pmax`` over the model
    axis: bitwise identity on replicated int32 rows, but a *real*
    cross-device collective — the model ranks' answers physically meet
    on the wire, so a desynced replica would surface as a readback
    divergence instead of silent disagreement.
    """
    mega = _megastep_math(api, n_micro, n_steps, block_tokens)
    extract = block_tokens is not None

    def sharded(params, cache, dev):
        out = mega(params, cache, dev)
        if extract:
            cache2, dev2, packed, staged = out
            return cache2, dev2, lax.pmax(packed, "model"), staged
        cache2, dev2, packed = out
        return cache2, dev2, lax.pmax(packed, "model")

    cache_spec = P(None, "data")          # every cache leaf is (L, B, ...)
    dev_spec = P("data")                  # every dev leaf is (B, ...)
    out_specs = ((cache_spec, dev_spec, P("data"), P(None, "data"))
                 if extract else (cache_spec, dev_spec, P("data")))
    # replication checking is off: the model-axis compute is replicated
    # by construction (identical math on identical inputs), but the
    # checker cannot track that through the engine's scan/cond structure
    # for arbitrary ``decode_step`` bodies — the differential test lane
    # is the guarantee instead.
    fn = jax.shard_map(sharded, mesh=mesh,
                       in_specs=(P(), cache_spec, dev_spec),
                       out_specs=out_specs, check_vma=False)
    return jax.jit(fn, donate_argnums=(1, 2))


# ---------------------------------------------------------------------------
# ICI billing — cross-device collectives through the core.channel model
# ---------------------------------------------------------------------------

def _fresh_ici_path_stats() -> dict:
    return {"bytes": 0.0, "collectives": 0,
            "duplex_us": 0.0, "serial_us": 0.0}


class IciMeter:
    """Bill modelled cross-device collective traffic per mesh axis.

    Each axis is one ``ici`` link set (``core.channel.
    INTERCONNECT_PRESETS``); volumes use the standard ring-collective
    wire formulas (all-reduce moves ``2(m-1)/m`` of the payload per
    device, an all-gather ``(m-1)/m`` of the gathered result). Billed
    time uses the same ``offload.channel_time_us`` duplex-vs-serial
    arithmetic as every other channel in the repo, so
    ``by_path["/serve/ici/*"]`` composes with the DDR5/CXL entries.
    """

    def __init__(self, mesh, link: channel_lib.ChannelModel | None = None):
        self.link = link or channel_lib.INTERCONNECT_PRESETS["ici"]
        self.axis_size = {str(a): int(mesh.shape[a])
                          for a in mesh.axis_names}
        self.by_path: dict[str, dict] = {}
        # observability: the sharded engine attaches its Tracer (ICI
        # busy intervals land on the same modelled clock as the
        # DDR5/CXL tracks) and CaxRegistry; None = disabled/zero-cost.
        self.trace = None
        self.telemetry = None

    def _bill(self, axis: str, read_bytes: float, write_bytes: float
              ) -> None:
        st = self.by_path.setdefault(f"/serve/ici/{axis}",
                                     _fresh_ici_path_stats())
        duplex_us = offload.channel_time_us(
            self.link, read_bytes, write_bytes)
        st["bytes"] += read_bytes + write_bytes
        st["collectives"] += 1
        st["duplex_us"] += duplex_us
        st["serial_us"] += offload.phase_separated_time_us(
            self.link, read_bytes, write_bytes)
        if self.trace is not None:
            self.trace.channel_transaction(
                [(f"ici:{axis}", read_bytes, write_bytes,
                  offload.phase_separated_time_us(
                      self.link, read_bytes, 0.0),
                  offload.phase_separated_time_us(
                      self.link, 0.0, write_bytes),
                  duplex_us, True)],
                duplex_us, name="collective")
        if self.telemetry is not None:
            self.telemetry.attribute(
                f"/serve/ici/{axis}",
                collective_bytes=read_bytes + write_bytes)

    def note_allreduce(self, axis: str, payload_bytes: float) -> None:
        """Ring all-reduce of ``payload_bytes`` per device over ``axis``:
        every device both sends and receives ``2(m-1)/m`` of the payload
        — full-duplex traffic, the regime the ICI link's independent
        SerDes exist for."""
        m = self.axis_size.get(axis, 1)
        if m <= 1 or payload_bytes <= 0:
            return
        wire = 2.0 * (m - 1) / m * payload_bytes
        self._bill(axis, wire, wire)

    def note_allgather(self, axis: str, shard_bytes: float) -> None:
        """Ring all-gather of one ``shard_bytes`` contribution per device
        over ``axis``: each device forwards ``(m-1)`` shards — read-heavy
        single-direction traffic."""
        m = self.axis_size.get(axis, 1)
        if m <= 1 or shard_bytes <= 0:
            return
        self._bill(axis, (m - 1) * shard_bytes, 0.0)

    def summary(self) -> dict:
        tot = _fresh_ici_path_stats()
        for st in self.by_path.values():
            for k in tot:
                tot[k] += st[k]
        tot["collectives"] = int(tot["collectives"])
        tot["links"] = dict(self.axis_size)
        return tot

    def reset(self) -> None:
        self.by_path = {}

    def snapshot_state(self) -> dict:
        return {p: dict(st) for p, st in self.by_path.items()}

    def load_state(self, state: dict) -> None:
        self.by_path = {p: dict(st) for p, st in state.items()}


# ---------------------------------------------------------------------------
# Per-shard fault routing
# ---------------------------------------------------------------------------

class ShardFaultView:
    """One pool shard's view of the shared ``FaultInjector``.

    The facade advances the fault clock ONCE per paging transaction and
    pre-routes drained events; each shard's ``PagedKVPool`` then sees an
    injector-shaped object whose ``tick`` is a no-op, whose poison
    queue holds only the blocks that shard owns (translated to local
    ids), and whose offline list names the tier channels every shard
    loses in common (channel ``c`` dies on every device's expander set
    — evacuation itself stays shard-local). Degradation factors, retry
    penalties and the stats dict delegate to the master injector, so
    counters stay global and the seeded retry stream stays one stream.
    """

    def __init__(self, master, shard: int, blocks_per_shard: int):
        self._master = master
        self._shard = shard
        self._per = blocks_per_shard
        self._poison: list[int] = []     # local ids, pre-routed
        self._offline: list[int] = []    # channel ids, shared

    # routed by the facade, once per transaction
    def push_poison(self, local_block: int) -> None:
        self._poison.append(local_block)

    def push_offline(self, channel: int) -> None:
        self._offline.append(channel)

    # injector surface the shard pool consumes
    def tick(self) -> None:
        pass                             # the facade already ticked

    def drain_poison(self) -> list[int]:
        out, self._poison = self._poison, []
        return out

    def drain_offline(self) -> list[int]:
        out, self._offline = self._offline, []
        return out

    def rearm_poison(self, block: int) -> None:
        # nothing to corrupt on this shard yet: back onto the master
        # queue in GLOBAL ids so a later transaction re-routes it.
        self._master.rearm_poison(self._shard * self._per + int(block))

    def bandwidth_factor(self, c: int) -> float:
        return self._master.bandwidth_factor(c)

    def retry_penalty_us(self, c: int, attempt_us: float) -> float:
        return self._master.retry_penalty_us(c, attempt_us)

    def is_offline(self, c: int) -> bool:
        return self._master.is_offline(c)

    @property
    def stats(self) -> dict:
        return self._master.stats


# ---------------------------------------------------------------------------
# The sharded pool facade
# ---------------------------------------------------------------------------

class _ShardedHostView:
    """The engine-facing slice of the per-shard ``TieredHostPool``s:
    capacity questions answered over the whole mesh (any shard degraded
    degrades the deployment; surviving capacity is the sum of surviving
    per-shard slots)."""

    def __init__(self, shards):
        self._shards = shards

    @property
    def capacity_degraded(self) -> bool:
        return any(sh.host.capacity_degraded for sh in self._shards)

    def live_capacity(self) -> int:
        return sum(sh.host.live_capacity() for sh in self._shards)


class ShardedKVPool:
    """``n_shards`` independent ``PagedKVPool``s behind one pool
    interface, in a global block-id namespace.

    Each shard is configured exactly like the single-device engine's
    pool (same ``n_blocks``, same ``hbm_blocks``, its own tier
    channels), so the engine's admission/budget arithmetic — which reads
    ``hbm_capacity`` as *per-slot-set* headroom — is byte-identical to
    the single-device schedule; scale-out multiplies capacity with the
    batch instead of splitting it. Block id ``g`` belongs to shard
    ``g // n_blocks_per_shard`` as local id ``g % n_blocks_per_shard``;
    every mutator routes by that rule, so migrations, victim picks and
    fault evacuation are shard-local by construction.

    Non-LLM tenants pin to shard 0 (their ``alloc`` default): shard 0's
    global ids coincide with its local ids, so the tenant-facing
    ``slot_of``/``hbm`` views stay valid unchanged.

    ``devices`` (one per shard) places shard ``s``'s buffers on
    ``devices[s]``; ``None`` keeps every shard on the default device.
    """

    def __init__(self, n_shards: int, n_blocks: int, hbm_blocks: int,
                 block_shape, hints: HintTree | None = None,
                 tiers=None, migrate_max: int = 8, faults=None,
                 devices=None):
        if n_shards < 1:
            raise ValueError("need at least one pool shard")
        if devices is None:
            devices = [None] * n_shards
        self.n_shards = n_shards
        self.blocks_per_shard = n_blocks
        self.n_blocks = n_shards * n_blocks          # global id space
        self.hbm_capacity = hbm_blocks               # per shard (see above)
        self.block_shape = tuple(block_shape)
        self._fx = faults
        self._views = []
        shard_faults: list = [None] * n_shards
        if faults is not None:
            self._views = [ShardFaultView(faults, s, n_blocks)
                           for s in range(n_shards)]
            shard_faults = self._views
        self.shards = [
            PagedKVPool(n_blocks, hbm_blocks, block_shape, hints=hints,
                        tiers=tiers, migrate_max=migrate_max,
                        faults=shard_faults[s], device=devices[s])
            for s in range(n_shards)]
        self.host = _ShardedHostView(self.shards)
        self.tiered = self.shards[0].tiered
        self._steps = 0                              # facade transactions

    # -- observability -------------------------------------------------------
    def attach_trace(self, tracer, prefix: str = "") -> None:
        """Fan the tracer out to every shard pool, namespacing each
        shard's channel tracks (``shard0/ddr5:0`` ...) on the one
        shared modelled clock."""
        for s, sh in enumerate(self.shards):
            sh.attach_trace(tracer, prefix=f"{prefix}shard{s}/")

    def attach_telemetry(self, registry) -> None:
        for sh in self.shards:
            sh.attach_telemetry(registry)

    # -- id routing ---------------------------------------------------------
    def shard_of(self, block: int) -> int:
        return int(block) // self.blocks_per_shard

    def _split(self, blocks) -> list[np.ndarray]:
        """Group global ids per owning shard, order-preserving, local."""
        blocks = np.asarray(blocks, np.int32).reshape(-1)
        out = []
        for s in range(self.n_shards):
            lo = s * self.blocks_per_shard
            sel = blocks[(blocks >= lo)
                         & (blocks < lo + self.blocks_per_shard)]
            out.append(sel - lo)
        return out

    # -- allocation (request lifecycle) ------------------------------------
    def alloc(self, k: int = 1, shard: int = 0) -> list[int]:
        lo = shard * self.blocks_per_shard
        return [lo + b for b in self.shards[shard].alloc(k)]

    def free(self, blocks) -> None:
        for s, ids in enumerate(self._split(blocks)):
            if ids.size:
                self.shards[s].free(ids)

    def reclaim(self, blocks) -> None:
        for s, ids in enumerate(self._split(blocks)):
            if ids.size:
                self.shards[s].reclaim(ids)

    def invalidate(self, blocks) -> None:
        for s, ids in enumerate(self._split(blocks)):
            if ids.size:
                self.shards[s].invalidate(ids)

    def resident_blocks(self) -> np.ndarray:
        return np.concatenate(
            [sh.resident_blocks() + s * self.blocks_per_shard
             for s, sh in enumerate(self.shards)])

    # -- the per-transaction paging step ------------------------------------
    def step(self, needed, hint_path: str = "/serve/kv_cache") -> dict:
        return self.step_multi([(hint_path, needed)])

    def step_multi(self, groups) -> dict:
        """One mesh-wide paging transaction: the fault clock ticks ONCE,
        drained events are routed to their owning shard (poison by block
        range, offline channels to every shard — each evacuates its own
        channel locally), then each shard with demand or pending events
        runs its own ``PagedKVPool.step_multi``. Reports come back in
        global ids."""
        self._steps += 1
        touched = set()
        if self._fx is not None:
            self._fx.tick()
            for b in self._fx.drain_poison():
                if 0 <= b < self.n_blocks:
                    s = self.shard_of(b)
                    self._views[s].push_poison(
                        b - s * self.blocks_per_shard)
                    touched.add(s)
                else:
                    # nothing to corrupt anywhere, ever: keep the
                    # single-pool "re-arm until it lands" semantics.
                    self._fx.rearm_poison(b)
            for c in self._fx.drain_offline():
                for s, v in enumerate(self._views):
                    v.push_offline(c)
                    touched.add(s)

        per_shard: list[list[tuple[str, np.ndarray]]] = [
            [] for _ in range(self.n_shards)]
        for path, ids in groups:
            for s, local in enumerate(self._split(ids)):
                if local.size:
                    per_shard[s].append((path, local))
                    touched.add(s)

        report = {"page_ins": 0, "page_outs": 0}
        if self._fx is not None:
            report.update({"poisoned": [], "offline": [],
                           "casualties": [], "evacuated": 0})
        for s in sorted(touched):
            rep = self.shards[s].step_multi(per_shard[s])
            report["page_ins"] += rep["page_ins"]
            report["page_outs"] += rep["page_outs"]
            if self._fx is not None:
                lo = s * self.blocks_per_shard
                report["poisoned"].extend(
                    lo + b for b in rep.get("poisoned", ()))
                report["casualties"].extend(
                    lo + b for b in rep.get("casualties", ()))
                for c in rep.get("offline", ()):
                    if c not in report["offline"]:
                        report["offline"].append(c)
                report["evacuated"] += rep.get("evacuated", 0)
        return report

    # -- batched data plane --------------------------------------------------
    def _localize_write_ids(self, blocks: np.ndarray, s: int) -> np.ndarray:
        """Global ids -> shard-local for the write scatter; everything
        the shard does not own (the facade-level sentinel pad, foreign
        rows) becomes the shard's own out-of-range sentinel."""
        lo = s * self.blocks_per_shard
        mine = (blocks >= lo) & (blocks < lo + self.blocks_per_shard)
        out = np.full(blocks.shape, self.blocks_per_shard, np.int32)
        out[mine] = blocks[mine] - lo
        return out

    def write(self, blocks, data) -> None:
        blocks = np.asarray(blocks, np.int32).reshape(-1)
        for s, sh in enumerate(self.shards):
            ids = self._localize_write_ids(blocks, s)
            if (ids < self.blocks_per_shard).any():
                sh.write(ids, data)

    def write_staged(self, blocks, staged, step: int) -> None:
        """Write-through by slot ownership: ids are slot-major
        (``slot * max_fills + j``) over the global batch, so shard ``s``
        owns the contiguous row band of its slots, and ``staged[s]`` is
        that band of the megastep staging slab (K, rows, tokens,
        kv_dims), already on shard ``s``'s device."""
        blocks = np.asarray(blocks, np.int32).reshape(-1)
        rows = blocks.size // self.n_shards
        for s, sh in enumerate(self.shards):
            band = blocks[s * rows:(s + 1) * rows]
            ids = self._localize_write_ids(band, s)
            if (ids < self.blocks_per_shard).any():
                sh.write_staged(ids, staged[s], step)

    def read(self, blocks):
        blocks = np.asarray(blocks, np.int32).reshape(-1)
        parts = []
        order = []
        for s, sh in enumerate(self.shards):
            lo = s * self.blocks_per_shard
            idx = np.flatnonzero(
                (blocks >= lo) & (blocks < lo + self.blocks_per_shard))
            if idx.size:
                # gathered onto shard 0's device: concatenation needs
                # one device
                parts.append(jax.device_put(sh.read(blocks[idx] - lo),
                                            self.shards[0].device))
                order.append(idx)
        if not parts:
            raise ValueError("read of no blocks")
        gathered = jnp.concatenate(parts, axis=0)
        inv = np.argsort(np.concatenate(order))
        return gathered[jnp.asarray(inv)]

    # -- tier migrations -----------------------------------------------------
    def migrate_tiers(self, max_moves: int | None = None) -> dict:
        moves = 0
        for sh in self.shards:
            moves += sh.migrate_tiers(max_moves)["migrations"]
        return {"migrations": moves}

    # -- snapshot/restore ----------------------------------------------------
    def flush_dirty(self, hint_path: str = "/serve/kv_cache") -> dict:
        """Snapshot durability barrier, fanned out per shard. Each
        shard's flush bills its own tier channels (the per-device
        expander sets write in parallel, like everything else
        shard-local), so the mesh-level flush time is the slowest
        shard's, while ``page_outs`` counts all shards' traffic."""
        report = {"page_outs": 0, "flush_us": 0.0}
        for sh in self.shards:
            r = sh.flush_dirty(hint_path)
            report["page_outs"] += r["page_outs"]
            report["flush_us"] = max(report["flush_us"], r["flush_us"])
        return report

    def snapshot_state(self) -> dict:
        """Per-shard snapshot fan-out: one state sub-tree per shard plus
        the facade's transaction counter. One manifest per mesh — the
        caller persists this whole tree as a single checkpoint."""
        state = {f"shard{s}": sh.snapshot_state()
                 for s, sh in enumerate(self.shards)}
        state["meta"] = {"steps": self._steps, "n_shards": self.n_shards}
        return state

    def load_state(self, state: dict) -> None:
        meta = state["meta"]
        if int(meta["n_shards"]) != self.n_shards:
            raise ValueError(
                f"pool snapshot has {meta['n_shards']} shards, mesh has "
                f"{self.n_shards} — restore needs the crashed run's mesh")
        for s, sh in enumerate(self.shards):
            sh.load_state(state[f"shard{s}"])
        self._steps = int(meta["steps"])

    # -- tenant-facing views (tenants pin to shard 0) ------------------------
    @property
    def hbm(self):
        return self.shards[0].hbm

    @property
    def slot_of(self) -> np.ndarray:
        # global-id-indexable; shard 0's band leads, so tenant (shard-0)
        # ids index their own shard's HBM slots.
        return np.concatenate([sh.slot_of for sh in self.shards])

    @property
    def _allocated(self) -> np.ndarray:
        return np.concatenate([sh._allocated for sh in self.shards])

    # -- reporting -----------------------------------------------------------
    @property
    def stats(self) -> dict:
        merged = None
        for sh in self.shards:
            if merged is None:
                merged = {k: (dict(v) if isinstance(v, dict) else v)
                          for k, v in sh.stats.items()}
                merged["by_path"] = {p: dict(st) for p, st
                                     in sh.stats["by_path"].items()}
                continue
            for k, v in sh.stats.items():
                if k == "by_path":
                    for p, st in v.items():
                        dst = merged["by_path"].setdefault(
                            p, {kk: 0 for kk in st})
                        for kk, vv in st.items():
                            dst[kk] += vv
                elif isinstance(v, (int, float)):
                    merged[k] += v
        merged["steps"] = self._steps      # transactions, not shard calls
        return merged

    def duplex_speedup(self, hint_path: str | None = None) -> float:
        st = self.stats
        if hint_path is not None:
            st = st["by_path"].get(hint_path)
            if st is None:
                return 1.0
        if st["duplex_us"] == 0:
            return 1.0
        return st["serial_us"] / st["duplex_us"]

    def tier_speedup(self) -> float:
        st = self.stats
        if st["tier_us"] == 0:
            return 1.0
        return st["ddr5_us"] / st["tier_us"]

    def tier_stats(self) -> dict:
        """Unified schema (core.metrics) for both pool flavors, plus the
        sharded extras: per-shard detail under ``"shards"`` and the
        merged per-channel view keyed ``shard<s>/<channel>``."""
        st = self.stats
        per_shard = [sh.tier_stats() for sh in self.shards]
        return {"tiered": self.tiered,
                "channels": {f"shard{s}/{name}": ch
                             for s, ts in enumerate(per_shard)
                             for name, ch in ts["channels"].items()},
                "shards": per_shard,
                "migrations": st["migrations"],
                "migrate_us": round(st["migrate_us"], 3),
                "tier_us": round(st["tier_us"], 3),
                "ddr5_us": round(st["ddr5_us"], 3),
                "tier_speedup": round(self.tier_speedup(), 4)}

    def reset_stats(self) -> None:
        self._steps = 0
        for sh in self.shards:
            sh.reset_stats()

    # -- invariants ----------------------------------------------------------
    def check_invariants(self) -> None:
        """Every shard's block-table/placement invariants, plus the
        cross-shard ownership contract: shards' allocated sets are
        disjoint in the global namespace and no shard's tables reference
        ids outside its own band."""
        for sh in self.shards:
            sh.check_invariants()
            if sh.n_blocks != self.blocks_per_shard:
                raise AssertionError("shard block-band size drifted")
        seen: set[int] = set()
        for s, sh in enumerate(self.shards):
            lo = s * self.blocks_per_shard
            owned = {lo + int(b) for b in np.flatnonzero(sh._allocated)}
            if seen & owned:
                raise AssertionError(
                    f"cross-shard ownership overlap: {sorted(seen & owned)}")
            seen |= owned


# ---------------------------------------------------------------------------
# The sharded engine
# ---------------------------------------------------------------------------

class ShardedServeEngine(ServeEngine):
    """``ServeEngine`` over a ``data × model`` mesh.

    Everything host-side (admission, trajectory planning, paging plans,
    speculation, reconcile) is inherited unchanged — the schedule is
    deterministic host arithmetic and does not know the batch is
    sharded. The overrides are exactly the device-placement seams:

    * the megastep cell is the ``shard_map``-wrapped program;
    * params/cache/slot-state live on the mesh (params replicated,
      batch-dim leaves split over ``data``);
    * the KV pool is a ``ShardedKVPool`` (one shard per data rank, on
      that rank's device) and block allocation routes by the owning
      slot's shard;
    * the staged write-through slab is split into its per-rank bands
      where they already live (``_stage_view``) — no copy, and still
      zero host syncs mid-megastep;
    * modelled ICI traffic for the megastep's collectives is billed at
      dispatch (``IciMeter``) and surfaces in ``paging_stats()``.
    """

    def __init__(self, api, params, cfg, hints: HintTree | None = None,
                 mesh=None):
        if mesh is None:
            from repro.launch.mesh import make_debug_mesh
            mesh = make_debug_mesh()
        self.mesh = mesh
        self.data_size = int(mesh.shape["data"])
        self.model_size = int(mesh.shape["model"])
        if cfg.max_batch % self.data_size:
            raise ValueError(
                f"max_batch={cfg.max_batch} must divide evenly over the "
                f"data axis ({self.data_size} ranks) — every rank owns a "
                f"fixed slot band")
        self.slots_per_shard = cfg.max_batch // self.data_size
        self._ici = IciMeter(mesh)
        # data rank s's pool shard lives on the first device of mesh row
        # s, which holds that rank's cache rows and staged band.
        self.pool_devices = [mesh.devices[s, 0]
                             for s in range(self.data_size)]
        super().__init__(api, params, cfg, hints)
        # the base __init__ built the tracer/CAX registry; the ICI links
        # join the same modelled clock and scope tree.
        self._ici.trace = self._tracer
        self._ici.telemetry = self.telemetry
        self._place_device_state()
        # per-layer tensor-parallel psum payload (bf16 activations): the
        # launch.sharding row-parallel rules (attn/wo and mlp/w_down
        # sharded on the contraction dim) imply one all-reduce each.
        d_model = (getattr(api.cfg, "d_model", None)
                   or getattr(api.cfg, "hidden", 0) or 0)
        n_layers = (getattr(api.cfg, "num_layers", None)
                    or getattr(api.cfg, "n_layers", 0) or 1)
        self._tp_psums_per_micro = 2 * int(n_layers)
        self._tp_psum_bytes = float(self.slots_per_shard * d_model * 2)

    # -- sharding seams ------------------------------------------------------
    def _place_device_state(self) -> None:
        """Land the device state on the mesh: params replicated, cache
        leaves (L, B, ...) and slot-state leaves (B, ...) split over
        the data axis. The pool shards place their own buffers
        (``pool_devices``). Called at construction AND after a snapshot restore reloads
        ``cache``/``_dev`` as host arrays — the placement seam the
        restore path re-runs."""
        mesh = self.mesh
        rep = NamedSharding(mesh, P())
        row = NamedSharding(mesh, P("data"))
        crow = NamedSharding(mesh, P(None, "data"))
        self.params = jax.device_put(self.params, rep)
        self.cache = jax.tree.map(
            lambda x: jax.device_put(x, crow), self.cache)
        self._cache0 = jax.tree.map(
            lambda x: jax.device_put(x, crow), self._cache0)
        self._dev = {k: jax.device_put(v, row)
                     for k, v in self._dev.items()}

    def _make_pool(self, block_shape) -> ShardedKVPool:
        return ShardedKVPool(
            self.data_size, self.cfg.resolved_pool_blocks(),
            self.cfg.hbm_blocks, block_shape, hints=self.hints,
            tiers=self.cfg.tiers, faults=self.cfg.faults,
            devices=self.pool_devices)

    def _alloc_block(self, r: Request) -> list[int]:
        return self.pool.alloc(1, shard=r.slot // self.slots_per_shard)

    def _mega_fn(self, n_steps: int):
        bt = self.cfg.block_tokens if self.paged else None
        return _sharded_megastep_program(
            self.api, self.cfg.prefill_chunk, n_steps, bt, self.mesh)

    def _stage_view(self, staged):
        # the mesh-sharded (K, B*max_fills, bt, kv) slab, as each data
        # rank's band on its pool shard's device — no copy.
        band = {sh.device: sh.data for sh in staged.addressable_shards}
        return [band[d] for d in self.pool_devices]

    # -- ICI accounting ------------------------------------------------------
    def _dispatch(self, rec):
        rec = super()._dispatch(rec)
        if rec.live:
            self._bill_ici(rec)
        return rec

    def _bill_ici(self, rec) -> None:
        """Bill the megastep's modelled collective traffic: per inner
        step, the tensor-parallel psums the PartitionSpec rules imply
        (skipped when the step's ``lax.cond`` skipped the model — no
        movers, no collective) on the model axis; per megastep, the real
        packed-readback ``pmax`` (model axis) and the packed readback's
        gather across the mesh (data axis)."""
        n_micro = max(1, self.cfg.prefill_chunk)
        if self.model_size > 1:
            for t in range(rec.k):
                steps_t = [rec.traj[r.rid][t] for r in rec.live
                           if r.rid in rec.traj]
                # a step where every row is already DONE skips the model
                # entirely (the program's no-movers lax.cond) — no
                # collective runs.
                if not any(st.emitted or st.state != S_DONE
                           for st in steps_t):
                    continue
                # prefill rows run every micro-step; decode-only steps
                # run micro-step 0 alone.
                micro = n_micro if any(
                    st.state == S_PREFILL or st.transition
                    for st in steps_t) else 1
                for _ in range(micro * self._tp_psums_per_micro):
                    self._ici.note_allreduce("model", self._tp_psum_bytes)
            # the packed readback pmax: (B_local, 3+K) int32 replicas.
            self._ici.note_allreduce(
                "model",
                float(self.slots_per_shard * (3 + rec.k) * 4))
        if self.data_size > 1:
            # packed readback crosses the mesh once per megastep (the
            # staged slab stays on its rank's device: nothing to bill).
            self._ici.note_allgather(
                "data", float(self.slots_per_shard * (3 + rec.k) * 4))

    # -- snapshot seams ------------------------------------------------------
    def _snapshot_extra_state(self) -> dict:
        return {"ici": self._ici.snapshot_state()}

    def _load_extra_state(self, extra: dict) -> None:
        self._ici.load_state(extra.get("ici", {}))

    # -- reporting -----------------------------------------------------------
    def paging_stats(self) -> dict:
        st = super().paging_stats()
        st["mesh"] = {"data": self.data_size, "model": self.model_size}
        st["ici"] = self._ici.summary()
        if "by_path" in st:
            st["by_path"] = {**st["by_path"],
                             **{p: dict(s) for p, s
                                in self._ici.by_path.items()}}
        else:
            st["by_path"] = {p: dict(s) for p, s
                             in self._ici.by_path.items()}
        return st
