"""Fault injection + graceful degradation: the chaos harness.

Contract under test: a deterministic ``FaultInjector`` plan (channel
bandwidth degradation, transient transfer errors, poisoned host blocks,
channel hot-unplug) must never drop the fleet. Transient errors retry
with billed backoff and the served tokens stay bit-exact with the
fault-free run; a poisoned block quarantines its host slot and fails
ONLY the owning request (structured ``Request.error``); an offline
channel emergency-evacuates its live rows onto survivors and sheds the
requests the degraded capacity can no longer hold; a workload that can
never progress raises ``EngineStallError`` naming the stuck rids instead
of spinning. Pool invariants hold at every boundary, and recovery is
never free — retries and evacuation land in ``busy_us`` / migration
counters.
"""

import dataclasses

import jax
import numpy as np
import pytest

from repro.core.faults import (FAULT_KINDS, FaultEvent, FaultInjector,
                               fresh_fault_stats, parse_fault_plan,
                               random_plan)
from repro.models import registry as R
from repro.serve import (FAILED, EngineConfig, EngineStallError,
                         KVStoreTenant, Request, ServeEngine)

N_REQ, PROMPT_LEN, GEN = 4, 6, 12


@pytest.fixture(scope="module")
def api():
    return R.build("smollm-135m", smoke=True)


@pytest.fixture(scope="module")
def params(api):
    return api.init(jax.random.PRNGKey(0))


def _cfg(**kw):
    base = dict(max_batch=3, cache_len=64, block_tokens=4, hbm_blocks=6,
                prefill_chunk=3, max_queue=8, megastep=4,
                pipeline_depth=2)
    base.update(kw)
    return EngineConfig(**base)


def _serve(api, params, *, max_steps=600, **cfg_kw):
    """The shared chaos workload: N_REQ staggered greedy requests.
    Tokens are per-request deterministic (greedy argmax over the
    prompt), so any fault-free run is the oracle for every fault run's
    survivors regardless of tiering or admission timing."""
    eng = ServeEngine(api, params, _cfg(**cfg_kw))
    prompts = jax.random.randint(jax.random.PRNGKey(77),
                                 (N_REQ, PROMPT_LEN), 0, api.cfg.vocab)
    reqs = [eng.submit(np.asarray(prompts[i]), GEN, arrival_step=2 * i)
            for i in range(N_REQ)]
    outs = eng.run(max_steps=max_steps)
    return eng, reqs, outs


@pytest.fixture(scope="module")
def baseline(api, params):
    """Fault-free oracle: submission index -> served tokens (rids are
    globally monotonic across engines), plus the engine for billing
    comparisons."""
    eng, reqs, outs = _serve(api, params)
    return [np.asarray(outs[r.rid]) for r in reqs], eng


def _check_survivors(eng, reqs, outs, oracle, allowed_kinds):
    """Every request either matches the oracle token-for-token or
    carries a structured error of an expected kind."""
    for i, r in enumerate(reqs):
        if r.rid in outs:
            np.testing.assert_array_equal(np.asarray(outs[r.rid]),
                                          oracle[i])
        else:
            fr = eng.failed[r.rid]
            assert fr.state == FAILED
            assert fr.error is not None
            assert fr.error["kind"] in allowed_kinds
            assert "step" in fr.error


class TestPlanGrammar:
    def test_parse_roundtrip(self):
        plan = parse_fault_plan(
            "offline:1@6,poison:3@4,degrade:0@2+8=0.5,"
            "transient:2@1+20=0.3")
        kinds = sorted(e.kind for e in plan)
        assert kinds == sorted(FAULT_KINDS)
        off = next(e for e in plan if e.kind == "offline")
        assert (off.channel, off.at_step) == (1, 6)
        deg = next(e for e in plan if e.kind == "degrade")
        assert (deg.factor, deg.duration) == (0.5, 8)

    @pytest.mark.parametrize("bad", [
        "", "nonsense", "offline:@3", "degrade:0@2=0.5",
        "poison:1@2+3=0.5", "transient:0@1+5=1.5", "degrade:0@1+5=0",
    ])
    def test_malformed_specs_raise(self, bad):
        with pytest.raises(ValueError):
            parse_fault_plan(bad)

    def test_event_validation(self):
        with pytest.raises(ValueError):
            FaultEvent(kind="poison", at_step=1)       # needs a block
        with pytest.raises(ValueError):
            FaultEvent(kind="degrade", at_step=1, channel=0,
                       factor=1.5, duration=4)
        with pytest.raises(ValueError):
            FaultEvent(kind="nope", at_step=1, channel=0)

    def test_random_plan_never_kills_last_channel(self):
        for seed in range(40):
            plan = random_plan(seed, n_channels=3, n_blocks=16,
                               horizon=50)
            offlined = {e.channel for e in plan if e.kind == "offline"}
            assert len(offlined) < 3


class TestZeroCostDisabled:
    def test_stats_schema_without_injector(self, baseline):
        """No injector: stats()["faults"] is present with every counter
        zero (consumers never branch on key presence) and the checksum
        plumbing is never allocated."""
        _, eng = baseline
        f = eng.stats()["faults"]
        assert f == fresh_fault_stats()
        assert all(not v for v in f.values())
        assert eng.pool._csum_data is None
        assert eng._fx is None

    def test_faults_require_paging(self, api, params):
        fx = FaultInjector(parse_fault_plan("poison:0@2"))
        with pytest.raises(ValueError, match="paged"):
            ServeEngine(api, params, _cfg(paging=False, faults=fx))


class TestTransientRetries:
    def test_bit_exact_and_billed(self, api, params, baseline):
        """Transient transfer errors + a degraded window on the flat
        host channel: every retry is billed into the paging clock (no
        free recovery bandwidth) and the served tokens are bit-exact
        with the fault-free run — transients are invisible except in
        time."""
        oracle, base_eng = baseline
        fx = FaultInjector(parse_fault_plan(
            "transient:0@1+80=0.5,degrade:0@4+40=0.25"), seed=3)
        eng, reqs, outs = _serve(api, params, faults=fx)
        _check_survivors(eng, reqs, outs, oracle, set())
        assert not eng.failed
        f = eng.stats()["faults"]
        assert f["injected"] == 2
        assert f["retried"] > 0
        assert f["recovered"] > 0
        assert f["retry_us"] > 0.0
        # same traffic, strictly more modelled time: retries + the
        # degraded-bandwidth window are billed, never absorbed.
        assert eng.pool.stats["duplex_us"] > \
            base_eng.pool.stats["duplex_us"]
        assert (eng.pool.stats["page_ins"], eng.pool.stats["page_outs"]) \
            == (base_eng.pool.stats["page_ins"],
                base_eng.pool.stats["page_outs"])
        eng.pool.check_invariants()


class TestPoisonQuarantine:
    def test_only_owner_fails(self, api, params, baseline):
        """Poisoned host copies are caught by the page-in checksum
        verify: the host slot quarantines, the owning request FAILs with
        a structured error, and everyone else's tokens are untouched."""
        oracle, _ = baseline
        fx = FaultInjector(parse_fault_plan(
            "poison:0@6,poison:1@7,poison:2@8"), seed=0)
        eng, reqs, outs = _serve(api, params, faults=fx,
                                 tiers="ddr5:1,cxl:2")
        f = eng.stats()["faults"]
        assert f["quarantined"] > 0
        assert f["failed"] == len(eng.failed) > 0
        assert len(outs) + len(eng.failed) == N_REQ
        _check_survivors(eng, reqs, outs, oracle, {"poisoned_block"})
        for fr in eng.failed.values():
            assert fr.blocks_freed or not fr.blocks
        eng.pool.check_invariants()
        # quarantined host slots left the free pool for good.
        host = eng.pool.host
        assert int(host._quarantined.sum()) == f["quarantined"]
        assert host.capacity_degraded

    def test_poison_on_flat_pool_scrubs_in_place(self, api, params,
                                                 baseline):
        """Identity (flat) host pools model scrub-in-place: slot==block,
        so a poisoned page is detected, the owner fails, and the slot is
        rewritten rather than retired — no capacity loss."""
        oracle, _ = baseline
        fx = FaultInjector(parse_fault_plan(
            "poison:0@6,poison:1@7,poison:2@8"), seed=0)
        eng, reqs, outs = _serve(api, params, faults=fx)
        f = eng.stats()["faults"]
        assert f["quarantined"] > 0
        assert f["failed"] == len(eng.failed) > 0
        _check_survivors(eng, reqs, outs, oracle, {"poisoned_block"})
        eng.pool.check_invariants()
        host = eng.pool.host
        assert host.live_capacity() == eng.pool.n_blocks
        assert not host.capacity_degraded

    def test_poison_before_host_copy_rearms(self):
        """A poison event for a block with no host copy yet re-arms
        instead of vanishing — the injector clock marches on."""
        fx = FaultInjector([FaultEvent(kind="poison", at_step=0,
                                       block=5)])
        fx.tick()
        assert fx.drain_poison() == [5]
        fx.rearm_poison(5)
        fx.tick()
        assert fx.drain_poison() == [5]


class TestOfflineEvacuation:
    @pytest.mark.parametrize("plan,casualties", [
        ("offline:2@8", {"evacuation_casualty", "shed"}),
        # transients before and poisoned blocks after the unplug
        ("transient:0@2+40=0.4,offline:2@8,poison:0@10,poison:1@11",
         {"evacuation_casualty", "shed", "poisoned_block"}),
    ], ids=["unplug", "unplug-transient-poison"])
    def test_hot_unplug_evacuates(self, api, params, baseline, plan,
                                  casualties):
        """Mid-serve channel hot-unplug: live host rows move to the
        surviving channels through the billed migration path, the dead
        channel holds nothing afterwards, placement never touches it
        again, and the survivors stay bit-exact."""
        oracle, _ = baseline
        fx = FaultInjector(parse_fault_plan(plan), seed=1)
        eng, reqs, outs = _serve(api, params, faults=fx,
                                 tiers="ddr5:1,cxl:2")
        f = eng.stats()["faults"]
        assert f["offline_channels"] == [2]
        assert f["evacuated"] > 0 and f["recovered"] >= f["evacuated"]
        assert outs, "no survivors"
        _check_survivors(eng, reqs, outs, oracle, casualties)
        host = eng.pool.host
        assert bool(host.offline[2])
        ts = eng.pool.tier_stats()
        dead = ts["channels"]["cxl:2"]
        assert dead["offline"] and dead["slots_used"] == 0
        assert dead["lost"] > 0
        # evacuation is billed: the dying channel's read leg + the
        # survivors' write legs land in busy_us / migrate_us.
        assert ts["migrate_us"] > 0.0
        assert dead["migrated_out"] > 0
        eng.pool.check_invariants()

    def test_offline_on_flat_pool_rejected(self, api, params):
        """Channel loss needs channels: a flat single-channel host pool
        surfaces the config error instead of silently dropping data."""
        fx = FaultInjector(parse_fault_plan("offline:0@2"))
        eng = ServeEngine(api, params, _cfg(faults=fx))
        eng.submit(np.ones(PROMPT_LEN, np.int32), GEN)
        with pytest.raises(RuntimeError, match="flat"):
            eng.run(max_steps=100)

    def test_invariants_every_boundary(self, api, params):
        """check_invariants() holds at every megastep boundary through
        degradation, poison, and a hot-unplug."""
        fx = FaultInjector(parse_fault_plan(
            "degrade:1@2+10=0.5,poison:0@5,offline:2@9,"
            "transient:0@3+30=0.4"), seed=5)
        eng = ServeEngine(api, params, _cfg(faults=fx,
                                            tiers="ddr5:1,cxl:2"))
        prompts = jax.random.randint(jax.random.PRNGKey(77),
                                     (N_REQ, PROMPT_LEN), 0,
                                     api.cfg.vocab)
        for i in range(N_REQ):
            eng.submit(np.asarray(prompts[i]), GEN, arrival_step=2 * i)
        for _ in range(60):
            if not eng.pending():
                break
            eng.megastep(4)
            eng.pool.check_invariants()
        assert not eng.pending()


class TestShedding:
    def test_deadline_shedding_under_lost_capacity(self, api, params,
                                                   baseline):
        """Single-kind tiers put host capacity == pool blocks, so a
        hot-unplug makes the committed footprint exceed the surviving
        slots: the engine sheds the largest/doomed requests with
        structured errors and finishes the rest cleanly — partial
        results, not a wedged fleet."""
        oracle, _ = baseline
        fx = FaultInjector(parse_fault_plan("offline:3@6"), seed=2)
        eng, reqs, outs = _serve(api, params, faults=fx, tiers="cxl:4",
                                 pool_blocks=16)
        f = eng.stats()["faults"]
        assert f["shed"] > 0
        assert eng.failed
        shed = [r for r in eng.failed.values()
                if r.error["kind"] == "shed"]
        assert shed
        for r in shed:
            assert r.error["live_capacity"] < 16
        _check_survivors(eng, reqs, outs, oracle,
                         {"shed", "evacuation_casualty"})
        assert outs, "shedding must leave survivors, not drop the fleet"
        # what kept running fits what survived.
        host = eng.pool.host
        assert eng._committed_blocks() <= host.live_capacity()
        eng.pool.check_invariants()


class TestStallGuard:
    def test_stuck_request_names_rids(self, api, params):
        """A request no admission path can ever serve (unknown tenant)
        trips the zero-progress guard: EngineStallError names the stuck
        rids instead of burning the step limit."""
        eng = ServeEngine(api, params, _cfg(stall_boundaries=4,
                                            hbm_blocks=10,
                                            pool_blocks=64))
        eng.add_tenant(KVStoreTenant(n_slots=1, ops_per_step=1,
                                     store_blocks=8))
        ghost = eng.queue.submit(Request(
            prompt=np.ones(4, np.int32), max_new_tokens=4,
            tenant="ghost"))
        with pytest.raises(EngineStallError) as ei:
            eng.run(max_steps=200)
        assert ghost.rid in ei.value.rids
        assert str(ghost.rid) in str(ei.value)

    def test_progress_resets_the_guard(self, api, params):
        """Normal serving never trips the guard, even at a tight
        threshold: every boundary with live rows counts as progress."""
        eng, reqs, outs = _serve(api, params, stall_boundaries=2)
        assert len(outs) == N_REQ


class TestDivergedDiagnostics:
    def test_diverged_names_rid_boundary_field(self, api, params):
        """The divergence error is a diagnosis, not a shrug: it names
        the rid, the boundary, and the exact field (consumed) that
        contradicted the dispatched trajectory."""
        eng = ServeEngine(api, params, _cfg())
        prompts = jax.random.randint(jax.random.PRNGKey(35),
                                     (3, 8), 0, api.cfg.vocab)
        for i in range(3):
            eng.submit(np.asarray(prompts[i]), 16)
        eng.megastep(4)
        rec = eng._dispatch(eng._plan(4))
        rid, steps = next(iter(rec.traj.items()))
        steps[-1] = dataclasses.replace(steps[-1],
                                        consumed=steps[-1].consumed + 1)
        with pytest.raises(RuntimeError, match="diverged") as ei:
            eng._reconcile(rec)
        msg = str(ei.value)
        assert f"rid {rid}" in msg
        assert "boundary at step" in msg
        assert "consumed" in msg
        assert "host planned" in msg and "device reported" in msg


class TestReclaimMigrationInterleave:
    def test_reclaim_across_tier_migrations(self, api, params, baseline):
        """Satellite: the journal-rollback reclaim path interleaved with
        boundary tier migrations — host rows may physically move between
        a free and its reclaim, and ownership must still round-trip
        (same blocks, clean invariants, untouched final tokens)."""
        oracle, _ = baseline
        eng = ServeEngine(api, params, _cfg(tiers="ddr5:2,cxl:2",
                                            pool_blocks=32))
        prompts = jax.random.randint(jax.random.PRNGKey(77),
                                     (N_REQ, PROMPT_LEN), 0,
                                     api.cfg.vocab)
        reqs = [eng.submit(np.asarray(prompts[i]), GEN,
                           arrival_step=2 * i) for i in range(N_REQ)]
        eng.megastep(4)
        eng.megastep(4)     # settle into decode; evictions made host rows
        pool = eng.pool
        victim = next(r for r in eng.active() if r.blocks)
        ids = list(victim.blocks)
        pool.free(ids)
        pool.migrate_tiers()            # rows may move channels here
        pool.reclaim(ids)               # ownership must still round-trip
        pool.migrate_tiers()
        pool.check_invariants()
        assert pool._allocated[ids].all()
        with pytest.raises(RuntimeError, match="reclaim"):
            pool.reclaim(ids)           # still guards allocated blocks
        outs = eng.run(max_steps=600)
        for i, r in enumerate(reqs):
            np.testing.assert_array_equal(np.asarray(outs[r.rid]),
                                          oracle[i])


class TestShardedChaos:
    """Chaos cross-test with the sharded engine: seeded fault plans
    replay bit-identically on a ``data × model`` mesh, poison routes to
    the shard owning the block's global-id band, and an offline channel
    evacuates on every shard WITHOUT any row crossing a shard boundary
    (each shard's tables are local-id-sized, so ``check_invariants``
    plus per-shard tier accounting pin it observably). Needs 4 forced
    host devices; skips gracefully otherwise."""

    def _serve_sharded(self, api, params, *, max_steps=600, **cfg_kw):
        if jax.device_count() < 4:
            pytest.skip("needs 4 devices (XLA_FLAGS="
                        "--xla_force_host_platform_device_count=4)")
        from repro.launch.mesh import make_debug_mesh
        from repro.serve import ShardedServeEngine
        # Pin to exactly 4 devices: the full suite may run with MORE
        # forced host devices (the launch dry-run forces 512), and the
        # data axis must divide max_batch=4.
        eng = ShardedServeEngine(api, params,
                                 _cfg(max_batch=4, **cfg_kw),
                                 mesh=make_debug_mesh(
                                     2, devices=jax.devices()[:4]))
        prompts = jax.random.randint(jax.random.PRNGKey(77),
                                     (N_REQ, PROMPT_LEN), 0,
                                     api.cfg.vocab)
        reqs = [eng.submit(np.asarray(prompts[i]), GEN,
                           arrival_step=2 * i) for i in range(N_REQ)]
        outs = eng.run(max_steps=max_steps)
        return eng, reqs, outs

    @staticmethod
    def _signature(eng, reqs, outs):
        """Everything a replay must reproduce bit-for-bit."""
        toks = [np.asarray(outs[r.rid]).tolist() if r.rid in outs
                else None for r in reqs]
        timing = [(eng.completed[r.rid].admitted_step,
                   eng.completed[r.rid].done_step)
                  if r.rid in eng.completed else None for r in reqs]
        errors = sorted(
            (r.error["kind"], r.error.get("block", -1), r.error["step"])
            for r in eng.failed.values())
        return toks, timing, errors, dict(eng.stats()["faults"])

    def test_seeded_plan_replays_bit_identical(self, api, params):
        """Same plan + same injector seed => the sharded run reproduces
        tokens, timing, structured errors and fault counters exactly."""
        plan = ("transient:0@2+40=0.4,degrade:1@4+12=0.5,"
                "poison:0@6,poison:1@7,offline:2@10")

        def once():
            fx = FaultInjector(parse_fault_plan(plan), seed=11)
            eng, reqs, outs = self._serve_sharded(
                api, params, faults=fx, tiers="ddr5:1,cxl:2")
            eng.pool.check_invariants()
            return self._signature(eng, reqs, outs)

        assert once() == once()

    def test_transients_bit_exact_with_oracle(self, api, params,
                                              baseline):
        """Transient retries on every shard's channels stay invisible
        except in billed time: all four requests finish with the
        fault-free oracle's tokens."""
        oracle, _ = baseline
        fx = FaultInjector(parse_fault_plan(
            "transient:0@1+80=0.5,degrade:0@4+40=0.25"), seed=3)
        eng, reqs, outs = self._serve_sharded(api, params, faults=fx)
        _check_survivors(eng, reqs, outs, oracle, set())
        assert not eng.failed
        f = eng.stats()["faults"]
        assert f["retried"] > 0 and f["recovered"] > 0
        eng.pool.check_invariants()

    def test_poison_routes_to_owning_shard(self, api, params, baseline):
        """Poison aimed at shard 1's global-id band quarantines host
        slots on shard 1 ONLY; shard 0's capacity and requests are
        untouched, and every failed request was a shard-1 resident."""
        oracle, _ = baseline
        per = 24                                  # blocks per shard
        fx = FaultInjector(parse_fault_plan(
            f"poison:{per}@2,poison:{per + 1}@3,poison:{per + 2}@3"),
            seed=0)
        eng, reqs, outs = self._serve_sharded(
            api, params, faults=fx, tiers="ddr5:1,cxl:2",
            pool_blocks=per, hbm_blocks=4)
        f = eng.stats()["faults"]
        assert f["quarantined"] > 0
        assert eng.failed
        _check_survivors(eng, reqs, outs, oracle, {"poisoned_block"})
        s0, s1 = eng.pool.shards
        assert int(s0.host._quarantined.sum()) == 0
        assert int(s1.host._quarantined.sum()) == f["quarantined"]
        assert not s0.host.capacity_degraded
        for fr in eng.failed.values():
            assert fr.error["block"] >= per    # the poisoned band
        eng.pool.check_invariants()

    def test_offline_evacuation_stays_shard_local(self, api, params,
                                                  baseline):
        """Hot-unplug of tier channel 2: every shard loses ITS channel
        2 and evacuates onto ITS survivors — the dead channel is empty
        on both shards, each shard's migrated_out is accounted in its
        own tier stats, and no shard's tables can name a foreign block
        (they are local-id-sized; check_invariants re-proves the band)."""
        oracle, _ = baseline
        fx = FaultInjector(parse_fault_plan("offline:2@12"), seed=1)
        eng, reqs, outs = self._serve_sharded(
            api, params, faults=fx, tiers="ddr5:1,cxl:2",
            pool_blocks=24, hbm_blocks=4)
        f = eng.stats()["faults"]
        assert f["offline_channels"] == [2]
        assert f["evacuated"] > 0
        _check_survivors(eng, reqs, outs, oracle,
                         {"evacuation_casualty", "shed"})
        migrated = 0
        for sh in eng.pool.shards:
            assert bool(sh.host.offline[2])
            dead = sh.tier_stats()["channels"]["cxl:2"]
            assert dead["offline"] and dead["slots_used"] == 0
            migrated += dead["migrated_out"]
        # all evacuation traffic is accounted inside the owning shards
        assert migrated >= f["evacuated"]
        eng.pool.check_invariants()


try:        # the property runs hypothesis-driven when available and
    from hypothesis import HealthCheck, given, settings   # noqa: F401
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:   # falls back to fixed seeds in lean containers
    HAVE_HYPOTHESIS = False


class TestChaosSchedules:
    """Property: ANY generated fault schedule (degrade / transient /
    poison / hot-unplug at random steps) leaves the fleet standing —
    run() returns, every casualty carries a structured error, every
    survivor is bit-exact with the fault-free oracle, and the pool's
    invariants hold."""

    def _survives(self, api, params, baseline, seed):
        oracle, _ = baseline
        plan = random_plan(seed, n_channels=3, n_blocks=24, horizon=20,
                           n_events=5)
        fx = FaultInjector(plan, seed=seed)
        eng, reqs, outs = _serve(api, params, faults=fx,
                                 tiers="ddr5:1,cxl:2")
        eng.pool.check_invariants()
        _check_survivors(eng, reqs, outs, oracle,
                         {"poisoned_block", "evacuation_casualty",
                          "shed"})
        f = eng.stats()["faults"]
        # a run can complete (or shed itself small) before the latest
        # events' transactions arrive — but the early ones must land.
        assert 1 <= f["injected"] <= len(plan)
        assert f["failed"] == len(eng.failed)

    @pytest.mark.parametrize("seed", [0, 1347, 9021])
    def test_fixed_seeds_survive(self, api, params, baseline, seed):
        self._survives(api, params, baseline, seed)

    if HAVE_HYPOTHESIS:
        @settings(max_examples=4, deadline=None,
                  suppress_health_check=[HealthCheck.too_slow])
        @given(seed=st.integers(min_value=0, max_value=10_000))
        def test_random_plan_survives(self, api, params, baseline,
                                      seed):
            self._survives(api, params, baseline, seed)
