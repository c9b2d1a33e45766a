"""Sharded multi-device serving: the differential test lane.

Contract under test: ``ShardedServeEngine`` over any ``data × model``
mesh is **bit-exact** with the single-device ``ServeEngine`` — same
tokens, same request states, same admission/completion step timing —
for every megastep width (K = 1/4/8), both pipeline depths (1/2), and
all three workload families (ring-cache LLM, recurrent-cache LLM,
mixed LLM + KV-store tenants). On top of exactness:

  * pool ownership — each data rank's ``PagedKVPool`` shard allocates
    only for the slots it owns; ``check_invariants()`` covers every
    shard plus cross-shard global-id disjointness;
  * ICI billing — when the model axis is > 1, the modelled
    tensor-parallel collectives land nonzero bytes in
    ``paging_stats()["by_path"]["/serve/ici/model"]`` through the
    ``ici`` kind in ``core.channel.INTERCONNECT_PRESETS``; a (1, 1)
    mesh bills nothing;
  * sync budget — ONE packed readback per megastep per *mesh* (not per
    device), re-asserted under ``jax.transfer_guard`` at every device
    count, and the sharded program caches per (api, config, K, mesh)
    cell with zero retraces across engines sharing a cell;
  * ``make_debug_mesh`` degrades with a clear RuntimeWarning (never an
    opaque reshape error) when the host cannot supply the model axis.

Multi-device cases need forced host devices and skip gracefully below
their device count — CI runs this module under
``XLA_FLAGS=--xla_force_host_platform_device_count=4``.
"""

import dataclasses
import warnings

import jax
import numpy as np
import pytest

from repro.core import channel as channel_lib
from repro.launch.mesh import make_debug_mesh
from repro.models import registry as R
from repro.serve import (EngineConfig, KVStoreTenant, ServeEngine,
                         ShardedServeEngine)
from repro.serve.shard import IciMeter, _sharded_megastep_program

DEVICES = jax.device_count()


def _mesh(data, model):
    need = data * model
    if DEVICES < need:
        pytest.skip(f"needs {need} devices (run under XLA_FLAGS="
                    f"--xla_force_host_platform_device_count=4), "
                    f"have {DEVICES}")
    return make_debug_mesh(model, devices=jax.devices()[:need])


@pytest.fixture(scope="module")
def api():
    return R.build("smollm-135m", smoke=True)


@pytest.fixture(scope="module")
def params(api):
    return api.init(jax.random.PRNGKey(0))


def _cfg(**kw):
    base = dict(max_batch=4, cache_len=64, block_tokens=4, hbm_blocks=6,
                prefill_chunk=3, max_queue=8, megastep=4,
                pipeline_depth=2)
    base.update(kw)
    return EngineConfig(**base)


def _drive(api, eng, n=5, gen=8, seed=1, prompt_len=6):
    """Staggered greedy workload; returns per-SUBMISSION-ORDER tokens,
    (admitted, done) timing and final states (rids are globally
    monotonic across engines, so order — not rid — is the join key)."""
    key = jax.random.PRNGKey(seed)
    rids = [eng.submit(
        np.asarray(jax.random.randint(jax.random.fold_in(key, i),
                                      (prompt_len,), 0, api.cfg.vocab)),
        gen, arrival_step=2 * i).rid for i in range(n)]
    outs = eng.run()
    toks = [np.asarray(outs[r]) for r in rids]
    timing = [(eng.completed[r].admitted_step, eng.completed[r].done_step)
              for r in rids]
    states = [eng.completed[r].state for r in rids]
    return toks, timing, states


_REF = {}


def _reference(api, params, **cfg_kw):
    """The single-device oracle, cached per config cell (each one is a
    fresh compile)."""
    key = tuple(sorted(cfg_kw.items()))
    if key not in _REF:
        _REF[key] = _drive(api, ServeEngine(api, params, _cfg(**cfg_kw)))
    return _REF[key]


def _assert_differential(got, ref):
    for a, b in zip(got[0], ref[0]):
        np.testing.assert_array_equal(a, b)
    assert got[1] == ref[1], "admission/completion timing diverged"
    assert got[2] == ref[2], "request states diverged"


class TestMakeDebugMeshFallback:
    """Satellite fix: an unsatisfiable model axis falls back with a
    clear warning instead of numpy's opaque reshape ValueError."""

    def test_model_axis_exceeding_devices_falls_back(self):
        with pytest.warns(RuntimeWarning, match="falling back to"):
            mesh = make_debug_mesh(3, devices=jax.devices()[:1])
        assert dict(mesh.shape) == {"data": 1, "model": 1}

    def test_falls_back_to_largest_divisor(self):
        if DEVICES < 4:
            pytest.skip("needs 4 devices")
        with pytest.warns(RuntimeWarning, match="model=2"):
            mesh = make_debug_mesh(3, devices=jax.devices()[:4])
        assert dict(mesh.shape) == {"data": 2, "model": 2}

    def test_warning_names_the_forcing_flag(self):
        with pytest.warns(RuntimeWarning,
                          match="xla_force_host_platform_device_count"):
            make_debug_mesh(2, devices=jax.devices()[:1])

    def test_exact_divisor_is_silent(self):
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            mesh = make_debug_mesh(1, devices=jax.devices()[:1])
        assert dict(mesh.shape) == {"data": 1, "model": 1}

    def test_model_below_one_raises(self):
        with pytest.raises(ValueError, match="model"):
            make_debug_mesh(0)


class TestShardDifferential:
    """The core lane: sharded == single-device, token-for-token and
    step-for-step."""

    @pytest.mark.parametrize("megastep", [1, 4, 8])
    @pytest.mark.parametrize("depth", [1, 2])
    def test_ring_matrix_on_2x2(self, api, params, megastep, depth):
        mesh = _mesh(2, 2)
        ref = _reference(api, params, megastep=megastep,
                         pipeline_depth=depth)
        eng = ShardedServeEngine(
            api, params, _cfg(megastep=megastep, pipeline_depth=depth),
            mesh=mesh)
        _assert_differential(_drive(api, eng), ref)
        assert not eng.failed
        eng.pool.check_invariants()
        st = eng.paging_stats()
        assert st["mesh"] == {"data": 2, "model": 2}
        assert st["by_path"]["/serve/ici/model"]["bytes"] > 0
        assert st["by_path"]["/serve/ici/data"]["bytes"] > 0

    @pytest.mark.parametrize("dm", [(1, 1), (2, 1), (4, 1), (1, 4)])
    def test_mesh_shapes(self, api, params, dm):
        """Pure-data, pure-model and trivial meshes all reproduce the
        oracle; ICI bytes appear exactly on the axes that exist."""
        d, m = dm
        mesh = _mesh(d, m)
        ref = _reference(api, params)
        eng = ShardedServeEngine(api, params, _cfg(), mesh=mesh)
        _assert_differential(_drive(api, eng), ref)
        eng.pool.check_invariants()
        st = eng.paging_stats()
        assert ("/serve/ici/model" in st["by_path"]) == (m > 1)
        assert ("/serve/ici/data" in st["by_path"]) == (d > 1)
        if d == 1 and m == 1:
            assert st["ici"]["bytes"] == 0.0

    def test_recurrent_cache_family(self, api, params):
        """The recurrent (rwkv) cache family shards the same way: its
        cache leaves are (L, B, ...) state rows, split over data."""
        api_r = R.build("rwkv6-7b", smoke=True)
        params_r = api_r.init(jax.random.PRNGKey(0))
        ref = _drive(api_r, ServeEngine(api_r, params_r, _cfg()),
                     n=4, gen=6, seed=2, prompt_len=5)
        mesh = _mesh(2, 2)
        eng = ShardedServeEngine(api_r, params_r, _cfg(), mesh=mesh)
        _assert_differential(
            _drive(api_r, eng, n=4, gen=6, seed=2, prompt_len=5), ref)
        assert eng.pool is None        # recurrent family: no paged pool

    def test_mixed_tenant(self, api, params):
        """LLM rows + a KV-store tenant sharing the pool: tokens, op
        counts and the tenant's GET checksum all match, and the tenant's
        blocks pin to shard 0."""
        def run(eng):
            kv = eng.add_tenant(KVStoreTenant(n_slots=2, ops_per_step=2,
                                              store_blocks=16))
            kv.preload(8)
            kv.submit("sequential", n_steps=12)
            toks, timing, states = _drive(api, eng, n=4)
            return toks, timing, states, kv.ops_done, kv.result(), eng

        cfg_kw = dict(pool_blocks=96, hbm_blocks=14)
        *ref, _ = run(ServeEngine(api, params, _cfg(**cfg_kw)))
        mesh = _mesh(2, 2)
        *got, eng = run(ShardedServeEngine(api, params, _cfg(**cfg_kw),
                                           mesh=mesh))
        _assert_differential(got[:3], ref[:3])
        assert got[3] == ref[3] and got[4] == ref[4]
        eng.pool.check_invariants()

    def test_block_ownership_follows_slot(self, api, params):
        """Every request's KV blocks come from the pool shard owning its
        slot — checked live at every megastep boundary, together with
        the cross-shard disjointness invariant."""
        mesh = _mesh(2, 2)
        eng = ShardedServeEngine(api, params, _cfg(), mesh=mesh)
        prompts = jax.random.randint(jax.random.PRNGKey(9), (5, 6), 0,
                                     api.cfg.vocab)
        for i in range(5):
            eng.submit(np.asarray(prompts[i]), 10, arrival_step=i)
        saw_blocks = False
        for _ in range(60):
            if not eng.pending():
                break
            eng.megastep(4)
            for r in eng.active():
                shard = r.slot // eng.slots_per_shard
                for b in r.blocks:
                    assert eng.pool.shard_of(b) == shard, (r.slot, b)
                saw_blocks = saw_blocks or bool(r.blocks)
            eng.pool.check_invariants()
        assert not eng.pending()
        assert saw_blocks

    @pytest.mark.parametrize("dm", [(4, 1), (2, 2)])
    def test_pool_shard_lives_on_its_rank_device(self, api, params, dm):
        """Shard s's pool buffers sit on data rank s's device (the first
        device of mesh row s), before and after serving — paging and
        write-through never pull them onto another device."""
        mesh = _mesh(*dm)
        # 7 filled blocks per request, more than a shard's 6 HBM
        # slots even at one slot per shard: every shard pages.
        ref = _drive(api, ServeEngine(api, params, _cfg()), gen=24)
        eng = ShardedServeEngine(api, params, _cfg(), mesh=mesh)

        def check():
            for s, sh in enumerate(eng.pool.shards):
                want = {mesh.devices[s, 0]}
                for buf in (sh.hbm, sh.host_q, sh.host_scale):
                    assert buf.devices() == want, (s, buf.devices())

        check()
        _assert_differential(_drive(api, eng, gen=24), ref)
        assert all(sh.stats["page_outs"] > 0 for sh in eng.pool.shards)
        check()

    def test_uneven_batch_rejected(self, api, params):
        mesh = _mesh(2, 1)
        with pytest.raises(ValueError, match="data axis"):
            ShardedServeEngine(api, params, _cfg(max_batch=3), mesh=mesh)


class TestShardSyncBudget:
    """Per device count: one packed readback per megastep per mesh, and
    zero retraces across engines sharing a program cell."""

    @pytest.mark.parametrize("dm", [(1, 1), (2, 1), (2, 2)])
    def test_one_readback_per_megastep(self, api, params, dm):
        mesh = _mesh(*dm)
        eng = ShardedServeEngine(api, params, _cfg(), mesh=mesh)
        prompts = jax.random.randint(jax.random.PRNGKey(24), (3, 6), 0,
                                     api.cfg.vocab)
        for i in range(3):
            eng.submit(np.asarray(prompts[i]), 20)
        eng.megastep(4)          # compile everything outside the guard
        syncs = []
        orig = eng._readback

        def guarded(packed):
            syncs.append(np.asarray(packed).shape)
            with jax.transfer_guard("allow"):
                return orig(packed)

        eng._readback = guarded
        for _ in range(3):
            n = len(syncs)
            with jax.transfer_guard_device_to_host("disallow"):
                report = eng.megastep(4)
            assert len(syncs) == n + 1
            assert report["steps"] == 4
        # the one sync is the mesh-global packed (B, 3+K) readback.
        assert all(s == (eng.cfg.max_batch, 3 + 4) for s in syncs)

    @pytest.mark.parametrize("dm", [(1, 1), (2, 1), (2, 2)])
    def test_program_cached_per_mesh_cell(self, api, params, dm):
        """One compile per (api, config, K, mesh) cell; engines sharing
        the cell reuse it with zero retraces, and distinct meshes get
        distinct cells."""
        mesh = _mesh(*dm)
        eng = ShardedServeEngine(api, params, _cfg(), mesh=mesh)
        eng.submit(np.ones(5, np.int32), 8)
        eng.run(max_steps=100)
        fn = eng._mega_fn(4)
        assert fn is _sharded_megastep_program(
            api, eng.cfg.prefill_chunk, 4, eng.cfg.block_tokens, mesh)
        size = fn._cache_size()
        assert size >= 1
        eng2 = ShardedServeEngine(api, params, _cfg(), mesh=mesh)
        assert eng2._mega_fn(4) is fn
        eng2.submit(np.ones(5, np.int32), 8)
        eng2.run(max_steps=100)
        assert fn._cache_size() == size        # zero retraces
        if DEVICES >= 2 and dm != (2, 1):
            other = ShardedServeEngine(api, params, _cfg(),
                                       mesh=_mesh(2, 1))
            assert other._mega_fn(4) is not fn


class TestIciChannel:
    """The interconnect is a first-class ``core.channel`` kind: billed
    with the same duplex/serial arithmetic as the host tiers."""

    def test_preset_registered(self):
        link = channel_lib.INTERCONNECT_PRESETS["ici"]
        assert isinstance(link, channel_lib.ChannelModel)
        assert link.duplex

    def test_meter_allreduce_wire_volume(self):
        mesh = make_debug_mesh(1, devices=jax.devices()[:1])
        m = IciMeter(mesh)
        m.axis_size = {"data": 1, "model": 4}      # synthetic 4-rank axis
        m.note_allreduce("model", 1000.0)
        st = m.by_path["/serve/ici/model"]
        # ring all-reduce: 2(m-1)/m per direction -> 1500 read + 1500
        # written per device.
        assert st["bytes"] == pytest.approx(3000.0)
        assert st["collectives"] == 1
        assert st["duplex_us"] > 0
        assert st["serial_us"] > st["duplex_us"]   # duplex overlaps legs
        m.note_allgather("data", 0.0)              # degenerate: no-op
        m.note_allreduce("data", 500.0)            # axis size 1: no-op
        assert "/serve/ici/data" not in m.by_path
        assert m.summary()["links"] == {"data": 1, "model": 4}

    def test_model_axis_bills_into_paths(self, api, params):
        mesh = _mesh(1, 2)
        eng = ShardedServeEngine(api, params, _cfg(), mesh=mesh)
        _drive(api, eng, n=3)
        st = eng.paging_stats()
        ici = st["ici"]
        assert ici["bytes"] > 0 and ici["collectives"] > 0
        assert ici["duplex_us"] > 0
        mp = st["by_path"]["/serve/ici/model"]
        assert mp["bytes"] == ici["bytes"]
        assert "/serve/ici/data" not in st["by_path"]
