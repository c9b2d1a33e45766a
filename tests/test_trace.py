"""Duplex-aware tracing plane: the observability contracts.

Contracts under test:
  * zero cost when disabled — a traced engine generates token-for-token
    what the untraced engine does, with identical modelled billing and
    tier accounting, and tracing adds ZERO device->host transfers to
    the one-packed-readback-per-megastep sync budget
    (``jax.transfer_guard``-asserted);
  * schema — ``phase_totals``/``duplex_util``/``summary`` and the
    ``engine.metrics()`` registry snapshot carry the documented keys
    (the ``core.metrics`` unified schema), and flat pools emit the same
    ``tiers`` keys as tiered pools, zeroed;
  * Perfetto round-trip — ``export_trace`` writes JSON that loads back
    with process/thread metadata, complete spans on the host-clock
    process, channel busy slices on the modelled-clock process, and
    monotonic non-overlapping intervals per track;
  * fault instants — an armed ``FaultInjector`` lands its events as
    instant markers on the ``faults`` track;
  * sharded — a (2, 2) mesh trace namespaces each data rank's channel
    tracks ``shard<s>/`` and bills the model-axis collectives on an
    ``ici:model`` track;
  * requests — every finished request carries ordered host-clock stamps
    (submit <= admit <= first token <= done) at any pipeline depth, and
    a tracer turns them into one ``queued``/``prefill``/``decode`` span
    each, keyed by rid;
  * profiler — the boundary phases appear as ``serve.*`` annotations in
    a ``jax.profiler`` trace, and the megastep's ops carry their
    ``megastep/*`` scopes in the compiled program's metadata.

Multi-device cases skip below 4 devices — CI runs the sharded lane
under ``XLA_FLAGS=--xla_force_host_platform_device_count=4``.
"""

import glob
import json
import os

import jax
import numpy as np
import pytest

from repro.models import registry as R
from repro.serve import EngineConfig, ServeEngine, Tracer
from repro.serve.engine import _megastep_math
from repro.serve.trace import PHASES, REQUEST_SPANS

DEVICES = jax.device_count()


@pytest.fixture(scope="module")
def api():
    return R.build("smollm-135m", smoke=True)


@pytest.fixture(scope="module")
def params(api):
    return api.init(jax.random.PRNGKey(0))


def _cfg(**kw):
    base = dict(max_batch=3, cache_len=64, block_tokens=4, hbm_blocks=6,
                prefill_chunk=3, max_queue=8, megastep=4)
    base.update(kw)
    return EngineConfig(**base)


def _drive(eng, n=5, gen=10, seed=21):
    prompts = jax.random.randint(jax.random.PRNGKey(seed), (n, 6), 0,
                                 eng.api.cfg.vocab)
    rids = [eng.submit(np.asarray(prompts[i]), gen,
                       arrival_step=2 * i).rid for i in range(n)]
    eng.run(max_steps=400)
    return [list(map(int, eng.completed[r].generated)) for r in rids]


class TestZeroCostWhenDisabled:
    def test_traced_run_bit_exact_with_untraced(self, api, params):
        """Acceptance: attaching the tracer changes NOTHING observable —
        tokens, modelled link time, tier accounting."""
        base = ServeEngine(api, params, _cfg(tiers="ddr5:1,cxl:1"))
        toks_base = _drive(base)
        traced = ServeEngine(api, params,
                             _cfg(tiers="ddr5:1,cxl:1", trace=True))
        toks_traced = _drive(traced)
        assert toks_base == toks_traced
        sb, st = base.paging_stats(), traced.paging_stats()
        assert sb["duplex_us"] == st["duplex_us"]
        assert sb["serial_us"] == st["serial_us"]
        assert sb["tiers"] == st["tiers"]
        assert traced.tracer is not None and base.tracer is None

    def test_tracing_adds_no_device_syncs(self, api, params):
        """The span/timeline hooks are host-side list appends: a traced
        megastep still performs exactly one device->host transfer (the
        packed readback) — transfer_guard-enforced."""
        eng = ServeEngine(api, params, _cfg(trace=True))
        prompts = jax.random.randint(jax.random.PRNGKey(24), (3, 6), 0,
                                     api.cfg.vocab)
        for i in range(3):
            eng.submit(np.asarray(prompts[i]), 20)
        eng.megastep(4)      # compile everything outside the guard
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
                eng.megastep(4)
            assert len(syncs) == n + 1          # exactly the readback
        assert len(eng.tracer.spans) > 0        # and it actually traced

    def test_export_disabled_raises(self, api, params):
        eng = ServeEngine(api, params, _cfg())
        with pytest.raises(ValueError, match="disabled"):
            eng.export_trace("/tmp/never.json")


class TestSchema:
    def test_phase_totals_and_duplex_util(self, api, params):
        tr = Tracer()
        eng = ServeEngine(api, params,
                          _cfg(tiers="ddr5:1,cxl:1", trace=tr))
        _drive(eng)
        totals = tr.phase_totals()
        for name in ("plan", "dispatch", "reconcile"):
            assert totals[f"{name}_us"] > 0.0
            assert totals["spans"][name] > 0
        assert set(totals["spans"]) <= set(PHASES)
        util = tr.duplex_util()
        # every configured channel reports, including idle ones
        assert {"ddr5:0", "cxl:1"} <= set(util)
        for u in util.values():
            assert set(u) == {"util", "rd_util", "wr_util", "busy_us",
                              "read_bytes", "write_bytes", "txns"}
            assert 0.0 <= u["util"] <= 1.0 + 1e-9
        assert any(u["txns"] > 0 for u in util.values())
        summ = tr.summary()
        assert set(summ) == {"phase_us", "duplex_util", "model_us",
                             "events", "instants"}
        assert summ["model_us"] > 0.0 and summ["events"] > 0

    def test_metrics_registry_snapshot(self, api, params):
        """engine.metrics() is the one typed view: paging_stats
        flattened to counters/gauges, span histograms when tracing,
        the CAX tree under "cax"."""
        eng = ServeEngine(api, params, _cfg(trace=True))
        _drive(eng)
        snap = eng.metrics()
        assert {"counters", "gauges", "histograms", "trace",
                "cax"} <= set(snap)
        assert snap["counters"]["engine.page_ins"] > 0
        assert "span.plan.us" in snap["histograms"]
        assert snap["histograms"]["span.plan.us"]["count"] > 0
        assert "/serve" in snap["cax"]
        # untraced engines still produce the registry view, minus trace
        eng2 = ServeEngine(api, params, _cfg())
        _drive(eng2, n=3, gen=6)
        snap2 = eng2.metrics()
        assert "trace" not in snap2 and "cax" in snap2

    def test_reset_stats_resets_telemetry(self, api, params):
        eng = ServeEngine(api, params, _cfg())
        _drive(eng)
        before = eng.telemetry.to_dict()
        assert any(v["read_bytes"] or v["write_bytes"]
                   for v in before.values())
        eng.reset_stats()
        after = eng.telemetry.to_dict()
        assert set(after) == set(before)        # scope tree survives
        assert all(v["read_bytes"] == 0.0 and v["write_bytes"] == 0.0
                   for v in after.values())


class TestPerfettoExport:
    def test_round_trip_and_monotonic_tracks(self, api, params,
                                             tmp_path):
        path = str(tmp_path / "trace.json")
        eng = ServeEngine(api, params,
                          _cfg(tiers="ddr5:1,cxl:1", trace=path))
        _drive(eng)
        out = eng.export_trace()
        assert out == path
        doc = json.load(open(path))
        evs = doc["traceEvents"]
        meta = [e for e in evs if e["ph"] == "M"]
        names = {e["args"]["name"] for e in meta
                 if e["name"] == "process_name"}
        assert any("host clock" in n for n in names)
        assert any("modelled clock" in n for n in names)
        # boundary spans live on the host-clock process
        spans = [e for e in evs if e["ph"] == "X"]
        assert {"plan", "dispatch", "reconcile"} <= {
            e["name"] for e in spans}
        # channel busy slices: reconstruct per-(pid, tid) timelines and
        # assert monotonic non-overlap — the modelled-clock guarantee
        by_track = {}
        for e in spans:
            by_track.setdefault((e["pid"], e["tid"]), []).append(
                (e["ts"], e["dur"]))
        for ivals in by_track.values():
            end = -1.0
            for ts, dur in sorted(ivals):
                assert ts >= end - 1e-6, "overlapping intervals"
                end = ts + dur
        # paging slices exist on the modelled-clock process
        thread_meta = {(e["pid"], e["tid"]): e["args"]["name"]
                       for e in meta if e["name"] == "thread_name"}
        chan_tracks = {k for k, n in thread_meta.items()
                       if n.endswith((".rd", ".wr"))}
        assert chan_tracks & set(by_track), "no channel busy slices"
        # one timeline per tier of the channel set
        tracks = sorted(eng.tracer.timelines)
        assert any(t.startswith("ddr5:") for t in tracks), tracks
        assert any(t.startswith("cxl:") for t in tracks), tracks

    def test_fault_instants_in_trace(self, api, params, tmp_path):
        from repro.core.faults import FaultInjector, parse_fault_plan
        eng = ServeEngine(api, params, _cfg(
            tiers="ddr5:1,cxl:1",
            faults=FaultInjector(
                parse_fault_plan("transient:0@2+40=0.4,poison:0@6"),
                seed=0),
            trace=str(tmp_path / "t.json")))
        prompts = jax.random.randint(jax.random.PRNGKey(21), (5, 6), 0,
                                     api.cfg.vocab)
        for i in range(5):
            eng.submit(np.asarray(prompts[i]), 10, arrival_step=2 * i)
        eng.run(max_steps=400)   # poisoned block may fail its owner
        kinds = {name for clock, track, name, _, _ in eng.tracer.instants
                 if track == "faults"}
        assert "transient" in kinds and "poison" in kinds
        doc = json.load(open(eng.export_trace()))
        assert any(e["ph"] == "i" for e in doc["traceEvents"])


class TestShardedTrace:
    @pytest.mark.skipif(DEVICES < 4, reason=(
        "needs 4 devices (run under "
        "XLA_FLAGS=--xla_force_host_platform_device_count=4)"))
    def test_shard_tracks_and_ici_links(self, api, params, tmp_path):
        from repro.launch.mesh import make_debug_mesh
        from repro.serve import ShardedServeEngine
        mesh = make_debug_mesh(2, devices=jax.devices()[:4])
        eng = ShardedServeEngine(
            api, params,
            _cfg(max_batch=4, tiers="ddr5:1,cxl:1",
                 trace=str(tmp_path / "shard.json")),
            mesh=mesh)
        _drive(eng, n=4)
        tracks = set(eng.tracer.timelines)
        # every data rank's channels are namespaced shard<s>/
        for s in range(2):
            assert any(t.startswith(f"shard{s}/") for t in tracks), tracks
        # model-axis collectives billed on their own ici track
        assert any(t.startswith("ici:model") for t in tracks), tracks
        path = eng.export_trace()
        doc = json.load(open(path))
        thread_names = {e["args"]["name"]
                        for e in doc["traceEvents"]
                        if e["ph"] == "M" and e["name"] == "thread_name"}
        assert any(n.startswith("shard0/") for n in thread_names)
        assert any(n.startswith("ici:model") for n in thread_names)


class TestRequestSpans:
    @pytest.mark.parametrize("depth", [1, 2])
    def test_stamps_ordered(self, api, params, depth):
        eng = ServeEngine(api, params, _cfg(pipeline_depth=depth))
        _drive(eng)
        done = list(eng.completed.values())
        assert len(done) == 5
        for r in done:
            assert r.t_submit <= r.t_admit <= r.t_first <= r.t_done, r.rid

    def test_one_span_of_each_kind_per_request(self, api, params, tmp_path):
        tr = Tracer()
        eng = ServeEngine(api, params, _cfg(trace=tr))
        _drive(eng)
        by_rid = {}
        for name, t0, dur, args in tr.request_spans:
            by_rid.setdefault(args["rid"], []).append((name, t0, dur))
        assert set(by_rid) == set(eng.completed)
        for rid, spans in by_rid.items():
            assert [n for n, _, _ in spans] == list(REQUEST_SPANS)
            r = eng.completed[rid]
            assert spans[0][1] == pytest.approx(tr.at_us(r.t_submit))
            # the three spans tile submit..done with no gap
            for (_, a, da), (_, b, _) in zip(spans, spans[1:]):
                assert a + da == pytest.approx(b)
        doc = json.load(open(eng.export_trace(str(tmp_path / "t.json"))))
        begins = [e for e in doc["traceEvents"] if e["ph"] == "b"]
        assert len(begins) == 3 * len(by_rid)
        assert {e["id"] for e in begins} == set(by_rid)


class TestProfilerAnnotations:
    def test_serve_phases_in_profiler_trace(self, api, params, tmp_path):
        from jax.profiler import ProfileData
        eng = ServeEngine(api, params, _cfg(pipeline_depth=2))
        with jax.profiler.trace(str(tmp_path)):
            _drive(eng, n=3, gen=6)
        path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                          recursive=True)
        names = {e.name for plane in ProfileData.from_file(path).planes
                 if plane.name.startswith("/host:")
                 for line in plane.lines for e in line.events}
        assert {"serve.plan", "serve.admit", "serve.dispatch",
                "serve.reconcile", "serve.readback"} <= names

    def test_megastep_scopes_in_op_metadata(self, api, params):
        eng = ServeEngine(api, params, _cfg())
        assert eng.paged
        mega = jax.jit(_megastep_math(api, eng.cfg.prefill_chunk, 2,
                                      eng.cfg.block_tokens))
        text = mega.lower(params, eng.cache, eng._dev).compile().as_text()
        for scope in ("megastep/model", "megastep/writethrough",
                      "megastep/pack"):
            assert f"/{scope}/" in text, scope
