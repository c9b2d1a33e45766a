"""The readers of the engine's request stamps: on synthetic records, and
on the tiny chat cell served traced on the CPU, where each request's time
to first token splits into the engine's waits with no gap."""

import statistics
import types

import pytest

from bench import run as R
from bench.metrics import _request_stamps as request_stamps
from bench.metrics import (boundary_wait_p95_ms, prefill_p95_ms,
                           queue_wait_p95_ms)
from bench.serve_loop import Record
from bench_fixtures import CPU_PEAKS, register_tiny, tiny_cell

READERS = (boundary_wait_p95_ms, queue_wait_p95_ms, prefill_p95_ms)


@pytest.fixture(autouse=True)
def tiny_harness(monkeypatch):
    monkeypatch.setattr(R, "set_compile_cache", lambda: None)
    register_tiny(monkeypatch)


def stamped(due, submit, admit, first):
    req = types.SimpleNamespace(t_submit=submit, t_admit=admit,
                                t_first=first)
    return Record(due=due, prompt_len=8, max_new=4, request=req)


def fake_run(records, t0=0.0, seconds=100.0, **slice_):
    window = types.SimpleNamespace(
        records=records,
        measured=lambda: [r for r in records if t0 <= r.due <= t0 + seconds],
        t_end=t0 + seconds, **slice_)
    return types.SimpleNamespace(window=window)


def test_readers_on_synthetic_records():
    # boundary waits 0.1 s x 19 and 1.1 s; queue 0.2 s; prefill 2 s
    recs = [stamped(i, i + 0.1, i + 0.3, i + 2.3) for i in range(19)]
    recs.append(stamped(50.0, 51.1, 51.3, 53.3))
    run = fake_run(recs)
    # p95 of 19 x 100 ms and one 1100 ms, linear between ranks
    assert boundary_wait_p95_ms.read(run) == pytest.approx(100 + 0.05 * 1000)
    assert queue_wait_p95_ms.read(run) == pytest.approx(200.0)
    assert prefill_p95_ms.read(run) == pytest.approx(2000.0)


def test_readers_skip_what_has_no_stamp():
    recs = [stamped(1.0, 1.5, None, None),           # still queued
            stamped(2.0, 2.25, 2.5, None),           # still prefilling
            Record(due=3.0, prompt_len=8, max_new=4, refused=True),
            stamped(500.0, 501.0, 502.0, 503.0)]      # due after the window
    run = fake_run(recs)
    assert boundary_wait_p95_ms.read(run) == pytest.approx(
        250 + 0.95 * (500 - 250))
    assert queue_wait_p95_ms.read(run) == pytest.approx(250.0)
    assert prefill_p95_ms.read(run) is None


def test_quiet_leaves_out_what_the_traced_slice_touched():
    # profiler on at 10 s for 2 s; its stop stalls the loop until 20 s
    a = stamped(1.0, 1.1, 1.2, 3.0)        # served before the slice
    b = stamped(5.0, 5.1, 5.2, 11.0)       # first token after the start
    c = stamped(6.0, 6.1, 6.2, 8.0)        # short, but due after b
    d = stamped(12.5, 20.0, 21.0, 25.0)    # due in the stall
    e = stamped(19.0, 20.001, 21.0, 24.0)  # submitted behind d's backlog
    f = stamped(22.0, 22.5, 22.6, 26.0)    # every earlier one had a slot
    g = stamped(30.0, 30.1, 30.2, 33.0)
    run = fake_run([a, b, c, d, e, f, g], _slice_t=10.0,
                   trace_slice=(10.0, 2.0, None, None))
    assert request_stamps.quiet(run.window) == [a, f, g]
    # a prefill p95 over a, f, g alone: 1.8, 3.4 and 2.8 s
    assert prefill_p95_ms.read(run) == pytest.approx(
        2800 + 0.9 * (3400 - 2800))


def test_quiet_reads_every_request_without_a_slice():
    recs = [stamped(i, i + 0.1, i + 0.3, i + 2.3) for i in range(5)]
    assert request_stamps.quiet(fake_run(recs).window) == recs


@pytest.mark.parametrize("reader", READERS)
def test_reader_returns_none_for_an_engine_without_stamps(reader):
    # an engine that predates the stamps: its Request has no t_* fields
    req = types.SimpleNamespace(rid=1)
    run = fake_run([Record(due=1.0, prompt_len=8, max_new=4, request=req)])
    assert reader.read(run) is None


def test_ttft_splits_into_the_engines_waits():
    cell = tiny_cell()
    served = R.prepare(cell, 11, require_accelerator=False, peaks=CPU_PEAKS)
    window, _ = R.serve(served, cell.traffic, 11, 2.0, True)
    # every host time at which the window received tokens
    delivered = sorted({t for r in window.records for t, _ in r.deliveries})
    gaps = {}
    for r in window.measured():
        q = r.request
        if q is None or r.first is None:
            continue
        pieces = (q.t_submit - r.due, q.t_admit - q.t_submit,
                  q.t_first - q.t_admit, r.first - q.t_first)
        assert all(p >= 0 for p in pieces), pieces
        assert sum(pieces) == pytest.approx(r.first - r.due)
        # t_first is stamped by the reconcile that delivered the token:
        # after every earlier delivery, and one reading per reconcile
        earlier = [t for t in delivered if t < r.first]
        assert not earlier or q.t_first > earlier[-1], pieces
        assert gaps.setdefault(r.first, pieces[3]) == pieces[3]
    assert gaps
    # the stamp and the delivery are a few lines apart; the median keeps
    # a garbage collection or a preempted thread out of the bound
    assert statistics.median(gaps.values()) < 5e-3, sorted(gaps.values())
