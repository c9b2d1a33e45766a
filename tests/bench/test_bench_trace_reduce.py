"""The reduction from a profiler trace to busy time, program time and idle
gaps, on a small synthetic trace (nanoseconds)."""

import pytest

from bench import trace_reduce as tr
from bench.metrics import (device_idle_share, megastep_device_ms_per_step,
                           paging_device_ms_per_step)


def synthetic():
    ops = [[("%fusion.1 = bf16[8] fusion(...)", 0.0, 10.0),
            ("%fusion.2 = bf16[8] fusion(...)", 5.0, 10.0),
            ("%copy.3 = bf16[8] copy(...)", 30.0, 5.0),
            ("%fusion.1 = bf16[8] fusion(...)", 48.0, 10.0)]]
    modules = [[("jit_mega(123)", 0.0, 15.0),
                ("jit__commit_paging(456)", 30.0, 5.0),
                ("jit_mega(123)", 48.0, 10.0)]]
    host = [("bench.slice", 0.0, 50.0), ("bench.plan", 15.0, 10.0),
            ("bench.reconcile", 35.0, 15.0), ("bench.readback", 40.0, 5.0)]
    return tr.Trace(ops=ops, modules=modules, host=host, window=(0.0, 50.0))


def test_clip_and_union():
    evs = [("a", -5.0, 10.0), ("b", 3.0, 4.0), ("c", 20.0, 10.0),
           ("d", 60.0, 1.0)]
    assert tr.clip(evs, 0.0, 25.0) == [("a", 0.0, 5.0), ("b", 3.0, 4.0),
                                       ("c", 20.0, 5.0)]
    assert tr.union_ns(tr.clip(evs, 0.0, 25.0)) == 12.0
    assert tr.union_ns([]) == 0.0


def test_busy_gaps_and_idle_share():
    t = synthetic()
    # busy: [0, 15) + [30, 35) + [48, 50) inside the window
    assert tr.busy_s(t) == pytest.approx(22e-9)
    assert tr.gaps(t.ops[0], 0.0, 50.0) == [(15.0, 15.0), (35.0, 13.0)]
    assert t.window_s == pytest.approx(50e-9)

    class Run:
        trace = t

    assert device_idle_share.read(Run) == pytest.approx(100 * 28 / 50)


def test_program_time_by_name():
    t = synthetic()
    assert tr.program_s(t, megastep_device_ms_per_step.match) == \
        pytest.approx(17e-9)
    assert tr.program_s(t, paging_device_ms_per_step.match) == \
        pytest.approx(5e-9)

    class Run:
        trace = t
        slice_steps = 2

    assert megastep_device_ms_per_step.read(Run) == pytest.approx(17e-9
                                                                 * 1e3 / 2)


def test_top_ops_and_idle_by_phase():
    t = synthetic()
    assert tr.op_name("%fusion.1 = bf16[8] fusion(...)") == "fusion.1"
    top = tr.top_ops(t)
    assert top[0] == ["fusion.1", pytest.approx(12e-9)]
    assert [n for n, _ in top] == ["fusion.1", "fusion.2", "copy.3"]
    # the first gap's midpoint lies in plan; the second's in readback,
    # which reconcile also covers: the innermost span wins
    idle = dict((k, v) for k, v in tr.idle_by_phase(t))
    assert idle == {"plan": pytest.approx(15e-9),
                    "readback": pytest.approx(13e-9)}


def test_a_reader_with_nothing_to_read_returns_none():
    class Run:
        trace = tr.Trace(ops=[], modules=[], host=[], window=(0.0, 1.0))
        slice_steps = 4

    assert device_idle_share.read(Run) is None
    assert megastep_device_ms_per_step.read(Run) is None
