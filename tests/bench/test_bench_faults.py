"""The comparison that decides ``correct`` catches what it must, on the CPU
at a tiny size: a run with the timed path broken underneath reads
``correct`` false, and the float8 control reads outside the limits that
the program's own readings keep inside."""

import numpy as np
import pytest

from bench import control, run as R
from bench.serve_loop import BenchEngine
from bench_fixtures import CPU_PEAKS, TINY_LIMITS, register_tiny, tiny_cell
from repro.serve import kv_pool


@pytest.fixture(autouse=True)
def tiny_harness(monkeypatch):
    monkeypatch.setattr(R, "set_compile_cache", lambda: None)
    register_tiny(monkeypatch)


def run_tiny(engine_factory=None, loop="open"):
    return R.run_cell(tiny_cell(loop), 9, 2.0, False,
                      require_accelerator=False, peaks=CPU_PEAKS,
                      engine_factory=engine_factory)


class AlteredTokens(BenchEngine):
    """Every token the megastep hands back is changed where it is read
    off the device: what the host serves is not what the model chose."""

    def _readback(self, packed):
        rb = np.array(super()._readback(packed))
        if rb.shape[1] > 3:
            rb[:, 3:] = (rb[:, 3:] + 1) % self.api.cfg.vocab
        return rb


def test_sound_run_is_correct():
    res = run_tiny()
    assert res["correct"] is True


def test_altered_tokens_fail():
    res = run_tiny(AlteredTokens)
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] > TINY_LIMITS["logit_gap"]


def test_altered_pool_blocks_fail(monkeypatch):
    real = kv_pool._write_blocks_at

    def scaled(hbm, dst, staged, t):
        # the write-through stores every block half again as large
        return real(hbm, dst, staged * 1.5, t)

    monkeypatch.setattr(kv_pool, "_write_blocks_at", scaled)
    res = run_tiny(loop="closed")
    assert res["correct"] is False
    assert res["checks"]["kv_err"]["value"] > TINY_LIMITS["kv_err"]


def test_float8_control_reads_outside_the_limits():
    cell = tiny_cell("closed")
    program, ctl, _ = control.readings_for_seed(
        cell, 11, 2.0, require_accelerator=False, peaks=CPU_PEAKS)
    for name, limit in TINY_LIMITS.items():
        assert program[name] <= limit
    # the control has to fail one of the numbers, by a wide margin
    worst = max(ctl[n] / TINY_LIMITS[n] for n in TINY_LIMITS)
    assert worst > 1.0
    assert max(ctl[n] / program[n] for n in TINY_LIMITS) >= 3.0
