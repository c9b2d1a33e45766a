"""The benchmark's engine hooks change nothing the engine computes, and
refuse to run where the engine they hook has changed shape."""

import numpy as np
import pytest

from bench import run as R
from bench import serve_loop
from bench.serve_loop import BenchEngine, HookError, check_engine
from bench_fixtures import CPU_PEAKS, register_tiny, tiny_cell
from repro.serve import EngineConfig, ServeEngine


@pytest.fixture(autouse=True)
def tiny_harness(monkeypatch):
    monkeypatch.setattr(R, "set_compile_cache", lambda: None)
    register_tiny(monkeypatch)


def test_tokens_equal_an_unhooked_engine_on_the_same_schedule():
    cell = tiny_cell("closed")
    served = R.prepare(cell, 3, require_accelerator=False, peaks=CPU_PEAKS)
    window, _ = R.serve(served, cell.traffic, 3, 1.5, False)
    done = [w for w in window.records
            if w.request is not None and w.finished]
    assert done
    plain = ServeEngine(served.api, served.params,
                        EngineConfig(**cell.config["engine"]))
    step0 = min(w.request.arrival_step for w in done)
    replay = [(w, plain.submit(w.request.prompt, w.max_new,
                               arrival_step=w.request.arrival_step - step0))
              for w in done]
    out = plain.run()
    for w, r in replay:
        assert np.array_equal(out[r.rid], np.asarray(w.request.generated))


def test_missing_engine_method_is_refused():
    class Gone:
        pass

    with pytest.raises(HookError):
        check_engine(Gone)


def test_changed_signature_is_refused():
    class Changed(ServeEngine):
        def _reconcile(self, rec, extra):
            return None

    with pytest.raises(HookError):
        check_engine(Changed)
    check_engine(BenchEngine)
    assert serve_loop.HOOKED["_reconcile"] == ("self", "rec")
