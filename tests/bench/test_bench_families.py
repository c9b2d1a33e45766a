"""A configuration names its model family in ``program.family``, and the
harness builds, weighs and counts through that module alone: a family
added as a new module is taken up with no edit to the harness."""

import dataclasses
import sys
import types

import pytest

from bench import run as R
from bench import trace_reduce
from bench.families import dense_lm
from bench.spec import SpecError
from bench_fixtures import CPU_PEAKS, register_tiny, tiny_cell, tiny_config

#: what a family module provides, each of which the harness must call
FAMILY_API = ("sizes", "build", "make_params", "weight_bytes",
              "context_kv_bytes", "positions_flops")


@pytest.fixture(autouse=True)
def tiny_harness(monkeypatch):
    monkeypatch.setattr(R, "set_compile_cache", lambda: None)
    register_tiny(monkeypatch)


def stub_family(calls):
    """``bench.families.stub_lm``: the dense family at the tiny widths,
    recording each call the harness makes."""
    stub = types.ModuleType("bench.families.stub_lm")
    for name in FAMILY_API:
        def recorded(*args, _name=name, **kw):
            calls.append(_name)
            return getattr(dense_lm, _name)(*args, **kw)
        setattr(stub, name, recorded)
    return stub


def with_a_device(load):
    """The CPU's trace holds no device plane: give it one whose megastep
    program spans the traced slice, so the device readers read."""
    def loaded(log_dir):
        t = load(log_dir)
        s0, s1 = t.window
        t.ops = [[("fusion.1", s0, s1 - s0)]]
        t.modules = [[("jit_mega(1)", s0, s1 - s0)]]
        return t
    return loaded


def test_a_new_family_is_a_new_module(monkeypatch):
    calls = []
    monkeypatch.setitem(sys.modules, "bench.families.stub_lm",
                        stub_family(calls))
    monkeypatch.setattr(trace_reduce, "load",
                        with_a_device(trace_reduce.load))
    cfg = tiny_config(paging=False)
    cfg["program"]["family"] = "stub_lm"
    cell = dataclasses.replace(tiny_cell("closed", paging=False),
                               config=cfg)
    res = R.run_cell(cell, 2 ** 31 + 13, 3.0, True,
                     require_accelerator=False, peaks=CPU_PEAKS)
    assert res["correct"] is True
    assert set(FAMILY_API) <= set(calls)
    assert {"megastep_hbm_roofline", "step_mfu"} <= set(res["metrics"])


def test_a_configuration_must_name_its_family():
    cfg = tiny_config()
    del cfg["program"]["family"]
    with pytest.raises(SpecError, match="program.family"):
        R.build_model(cfg)


def test_an_unknown_family_is_refused():
    cfg = tiny_config()
    cfg["program"]["family"] = "no_such_family"
    with pytest.raises(SpecError, match="no_such_family"):
        R.build_model(cfg)
