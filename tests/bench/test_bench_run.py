"""A whole run of the harness on the CPU at a tiny size, past its look for
a chip: the shape of the result line, and the refusal to measure off a
TPU."""

import json

import pytest

from bench import run as R
from bench_fixtures import (CPU_PEAKS, ROOT, register_tiny, tiny_cell,
                            tiny_config)


@pytest.fixture(autouse=True)
def tiny_harness(monkeypatch):
    # the harness points JAX's persistent cache into the checkout; a test
    # must leave the process's JAX settings as it found them. The tiny
    # model stands in the program's registry for smollm-135m.
    monkeypatch.setattr(R, "set_compile_cache", lambda: None)
    register_tiny(monkeypatch)


def run_tiny(loop="open", trace=False, seed=5, seconds=2.0, paging=True,
             **kw):
    return R.run_cell(tiny_cell(loop, paging=paging), seed, seconds, trace,
                      require_accelerator=False, peaks=CPU_PEAKS, **kw)


def test_untraced_result_line():
    res = run_tiny()
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    cell = tiny_cell()
    want = {m["name"]: m["unit"] for m in cell.end_to_end}
    assert {k: v["unit"] for k, v in res["metrics"].items()} == want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    dev = res["device"]
    assert set(dev) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert dev["count"] == 1
    for name, c in res["checks"].items():
        assert set(c) == {"value", "limit"}
        assert c["value"] <= c["limit"]
    json.loads(json.dumps(res))           # one plain JSON object


def test_traced_result_line_closed_loop_unpaged():
    res = run_tiny("closed", trace=True, seconds=3.0, paging=False)
    assert res["correct"] is True
    assert set(res["checks"]) == {"logit_gap"}
    # a reader that finds nothing to read is left out of the line
    assert "paging_device_ms_per_step" not in res["metrics"]
    names = {m["name"] for m in tiny_cell("closed").per_layer}
    assert set(res["metrics"]) <= names
    assert "host_ms_per_step" in res["metrics"]
    assert "step_mfu" in res["metrics"]
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(res)[-1] == "checks"


def test_refuses_to_measure_off_a_tpu(capsys, monkeypatch):
    monkeypatch.chdir(ROOT)
    rc = R.main(["--workload", "smollm135m-chat", "--seed", "1",
                 "--seconds", "1"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "TPU" in out.err


def test_configuration_must_match_the_programs_widths():
    cfg = tiny_config()
    cfg["intermediate_size"] += 1
    with pytest.raises(ValueError, match="d_ff"):
        R.build_model(cfg)


def test_unknown_cell_is_refused():
    from bench.spec import SpecError, load_cell
    with pytest.raises(SpecError):
        load_cell("no-such-cell")
