"""``BENCHMARK.json`` keeps to the form its readers rely on, and every cell
finds its configuration, traffic and metric readers by name."""

import importlib
import json
import re
from pathlib import Path

import pytest

from bench.spec import load_cell

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def applies(m, cell):
    return "workloads" not in m or cell in m["workloads"]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][:2] == ["python3", "bench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
        assert not p.startswith("/") and ".." not in p.split("/")
        assert (ROOT / p).is_dir()


def test_configs():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"])
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert all(k in cfg for k in c["reduced"])
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])


def test_workloads():
    pairs = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    assert len(set(CELLS)) == len(CELLS)


def test_metrics():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        for cell in m.get("workloads", CELLS):
            assert applies(e2e[m["moves"]], cell)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= set(CELLS)
        mod = importlib.import_module("bench.metrics." + m["name"])
        assert callable(mod.read)
    for m in BENCH["per_layer"]:
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_loads_and_reports_enough(cell):
    c = load_cell(cell)
    names = [m["name"] for m in c.end_to_end]
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    assert c.config["engine"]["pipeline_depth"] == 2
    assert set(c.config["check"]["limits"]) <= {"logit_gap", "kv_err"}


# the metrics each cell reported before the five that every cell reports
# lost their ``workloads`` lists; a cell added later reports those five
# with no edit to their entries
PER_LAYER = {
    "smollm135m-chat": [
        "host_ms_per_step", "megastep_device_ms_per_step", "step_mfu",
        "paging_device_ms_per_step", "megastep_hbm_roofline",
        "device_idle_share", "boundary_wait_p95_ms", "queue_wait_p95_ms",
        "prefill_p95_ms"],
    "stablelm3b-decode": [
        "host_ms_per_step", "megastep_device_ms_per_step", "step_mfu",
        "megastep_hbm_roofline", "device_idle_share"],
}


@pytest.mark.parametrize("cell", sorted(PER_LAYER))
def test_each_cell_reports_the_same_per_layer_metrics(cell):
    assert [m["name"] for m in load_cell(cell).per_layer] == PER_LAYER[cell]
