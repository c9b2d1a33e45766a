"""Shared helpers for the benchmark harness."""

from __future__ import annotations

import csv
import io
import json
import os
import time

#: Row provenance labels: ``engine`` rows drive the real ServeEngine /
#: kernels (functional execution real, link timing modelled); ``sim`` rows
#: come from the analytical stream simulator (``core.scheduler``). run.py's
#: CSV carries the label per row so the two are never conflated.
ENGINE, SIM = "engine", "sim"


class Bench:
    """Collects rows and renders the run.py CSV contract:
    ``name,provenance,us_per_call,derived``."""

    def __init__(self, name: str, provenance: str = SIM):
        self.name = name
        self.provenance = provenance
        self.rows: list[tuple[str, str, float, str]] = []
        self._t0 = time.monotonic()

    def row(self, sub: str, us: float, derived: str,
            provenance: str | None = None):
        self.rows.append((f"{self.name}/{sub}",
                          provenance or self.provenance, us, derived))

    def done(self, derived: str = ""):
        total_us = (time.monotonic() - self._t0) * 1e6
        self.rows.append((self.name, self.provenance, total_us, derived))
        return self

    def render(self) -> str:
        buf = io.StringIO()
        w = csv.writer(buf)
        for name, provenance, us, derived in self.rows:
            w.writerow([name, provenance, f"{us:.1f}", derived])
        return buf.getvalue()


def out_dir() -> str:
    d = os.path.join(os.path.dirname(__file__), "..", "experiments",
                     "bench")
    os.makedirs(d, exist_ok=True)
    return d


def write_csv(fname: str, header: list[str], rows: list[list]):
    path = os.path.join(out_dir(), fname)
    with open(path, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(header)
        w.writerows(rows)
    return path


def aggregate_link_stats(stats: dict, prefix: str) -> dict:
    """Sum a tenant's hint scopes out of ``paging_stats()["by_path"]``."""
    agg = {"duplex_us": 0.0, "serial_us": 0.0, "page_ins": 0,
           "page_outs": 0, "fused_calls": 0}
    for path, st in stats["by_path"].items():
        if path.startswith(prefix):
            for k in agg:
                agg[k] += st[k]
    return agg


def bench_json_path() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                        "BENCH_serve.json")


def update_bench_json(section: str, payload: dict) -> dict:
    """Read-modify-write one workload's section of ``BENCH_serve.json``.

    The file holds the paper-figure A/Bs of the tenant and tier modules,
    one section per module (``redis``, ``vectordb``, ``tiered``). Every
    number in it is modelled (link time, engine steps), so it is the same
    on any machine; serving speed is measured on the chip by
    ``bench/run.py`` and recorded nowhere in this file.
    """
    path = bench_json_path()
    doc: dict = {}
    if os.path.exists(path):
        with open(path) as f:
            doc = json.load(f)
    doc[section] = payload
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    return doc
