"""Roofline analysis (deliverable g) — three terms per (arch × shape × mesh).

Reads the dry-run artifacts (experiments/dryrun/*.json) and derives, per
cell, for TPU v5e targets:

    compute term    = HLO_FLOPs_per_device / peak_FLOPs          [s]
    memory term     = HLO_bytes_per_device / HBM_bw              [s]
    collective term = collective_bytes_per_device / link_bw      [s]

plus MODEL_FLOPS = 6·N·D (train) / 2·N·D (prefill) / 2·N_active·B (decode),
the useful-compute ratio MODEL_FLOPS/HLO_FLOPs, the dominant term, the
bound-MFU (useful compute time / dominant term), and a rule-based
what-would-move-it note.

Methodology notes (also in EXPERIMENTS.md):
  * cost_analysis() describes the per-device SPMD module — global FLOPs =
    per-device × n_devices; the spec's formula FLOPs/(chips×peak) therefore
    reduces to per-device/peak.
  * 'bytes accessed' counts operand+result bytes per HLO op (pre-fusion
    semantics on the CPU backend) — an upper bound on HBM traffic.
  * collective bytes are post-SPMD result-shape bytes (consistent across
    §Perf iterations); rolled time-scan FLOPs are re-added analytically
    (``recurrence_flops``).
"""

from __future__ import annotations

import glob
import json
import os
import time

from bench.peaks import PEAKS

from benchmarks.common import Bench, write_csv

PEAK_FLOPS = PEAKS["TPU v5 lite"]["bf16_flops"]
HBM_BW = PEAKS["TPU v5 lite"]["hbm_bytes_s"]
LINK_BW = 50e9             # bytes/s / link (ICI, per direction)

DRYRUN_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments",
                          "dryrun")


def analyse(rec: dict) -> dict | None:
    if rec.get("status") != "ok":
        return None
    # cells compiled with rolled layer scans are sharding/memory proofs;
    # their cost columns undercount by ~num_layers and are flagged.
    rolled = not rec.get("unroll", True)
    n = rec["n_devices"]
    flops_dev = rec["cost_analysis"].get("flops", 0.0) \
        + rec.get("recurrence_flops", 0.0) / n
    bytes_dev = rec["cost_analysis"].get("bytes accessed", 0.0)
    coll_dev = rec["collectives"]["total_bytes"]
    compute_s = flops_dev / PEAK_FLOPS
    memory_s = bytes_dev / HBM_BW
    coll_s = coll_dev / LINK_BW
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": coll_s}
    dominant = max(terms, key=terms.get)
    useful_s = rec["model_flops"] / n / PEAK_FLOPS
    bound = max(terms.values())
    mfu_bound = useful_s / bound if bound > 0 else 0.0
    flops_ratio = rec["model_flops"] / max(flops_dev * n, 1.0)

    note = {
        "compute": ("reduce non-useful FLOPs (masked attention blocks, "
                    "remat recompute) or shard compute further"),
        "memory": ("fuse/keep activations in VMEM, shrink dtype, or "
                   "re-tile to raise arithmetic intensity"),
        "collective": ("re-shard to cut resharding, overlap collectives "
                       "with compute, or compress (bf16/int8) payloads"),
    }[dominant]
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "devices": n, "rolled": rolled,
        "compute_s": compute_s, "memory_s": memory_s,
        "collective_s": coll_s, "dominant": dominant,
        "model_flops": rec["model_flops"],
        "useful_ratio": flops_ratio, "mfu_bound": mfu_bound,
        "note": note,
    }


def load_all(mesh: str | None = None) -> list[dict]:
    out = []
    for path in sorted(glob.glob(os.path.join(DRYRUN_DIR, "*.json"))):
        if ".pre_" in path or ".iter" in path:
            continue          # §Perf before/after snapshots, not baselines
        with open(path) as f:
            rec = json.load(f)
        if mesh and rec.get("mesh") != mesh:
            continue
        row = analyse(rec)
        if row:
            out.append(row)
    return out


def run(smoke: bool = False) -> Bench:
    b = Bench("roofline")
    t0 = time.monotonic()
    rows = load_all()
    us = (time.monotonic() - t0) * 1e6
    csv_rows = [[r["arch"], r["shape"] + (" (rolled)" if r["rolled"]
                                           else ""), r["mesh"], r["devices"],
                 f"{r['compute_s']:.3e}", f"{r['memory_s']:.3e}",
                 f"{r['collective_s']:.3e}", r["dominant"],
                 f"{r['useful_ratio']:.3f}", f"{r['mfu_bound']:.3f}"]
                for r in rows]
    write_csv("roofline.csv",
              ["arch", "shape", "mesh", "devices", "compute_s",
               "memory_s", "collective_s", "dominant", "useful_ratio",
               "mfu_bound"], csv_rows)
    pod = [r for r in rows if r["mesh"] == "pod" and not r["rolled"]]
    by_dom = {}
    for r in pod:
        by_dom[r["dominant"]] = by_dom.get(r["dominant"], 0) + 1
    worst = min(pod, key=lambda r: r["mfu_bound"]) if pod else None
    b.row("cells-analysed", us, f"{len(rows)} records "
          f"(pod dominant-term histogram: {by_dom})")
    if worst:
        b.row("worst-mfu-bound", 0.0,
              f"{worst['arch']}×{worst['shape']}: "
              f"mfu_bound={worst['mfu_bound']:.3f} ({worst['dominant']})")

    return b.done(f"{len(rows)} cells -> experiments/bench/roofline.csv")


if __name__ == "__main__":
    print(run().render())
