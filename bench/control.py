"""The readings a cell's limits are set from: the program's and the control's.

  python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3

For each seed, in one process: set the cell up with that seed's weights,
serve a window of its traffic, draw the sample the benchmark compares,
and read every compared number twice: for the program, against the plain
reference in float32, and for the control, the reference computed in
float8 (``quant="fp8"``) in the program's place. One JSON line per seed,
then one with the program's largest and the control's smallest reading
of each number. The benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def readings_for_seed(cell, seed: int, seconds: float, *, model=None,
                      require_accelerator: bool = True, peaks=None):
    """Serve one window and read the program and the control."""
    from bench import check, run as R
    served = R.prepare(cell, seed, model=model,
                       require_accelerator=require_accelerator, peaks=peaks)
    model = (served.api, served.sizes)
    window, _ = R.serve(served, cell.traffic, seed, seconds, False)
    bt = served.ecfg.block_tokens
    sample = check.draw(window, served.engine, seed, bt)
    params, sz = served.params, served.sizes
    del served, window
    check.free_device_memory()
    program = check.readings(cell.config, params, sz, sample, bt)
    control = check.readings(cell.config, params, sz, sample, bt,
                             quant="fp8")
    return program, control, model


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import run as R
    from bench.spec import load_cell

    cell = load_cell(args.workload)
    names = list(cell.config["check"]["limits"])
    worst = {n: 0.0 for n in names}
    best = {n: float("inf") for n in names}
    model = None
    for seed in args.seeds:
        try:
            program, control, model = readings_for_seed(
                cell, seed, args.seconds, model=model)
        except R.NoAccelerator as e:
            print(f"control: {e}", file=sys.stderr)
            return 3
        print(json.dumps({"seed": seed, "program": program,
                          "control": control}), flush=True)
        for n in names:
            worst[n] = max(worst[n], program[n])
            best[n] = min(best[n], control[n])
    print(json.dumps({"program_max": worst, "control_min": best,
                      "limits": cell.config["check"]["limits"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
