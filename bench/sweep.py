"""Find an open-loop cell's knee: serve its traffic at several fixed rates.

  python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
      --rates 2 4 6 8

One process sets the cell up once, then serves a window at each rate in
turn (the traffic file with ``rate_per_s`` replaced), finishing every
request between windows. One JSON line per rate: the rate offered, the
tokens per second delivered in the window, time to first token, and how
many requests were waiting or live when the window closed. The knee is
the highest rate at which nothing piles up. Used once to fix a cell's
rate; the benchmark's own runs never sweep.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import run as R
    from bench.serve_loop import percentile, tokens_in_window
    from bench.spec import load_cell

    cell = load_cell(args.workload)
    try:
        served = R.prepare(cell, args.seed)
    except R.NoAccelerator as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 3
    for rate in args.rates:
        spec = dict(cell.traffic, rate_per_s=rate)
        window, stopped = R.serve(served, spec, args.seed, args.seconds,
                                  False)
        engine = served.engine
        due = window.measured()
        ttft = [((r.first or stopped) - r.due) * 1e3 for r in due]
        backlog = sum(1 for r in due if r.request is not None
                      and not r.finished)
        print(json.dumps({
            "rate_per_s": rate, "due": len(due),
            "refused": sum(r.refused for r in due),
            "tok_s": tokens_in_window(window) / args.seconds,
            "ttft_p50_ms": percentile(ttft, 50) if ttft else None,
            "ttft_p95_ms": percentile(ttft, 95) if ttft else None,
            "unfinished_at_stop": backlog,
            "steps_per_s": window.steps / args.seconds}), flush=True)
        engine.run(max_steps=1 << 40)        # finish what is left
    return 0


if __name__ == "__main__":
    sys.exit(main())
