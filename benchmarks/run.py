"""Paper-figure runner — one module per paper table/figure.

Prints ``name,provenance,us_per_call,derived`` CSV rows (run.py contract)
and writes per-figure CSVs under experiments/bench/. The ``provenance``
column separates rows that drive the real engine (``engine``: ServeEngine /
Pallas kernels; functional execution real, link timing modelled) from
analytical stream-simulator numbers (``sim``: ``core.scheduler``). The
figures are modelled; serving speed is measured on the chip by
``bench/run.py`` (see ``BENCHMARK.json`` and ``PERF.md``).

  PYTHONPATH=src python -m benchmarks.run [--only characterization,...]
                                          [--smoke]
"""

from __future__ import annotations

import argparse
import inspect
import sys
import traceback

from benchmarks.common import out_dir
from repro.launch.compile_cache import enable_compile_cache

MODULES = ("characterization", "microbench", "redis_like",
           "llm_inference", "vectordb", "tiered_memory", "roofline")


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--only", default=None,
                   help="comma-separated subset of: " + ",".join(MODULES))
    p.add_argument("--smoke", action="store_true",
                   help="tiny step counts (CI smoke mode) for modules "
                        "that support it")
    args = p.parse_args()
    todo = args.only.split(",") if args.only else list(MODULES)
    unknown = [n for n in todo if n not in MODULES]
    if unknown:
        p.error(f"unknown benchmark modules {unknown}; "
                f"choose from {','.join(MODULES)}")

    enable_compile_cache()
    # create experiments/bench/ up front so a missing output directory can
    # never surface as a module failure mid-run.
    out_dir()

    failed: list[str] = []
    print("name,provenance,us_per_call,derived")
    for name in todo:
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            kwargs = {}
            if args.smoke and \
                    "smoke" in inspect.signature(mod.run).parameters:
                kwargs["smoke"] = True
            bench = mod.run(**kwargs)
            sys.stdout.write(bench.render())
            sys.stdout.flush()
        except Exception:                      # noqa: BLE001
            failed.append(name)
            print(f"{name},error,0,ERROR")
            traceback.print_exc()
    if failed:
        print(f"benchmark modules failed: {','.join(failed)}",
              file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
