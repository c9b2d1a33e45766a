"""Run one benchmark cell on the chip and print one JSON result line.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration and
a traffic mix. Set-up makes the weights on the device from the seed,
builds ``ServeEngine`` as the configuration states, and compiles every
program the window runs (``bench/warmup.py``). The window then drives
``ServeEngine.run()`` with the traffic on the wall clock for
``--seconds`` (``bench/serve_loop.py``). With ``--trace 0`` the result
holds the cell's end-to-end metrics; with ``--trace 1`` a slice of the
window is traced by the profiler and the result holds its per-layer
metrics, ``busy_s``/``window_s`` and a breakdown. Either way, once the
window has closed, a sample of what it served is compared with the
configuration's plain reference (``bench/check.py``) and ``correct``
says whether every number is within its limit; the numbers and limits
are printed last on standard error and last in the result line.

Runs on a TPU only: elsewhere, or with fewer chips than the cell asks
for, it exits nonzero and prints no result. JAX's persistent compilation
cache lives at ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

# ruff: noqa: E402  (the clock starts before the imports it measures)
import argparse
import dataclasses
import importlib
import json
import math
import os
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class RunData:
    """What a run measured, for the metric readers in ``bench/metrics``."""
    seconds: float
    setup_s: float
    window: object            # serve_loop.Window
    tokens_in_window: int
    stopped: float            # host time the run stopped serving
    pool_stats: dict          # the pool's own counters (paging_stats)
    sizes: object             # the family's sizes
    family: object            # the family module (bench/families)
    peaks: dict
    chips: int
    trace: object = None      # trace_reduce.Trace of the traced slice
    slice_boundaries: list = dataclasses.field(default_factory=list)

    @property
    def slice_steps(self) -> int:
        return sum(b.k for b in self.slice_boundaries)


def read_metric(name: str, run: RunData):
    mod = importlib.import_module(
        "bench.metrics." + name.replace(".", "_").replace("-", "_"))
    return mod.read(run)


def set_compile_cache() -> None:
    CACHE_DIR.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def devices_for(chips: int, require_accelerator: bool):
    import jax
    devs = jax.devices()
    if require_accelerator:
        if devs[0].platform != "tpu":
            raise NoAccelerator(f"found {devs[0].platform} "
                                f"({devs[0].device_kind}), not a TPU")
        if len(devs) < chips:
            raise NoAccelerator(f"the cell needs {chips} chips, found "
                                f"{len(devs)}")
    return devs[:chips]


def family_module(config: dict):
    """The model family module (``bench/families/<name>.py``) that the
    configuration names in ``program.family``."""
    from bench.spec import SpecError
    name = config.get("program", {}).get("family")
    if name is None:
        raise SpecError("the configuration file names no model family "
                        "(program.family)")
    module = f"bench.families.{name}"
    try:
        return importlib.import_module(module)
    except ModuleNotFoundError as e:
        if e.name != module:
            raise
        raise SpecError(f"no model family {name!r} (program.family): "
                        f"bench/families has no {name}.py") from None


def _model(config: dict):
    """The configuration's family, the program's model API that the
    family builds and checks against the program's own entry, and the
    family's sizes."""
    family = family_module(config)
    sz = family.sizes(config)
    return family, family.build(config, sz), sz


def build_model(config: dict):
    """The program's model API for the configuration, and its sizes."""
    _, api, sz = _model(config)
    return api, sz


def check_tree(api, params) -> None:
    """The weights must be the tree, shapes and types the program's own
    ``init`` makes."""
    import jax
    want = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got):
        raise ValueError("weight tree differs from the program's init")
    for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        if (w.shape, w.dtype) != (g.shape, g.dtype):
            raise ValueError(f"weight {g.shape} {g.dtype} where the "
                             f"program makes {w.shape} {w.dtype}")


def peak_memory(devs) -> int:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


@dataclasses.dataclass
class Served:
    """A cell set up for serving: the engine and what it was built from."""
    cell: object
    engine: object
    params: object
    sizes: object
    family: object
    ecfg: object
    devs: list
    peaks: dict
    api: object


def prepare(cell, seed: int, *, require_accelerator: bool = True,
            engine_factory=None, peaks=None, model=None) -> Served:
    """Weights from the seed, the engine as the configuration states, and
    every program the window runs compiled. ``model`` reuses the
    ``(api, sizes)`` of an earlier ``prepare`` in this process, and with it
    the compiled programs."""
    set_compile_cache()
    devs = devices_for(cell.chips, require_accelerator)
    from repro.serve import EngineConfig
    from bench import peaks as peaks_mod, warmup
    from bench.serve_loop import BenchEngine
    from bench.traffic import max_tokens

    if peaks is None:
        peaks = peaks_mod.peaks_for(devs[0].device_kind)
    if model is None:
        family, api, sz = _model(cell.config)
    else:
        family, (api, sz) = family_module(cell.config), model
    ecfg = EngineConfig(**cell.config["engine"])
    if max_tokens(cell.traffic) > ecfg.cache_len:
        raise ValueError("the traffic's longest request exceeds cache_len")
    params = family.make_params(sz, seed)
    check_tree(api, params)
    engine = (engine_factory or BenchEngine)(api, params, ecfg)
    warmup.warm(engine, sz.vocab)
    return Served(cell=cell, engine=engine, params=params, sizes=sz,
                  family=family, ecfg=ecfg, devs=devs, peaks=peaks, api=api)


class CompileLog:
    """Compilations (or cache loads) JAX reports while a window is open."""

    #: the event JAX records around each backend compile or cache load
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.active = False
        self.seen: list[tuple[str, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.active and event == self.EVENT:
            self.seen.append((str(kw.get("fun_name", "?")), duration))


def serve(served: Served, traffic_spec: dict, seed: int, seconds: float,
          trace: bool, *, compile_log=None):
    """Open the window and run the engine until it has closed (and, in a
    cell judged on time to first token, until every request due in it has
    its first token). Returns the window and the host time serving
    stopped."""
    import jax
    from bench import trace_reduce
    from bench.serve_loop import Window
    from bench.traffic import Traffic

    cell, engine = served.cell, served.engine
    lead_s = float(traffic_spec.get("lead_s", 0.0))
    traffic = Traffic(traffic_spec, seed, served.sizes.vocab,
                      lead_s + seconds)
    trace_slice = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        ann = []

        def on():
            jax.profiler.start_trace(trace_dir)
            ann.append(jax.profiler.TraceAnnotation(trace_reduce.SLICE))
            ann[0].__enter__()

        def off():
            ann[0].__exit__(None, None, None)
            jax.profiler.stop_trace()

        trace_slice = (0.4 * seconds, min(4.0, 0.3 * seconds), on, off)
    window = Window(traffic, seconds,
                    drain_first_tokens=cell.reports("ttft_p95_ms"),
                    lead_s=lead_s, traced=trace, trace_slice=trace_slice,
                    chunk=served.ecfg.prefill_chunk)
    if trace:
        window.trace_dir = trace_dir
    engine.window = window
    engine.reset_stats()
    if compile_log is not None:
        compile_log.active = True
    window.open()
    engine.run(max_steps=1 << 40)
    stopped = time.perf_counter()
    if compile_log is not None:
        compile_log.active = False
    engine.window = None
    return window, stopped


def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             require_accelerator: bool = True, t_start: float = T_START,
             engine_factory=None, peaks=None, log=sys.stderr) -> dict:
    """Set up, serve the window, read the metrics, check the answers.
    Returns the result object the command prints."""
    from repro.serve.queue import FAILED
    from bench import check, trace_reduce
    from bench.serve_loop import tokens_in_window

    served = prepare(cell, seed, require_accelerator=require_accelerator,
                     engine_factory=engine_factory, peaks=peaks)
    compiles = CompileLog()
    window, stopped = serve(served, cell.traffic, seed, seconds, trace,
                            compile_log=compiles)
    setup_s = window.t0 - t_start
    engine, devs = served.engine, served.devs
    mem = peak_memory(devs)
    run = RunData(seconds=seconds, setup_s=setup_s, window=window,
                  tokens_in_window=tokens_in_window(window),
                  stopped=stopped,
                  pool_stats=dict(engine.pool.stats) if engine.paged else {},
                  sizes=served.sizes, family=served.family,
                  peaks=served.peaks, chips=cell.chips)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    extra = {}
    if trace:
        if window.slice_bounds is None or window.slice_bounds[1] is None:
            raise RuntimeError("the traced slice did not complete inside "
                               "the window")
        run.trace = trace_reduce.load(window.trace_dir)
        shutil.rmtree(window.trace_dir, ignore_errors=True)
        lo, hi = window.slice_bounds
        run.slice_boundaries = window.boundaries[lo:hi]
        device["busy_s"] = trace_reduce.busy_s(run.trace)
        device["window_s"] = run.trace.window_s
        extra["breakdown"] = {
            "device_ops": trace_reduce.top_ops(run.trace),
            "idle_gaps": trace_reduce.idle_by_phase(run.trace)}
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = read_metric(m["name"], run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    due = window.measured()
    failed = sum(1 for r in due if r.refused or (
        r.request is not None and r.request.state == FAILED))
    if cell.reports("ttft_p95_ms"):
        failed += sum(1 for r in due if not r.refused and not r.deliveries)

    sample = check.draw(window, engine, seed, served.ecfg.block_tokens)
    params, sz, bt = served.params, served.sizes, served.ecfg.block_tokens
    del engine, run, served
    check.free_device_memory()
    values = check.readings(cell.config, params, sz, sample, bt)
    ok, checks = check.verdict(values, cell.config["check"]["limits"])
    print(f"window: {len(compiles.seen)} compilations "
          f"({sum(d for _, d in compiles.seen):.3f} s): "
          f"{sorted(set(n for n, _ in compiles.seen))}; sample "
          f"{values.get('served_tokens', 0)} served tokens, "
          f"{values.get('kv_rows', 0)} pool rows", file=log)
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=log)
        if not math.isfinite(c["value"]):
            c["value"] = str(c["value"])      # keep the line strict JSON
    return {"correct": bool(ok), "attempted": len(due), "failed": failed,
            "metrics": metrics, "device": device,
            "compiles_in_window": len(compiles.seen), **extra,
            "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.spec import load_cell
    cell = load_cell(args.workload)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except NoAccelerator as e:
        print(f"bench: {e}; this benchmark runs on a TPU only",
              file=sys.stderr)
        return 3
    print(json.dumps(result))
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
