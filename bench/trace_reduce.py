"""From a profiler trace to device busy time, program time and idle gaps.

``load`` reads the ``.xplane.pb`` the JAX profiler writes, with nothing
but JAX: the device planes' executed programs (``XLA Modules``) and
operations (``XLA Ops``), and the host threads' ``bench.*`` annotations
that ``serve_loop`` writes. The window is the ``bench.slice`` annotation.
The reductions below are plain interval arithmetic on those lists, so
they can be checked on a synthetic trace.
"""

from __future__ import annotations

import dataclasses
import glob
import os

DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SLICE = "bench.slice"
PHASES = ("bench.plan", "bench.dispatch", "bench.reconcile",
          "bench.readback", "bench.idle")


@dataclasses.dataclass
class Trace:
    """Events in nanoseconds on one clock: ``(name, start, duration)``.
    ``ops`` and ``modules`` are per device, in device order."""
    ops: list
    modules: list
    host: list
    window: tuple          # (start, end) of the traced slice

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9


def _events(line):
    for e in line.events:
        yield (e.name, float(e.start_ns), float(e.duration_ns))


def load(log_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    ops, modules, host = [], [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE not in lines:
                continue
            ops.append(list(_events(lines[OPS_LINE])))
            modules.append(list(_events(lines[MODULES_LINE]))
                           if MODULES_LINE in lines else [])
        else:
            for line in plane.lines:
                host.extend(ev for ev in _events(line)
                            if ev[0].startswith("bench."))
    slices = [ev for ev in host if ev[0] == SLICE]
    if not slices:
        raise ValueError("trace holds no bench.slice annotation")
    _, s0, d = slices[0]
    return Trace(ops=ops, modules=modules, host=host, window=(s0, s0 + d))


def clip(events, lo: float, hi: float):
    """Events cut to [lo, hi], dropping those outside."""
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def union_ns(events) -> float:
    """Length of the union of the events' intervals."""
    total = 0.0
    end = None
    for _, s, d in sorted(events, key=lambda e: e[1]):
        e = s + d
        if end is None or s > end:
            total += d
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def gaps(events, lo: float, hi: float):
    """The idle intervals ``(start, length)`` in [lo, hi] that no event
    covers."""
    out = []
    cur = lo
    for _, s, d in sorted(clip(events, lo, hi), key=lambda e: e[1]):
        if s > cur:
            out.append((cur, s - cur))
        cur = max(cur, s + d)
    if hi > cur:
        out.append((cur, hi - cur))
    return out


def busy_s(trace: Trace) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    lo, hi = trace.window
    per = [union_ns(clip(ops, lo, hi)) for ops in trace.ops]
    return sum(per) / len(per) / 1e9 if per else 0.0


def program_s(trace: Trace, match) -> float:
    """Device seconds of executed programs whose name ``match`` accepts,
    summed over devices, inside the window (a program's time is its
    module execution; where a device has no module line, its ops')."""
    lo, hi = trace.window
    total = 0.0
    for mods, ops in zip(trace.modules, trace.ops):
        evs = mods if mods else ops
        total += sum(d for name, _, d in clip(evs, lo, hi) if match(name))
    return total / 1e9


def op_name(text: str) -> str:
    """An operation's short name: the HLO instruction name the trace
    prints before its ``=`` (``%fusion.12 = ...`` -> ``fusion.12``)."""
    return text.split(" = ", 1)[0].lstrip("%").strip()


def top_ops(trace: Trace, n: int = 10):
    """``[[name, seconds], ...]``: the device operations that took the most
    time in the window, summed over devices, longest first."""
    lo, hi = trace.window
    acc: dict[str, float] = {}
    for ops in trace.ops:
        for name, _, d in clip(ops, lo, hi):
            key = op_name(name)
            acc[key] = acc.get(key, 0.0) + d / 1e9
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])
            ][:n]


def idle_by_phase(trace: Trace, n: int = 10):
    """``[[phase, seconds], ...]``: device idle time in the window, each
    gap put down to the innermost host annotation over its midpoint
    (``other`` where none is), most first. Taken on the first device."""
    if not trace.ops:
        return []
    lo, hi = trace.window
    spans = [ev for ev in trace.host if ev[0] in PHASES]
    acc: dict[str, float] = {}
    for s, d in gaps(trace.ops[0], lo, hi):
        mid = s + d / 2
        over = [ev for ev in spans if ev[1] <= mid <= ev[1] + ev[2]]
        phase = (min(over, key=lambda ev: ev[2])[0].split(".", 1)[1]
                 if over else "other")
        acc[phase] = acc.get(phase, 0.0) + d / 1e9
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])
            ][:n]
