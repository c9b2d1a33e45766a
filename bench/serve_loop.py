"""The measured window: ``ServeEngine.run()`` driven by wall-clock traffic.

``ServeEngine`` takes its requests before ``run()`` and hands back the
tokens after it. A served window needs two things that public API cannot
do: take a request while ``run()`` is running, and tell when each token
reached the host. ``BenchEngine`` adds exactly those, through the
engine's own boundary methods, and changes nothing it computes:

  * at each boundary (``_auto_megastep``, the first call ``run()`` makes
    for a boundary) the ``Window`` submits the requests whose due time has
    passed, with ``arrival_step`` set to the current step;
  * after each ``_reconcile`` it stamps the host time at which each
    request's new tokens arrived;
  * ``pending()`` stays nonzero while the window is open, so ``run()``
    keeps serving between arrivals, and drops to the engine's own count
    once it has closed.

In a traced run each phase is also wrapped in a
``jax.profiler.TraceAnnotation`` (``bench.plan``, ``bench.dispatch``,
``bench.reconcile``, ``bench.readback``, ``bench.idle``), so that the
device's idle gaps can be put down to what the host was doing, and the
per-boundary model work is counted from the host-deterministic
trajectories. If an engine method the hooks rely on is missing or has
another signature, importing this module fails: it does not measure
something else.
"""

from __future__ import annotations

import contextlib
import dataclasses
import inspect
import time

import jax
import numpy as np

from repro.serve import engine as engine_mod
from repro.serve.engine import ServeEngine

#: engine methods the hooks wrap, with the parameters they must take.
HOOKED = {
    "_auto_megastep": ("self", "remaining"),
    "_plan": ("self", "n_steps"),
    "_dispatch": ("self", "rec"),
    "_reconcile": ("self", "rec"),
    "_readback": ("self", "packed"),
    "pending": ("self",),
    "run": ("self", "max_steps"),
}
#: fields of the engine's in-flight boundary record the hooks read.
INFLIGHT_FIELDS = {"now", "k", "live", "traj"}
#: request fields the hooks read.
REQUEST_FIELDS = ("plan_state", "plan_consumed", "plan_n_gen", "generated",
                  "prompt_len", "max_new_tokens", "rid", "state")


class HookError(RuntimeError):
    """The engine no longer has a method or field the hooks need."""


def check_engine(cls=ServeEngine) -> None:
    for name, params in HOOKED.items():
        fn = getattr(cls, name, None)
        if not callable(fn):
            raise HookError(f"ServeEngine.{name} is gone")
        got = tuple(inspect.signature(fn).parameters)
        if got != params:
            raise HookError(f"ServeEngine.{name}{got} no longer takes "
                            f"{params}")
    have = {f.name for f in dataclasses.fields(engine_mod._InFlight)}
    if not INFLIGHT_FIELDS <= have:
        raise HookError(f"engine _InFlight lacks "
                        f"{sorted(INFLIGHT_FIELDS - have)}")
    for name in REQUEST_FIELDS:
        if not hasattr(engine_mod.Request, name) and name not in {
                f.name for f in dataclasses.fields(engine_mod.Request)}:
            raise HookError(f"Request.{name} is gone")


check_engine()


@dataclasses.dataclass
class Record:
    """One request of the window, as the client sees it."""
    due: float                 # host time it was due (perf_counter)
    prompt_len: int
    max_new: int
    request: object = None     # the engine's Request once submitted
    refused: bool = False
    deliveries: list = dataclasses.field(default_factory=list)
                               # (host time, tokens) per reconcile
    delivered: int = 0

    @property
    def first(self) -> float | None:
        return self.deliveries[0][0] if self.deliveries else None

    @property
    def last(self) -> float | None:
        return self.deliveries[-1][0] if self.deliveries else None

    @property
    def finished(self) -> bool:
        return self.delivered >= self.max_new


@dataclasses.dataclass
class Boundary:
    """Model work of one dispatched megastep, counted from the engine's
    host-deterministic trajectories (traced runs only)."""
    k: int
    micro_steps: int           # model passes the program runs (all rows)
    positions: list            # positions of every token processed


def _annotate(traced: bool, name: str):
    return (jax.profiler.TraceAnnotation(name) if traced
            else contextlib.nullcontext())


class Window:
    """The load generator and the client's clock for one run."""

    def __init__(self, traffic, seconds: float, *, drain_first_tokens:
                 bool, lead_s: float = 0.0, drain_cap_s: float = 60.0,
                 traced: bool = False, trace_slice=None, chunk: int = 4):
        self.traffic = traffic
        self.seconds = float(seconds)
        self.lead_s = float(lead_s)
        self.drain_first_tokens = drain_first_tokens
        self.drain_cap_s = drain_cap_s
        self.traced = traced
        self.trace_slice = trace_slice   # (start_s, length_s, on, off)
        self.chunk = chunk
        self.phase = "setup"
        self.t_lead = self.t0 = self.t_end = None
        self.records: list[Record] = []
        self._by_rid: dict[int, Record] = {}
        self._next = 0                   # next planned request
        self._ready: list[float] = []    # closed loop: due times to send
        self.steps = 0                   # engine steps dispatched in window
        self.host_s = {"plan": 0.0, "dispatch": 0.0, "reconcile": 0.0,
                       "readback": 0.0}
        self.boundaries: list[Boundary] = []
        self.slice_bounds = None         # (first, end) boundary indices
        self._slice_state = "before"

    # -- the clock ---------------------------------------------------------
    def open(self) -> None:
        """Start the traffic. It runs ``lead_s`` before the measured
        window opens, so that the window starts from a served steady state
        and not from an empty engine; what is due or delivered in the
        lead is served and not counted."""
        self.t_lead = time.perf_counter()
        self.t0 = self.t_lead + self.lead_s
        self.t_end = self.t0 + self.seconds
        self.phase = "window"
        if self.traffic.loop == "closed":
            self._ready = [self.t_lead] * self.traffic.clients

    def in_window(self, t: float) -> bool:
        return self.t0 is not None and self.t0 <= t <= self.t_end

    def measured(self) -> list:
        """The requests due inside the measured window."""
        return [r for r in self.records if self.in_window(r.due)]

    # -- intake ------------------------------------------------------------
    def _submit(self, engine, due: float) -> None:
        if self._next >= len(self.traffic.requests):
            raise RuntimeError("traffic ran out of planned requests; make "
                               "the pool larger")
        p = self.traffic.requests[self._next]
        self._next += 1
        rec = Record(due=due, prompt_len=len(p.prompt), max_new=p.max_new)
        self.records.append(rec)
        q = engine.queue
        if len(q) >= q.capacity:
            rec.refused = True
            return
        rec.request = engine.submit(p.prompt, p.max_new,
                                    arrival_step=engine.step_count)
        self._by_rid[rec.request.rid] = rec

    def inject(self, engine) -> None:
        now = time.perf_counter()
        if self.traffic.loop == "open":
            reqs = self.traffic.requests
            while (self._next < len(reqs)
                   and self.t_lead + reqs[self._next].due_s
                   <= min(now, self.t_end)):
                self._submit(engine, self.t_lead + reqs[self._next].due_s)
        else:
            due = [t for t in self._ready if t <= now]
            self._ready = [t for t in self._ready if t > now]
            for t in sorted(due):
                self._submit(engine, t)

    def next_due(self) -> float | None:
        if self.traffic.loop == "open":
            reqs = self.traffic.requests
            if self._next < len(reqs):
                return self.t_lead + reqs[self._next].due_s
            return None
        return min(self._ready) if self._ready else None

    # -- the boundary hook -------------------------------------------------
    def on_boundary(self, engine) -> None:
        if self.phase != "window":
            return
        now = time.perf_counter()
        if now >= self.t_end:
            self.phase = "drain" if self.drain_first_tokens else "closed"
            self._drain_end = now + self.drain_cap_s
            return
        self._maybe_trace(engine, now)
        self.inject(engine)
        if ServeEngine.pending(engine) == 0:
            # nothing to serve: hand over what is in flight, then wait
            # for the next arrival
            with _annotate(self.traced, "bench.idle"):
                while engine._inflight:
                    engine._reconcile(engine._inflight[0])
                nxt = self.next_due()
                wake = self.t_end if nxt is None else min(nxt, self.t_end)
                if wake > time.perf_counter():
                    time.sleep(wake - time.perf_counter())
            if time.perf_counter() < self.t_end:
                self.inject(engine)

    def pending(self, engine, base: int) -> int:
        if self.phase == "window":
            return max(base, 1)
        if self.phase == "drain":
            waiting = any(r.request is not None and not r.deliveries
                          for r in self.measured())
            if waiting and time.perf_counter() < self._drain_end:
                return base
            self.phase = "closed"
        return 0

    def on_reconciled(self, rec) -> None:
        t = time.perf_counter()
        for r in rec.live:
            w = self._by_rid.get(r.rid)
            if w is None:
                continue
            n = len(r.generated) - w.delivered
            if n > 0:
                w.deliveries.append((t, n))
                w.delivered += n
                if (self.traffic.loop == "closed" and w.finished
                        and self.phase == "window"):
                    self._ready.append(t)

    def counting(self) -> bool:
        """Inside the measured window, where steps and host time count."""
        return self.phase == "window" and time.perf_counter() >= self.t0

    def on_dispatched(self, rec, k: int) -> None:
        if self.counting():
            self.steps += k

    # -- traced slice ------------------------------------------------------
    def _maybe_trace(self, engine, now: float) -> None:
        if self.trace_slice is None or self._slice_state == "done":
            return
        start_s, length_s, on, off = self.trace_slice
        if self._slice_state == "before" and now >= self.t0 + start_s:
            while engine._inflight:               # a quiet device to start
                engine._reconcile(engine._inflight[0])
            on()
            self._slice_state = "on"
            self._slice_t = time.perf_counter()
            self.slice_bounds = [len(self.boundaries), None]
        elif (self._slice_state == "on"
              and now >= self._slice_t + length_s):
            while engine._inflight:               # and a quiet one to stop
                engine._reconcile(engine._inflight[0])
            self.slice_bounds[1] = len(self.boundaries)
            off()
            self._slice_state = "done"

    def count_boundary(self, engine, rec) -> None:
        """Model work of a planned megastep: which micro-steps run the
        model, and the position of every token it processes."""
        chunk = self.chunk
        micro = 0
        positions = []
        state = {r.rid: (r.plan_state, r.plan_consumed, r.plan_n_gen)
                 for r in rec.live}
        for t in range(rec.k):
            run = 0
            for r in rec.live:
                st, c, g = state[r.rid]
                nxt = rec.traj[r.rid][t]
                if st == engine_mod.PREFILL:
                    positions.extend(range(c, nxt.consumed))
                    run = max(run, nxt.consumed - c)
                elif st == engine_mod.DECODE:
                    positions.append(c + g - 1)
                    run = max(run, 1)
                state[r.rid] = ({engine_mod.S_PREFILL: engine_mod.PREFILL,
                                 engine_mod.S_DECODE: engine_mod.DECODE,
                                 engine_mod.S_DONE: engine_mod.DONE
                                 }[nxt.state], nxt.consumed, nxt.n_gen)
            micro += min(run, chunk)
        self.boundaries.append(Boundary(k=rec.k, micro_steps=micro,
                                        positions=positions))


class BenchEngine(ServeEngine):
    """``ServeEngine`` with the window's intake and delivery hooks."""

    window: Window | None = None

    def pending(self) -> int:
        base = super().pending()
        if self.window is None:
            return base
        return self.window.pending(self, base)

    def _auto_megastep(self, remaining):
        if self.window is not None:
            self.window.on_boundary(self)
        return super()._auto_megastep(remaining)

    def _plan(self, n_steps=None):
        w = self.window
        traced = w is not None and w.traced
        t = time.perf_counter()
        with _annotate(traced, "bench.plan"):
            rec = super()._plan(n_steps)
        if traced and w.counting():
            w.host_s["plan"] += time.perf_counter() - t
            w.count_boundary(self, rec)
        return rec

    def _dispatch(self, rec):
        w = self.window
        traced = w is not None and w.traced
        t = time.perf_counter()
        with _annotate(traced, "bench.dispatch"):
            out = super()._dispatch(rec)
        if w is not None:
            w.on_dispatched(rec, rec.k)
            if traced and w.counting():
                w.host_s["dispatch"] += time.perf_counter() - t
        return out

    def _readback(self, packed):
        w = self.window
        traced = w is not None and w.traced
        t = time.perf_counter()
        with _annotate(traced, "bench.readback"):
            out = super()._readback(packed)
        if traced and w.counting():
            w.host_s["readback"] += time.perf_counter() - t
        return out

    def _reconcile(self, rec):
        w = self.window
        traced = w is not None and w.traced
        t = time.perf_counter()
        with _annotate(traced, "bench.reconcile"):
            out = super()._reconcile(rec)
        if w is not None:
            w.on_reconciled(rec)
            if traced and w.counting():
                w.host_s["reconcile"] += time.perf_counter() - t
        return out


def tokens_in_window(window: Window) -> int:
    """Tokens the host received inside the measured window."""
    return sum(n for r in window.records for t, n in r.deliveries
               if window.in_window(t))


def percentile(values, q: float) -> float:
    """The ``q``-th percentile, linear between closest ranks."""
    return float(np.percentile(np.asarray(values, np.float64), q))
