"""Not a metric: what the readers of the engine's per-request host-clock
stamps (``Request.t_submit``, ``t_admit``, ``t_first``:
``time.perf_counter()`` seconds) share. They read the requests due in
the window that the traced slice left alone.

Per-layer metrics come from ``--trace 1`` runs, and there the profiler
stalls the boundary loop: the slice starts after the pipeline drains,
and ``stop_trace`` blocks the loop for as long as it takes to collect
the trace (seconds on a chip), while requests fall due and wait behind
it. A request touched by that stall reads the profiler, not the layer a
stamp metric names. So the stamp metrics read two runs of arrivals,
picked by due time alone, so that long and short requests drop out
alike:

* before: the requests due before the first one whose first token came
  after the profiler started;
* after: the requests due from the first one that fell due after the
  loop resumed from the stop and found every request submitted before
  it in a slot, when the backlog the stall left behind is gone.

A run with no traced slice reads every request due in the window."""

import math

from bench.serve_loop import percentile


def due(rec):
    return rec.due


def stamp(name: str):
    """A getter of the engine's ``name`` stamp on a window record: None
    where the request was refused, is not that far along, or comes from
    an engine without the stamps."""
    return lambda rec: getattr(rec.request, name, None)


def quiet(window) -> list:
    """The records due in the window whose time to first token the traced
    slice did not touch (module docstring)."""
    recs = window.measured()
    t_on = getattr(window, "_slice_t", None)
    if t_on is None:
        return recs
    first = stamp("t_first")
    before = min((r.due for r in recs if r.request is not None
                  and (first(r) is None or first(r) >= t_on)),
                 default=math.inf)
    submitted = sorted((r for r in window.records
                        if stamp("t_submit")(r) is not None),
                       key=lambda r: r.request.t_submit)
    resumed = _resumed(window, t_on + window.trace_slice[1], submitted)
    after, admitted_by = math.inf, -math.inf
    for rec in submitted:
        q = rec.request
        if rec.due >= resumed and admitted_by <= q.t_submit:
            after = rec.due
            break
        admitted_by = max(admitted_by, math.inf if q.t_admit is None
                          else q.t_admit)
    return [r for r in recs if r.due < before or r.due >= after]


def _resumed(window, t_off, submitted) -> float:
    """When the boundary loop resumed after the profiler's stop: the end
    of the longest silence, after the slice's end and inside the window,
    between the host's submits and deliveries. The loop stops the
    profiler at its first boundary past ``t_off`` (having drained the
    pipeline, or while idle), and neither submits nor delivers until the
    stop returns."""
    events = sorted([r.request.t_submit for r in submitted]
                    + [t for r in window.records for t, _ in r.deliveries])
    t_end = getattr(window, "t_end", math.inf)
    gaps = [(b - a, b) for a, b in zip(events, events[1:])
            if b > t_off and a <= t_end]
    return max(gaps)[1] if gaps else math.inf


def p95_ms(run, start, end):
    """95th percentile in ms of ``end(rec) - start(rec)`` over the quiet
    records for which both give a time. None where none does (an engine
    whose requests carry no stamps)."""
    vals = []
    for rec in quiet(run.window):
        a, b = start(rec), end(rec)
        if a is not None and b is not None:
            vals.append((b - a) * 1e3)
    return percentile(vals, 95) if vals else None
