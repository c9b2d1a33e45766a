"""95th percentile, over every request due inside the window, of its
first token's arrival at the host minus the time it was due. A request
that got no first token (refused, failed, or still waiting when the run
stopped waiting) counts with the time it had waited by then."""

from bench.serve_loop import percentile


def read(run):
    due = run.window.measured()
    if not due:
        return None
    vals = [((r.first if r.first is not None else run.stopped) - r.due)
            * 1e3 for r in due]
    return percentile(vals, 95)
