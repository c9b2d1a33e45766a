"""95th percentile, over requests due inside the window that delivered
two tokens or more, of (last delivery - first delivery) / (tokens - 1)."""

from bench.serve_loop import percentile


def read(run):
    vals = [(r.last - r.first) * 1e3 / (r.delivered - 1)
            for r in run.window.measured() if r.delivered >= 2]
    return percentile(vals, 95) if vals else None
