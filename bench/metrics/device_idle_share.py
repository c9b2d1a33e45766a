"""Share of the traced slice in which no operation ran on the device,
averaged over the chips, in percent."""

from bench.trace_reduce import busy_s


def read(run):
    if run.trace is None or not run.trace.ops or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - busy_s(run.trace) / run.trace.window_s)
