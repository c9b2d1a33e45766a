"""Device time of the fused megastep program per engine step, in the
traced slice."""

from bench.trace_reduce import program_s

NAME = "jit_mega"


def match(name):
    return NAME in name


def read(run):
    if run.trace is None or not run.slice_steps:
        return None
    t = program_s(run.trace, match)
    return t * 1e3 / run.slice_steps if t > 0 else None
