"""Seconds from the start of the process until the measured window opened:
imports, weights, engine, compilation (or loading it from the cache),
warm-up, and the traffic's lead (``lead_s`` of the traffic file: the
same traffic served for that long before the window opens, so that the
window starts from a steady state; a fixed stretch of wall time)."""


def read(run):
    return run.setup_s
