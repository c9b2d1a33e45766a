"""Host self time of the engine's boundary loop per engine step: plan,
dispatch and reconcile, less the time reconcile waits on the device's
readback, over the steps dispatched in the window. Host clock, around
the engine's own boundary methods."""


def read(run):
    h = run.window.host_s
    if not run.window.steps:
        return None
    busy = h["plan"] + h["dispatch"] + h["reconcile"] - h["readback"]
    return busy * 1e3 / run.window.steps
