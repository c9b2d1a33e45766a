"""95th percentile, over the requests due in the window that the traced
slice left alone (``_request_stamps.quiet``), of the engine's admit
stamp minus its submit stamp (``Request.t_admit - t_submit``): the wait
in ``RequestQueue`` until ``ServeEngine._admit`` gives the request a
slot, and with it admission's own blocking read of the policy's weights
(``RequestQueue.dispatch``), which waits for the megastep in flight.
Host clock."""

from bench.metrics import _request_stamps as S


def read(run):
    return S.p95_ms(run, S.stamp("t_submit"), S.stamp("t_admit"))
