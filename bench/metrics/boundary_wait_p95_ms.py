"""95th percentile, over the requests due in the window that the traced
slice left alone (``_request_stamps.quiet``), of the engine's submit
stamp (``Request.t_submit``) minus the time the request was due: the
wait for the engine's next megastep boundary, where the window hands it
due requests. Host clock."""

from bench.metrics import _request_stamps as S


def read(run):
    return S.p95_ms(run, S.due, S.stamp("t_submit"))
