"""95th percentile, over the requests due in the window that the traced
slice left alone (``_request_stamps.quiet``), of the engine's
first-token stamp minus its admit stamp (``Request.t_first - t_admit``):
from taking a slot until the first token's value reached the host, which
is the prompt's chunked prefill plus the megasteps in flight ahead of
it. Host clock."""

from bench.metrics import _request_stamps as S


def read(run):
    return S.p95_ms(run, S.stamp("t_admit"), S.stamp("t_first"))
