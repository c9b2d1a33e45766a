"""Share of the HBM roofline the megastep program reaches in the traced
slice: the weight bytes of every model pass it runs plus the key and
value bytes of each processed token's real context, over its device time
times the chip's HBM bandwidth, in percent. A decode step is bound by
bytes, so this is the larger of its two roofline terms."""

from bench.metrics import megastep_device_ms_per_step as mega
from bench.trace_reduce import program_s


def read(run):
    if run.trace is None or not run.slice_boundaries:
        return None
    t = program_s(run.trace, mega.match)
    if t <= 0:
        return None
    wb = run.family.weight_bytes(run.sizes)
    total = sum(wb * b.micro_steps
                + run.family.context_kv_bytes(run.sizes, b.positions)
                for b in run.slice_boundaries)
    return 100.0 * total / (t * run.peaks["hbm_bytes_s"])
