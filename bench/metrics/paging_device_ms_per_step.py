"""Device time of the KV paging transaction per engine step, in the
traced slice: the pool's gather, commit and write-through programs and
the int8 stream kernels."""

from bench.trace_reduce import program_s

NAMES = ("_commit_paging", "_gather_duplex", "_gather_in", "_write_blocks",
         "_migrate_rows", "duplex_kv_stream", "dequant_stream",
         "quant_stream")


def match(name):
    return any(n in name for n in NAMES)


def read(run):
    if run.trace is None or not run.slice_steps:
        return None
    t = program_s(run.trace, match)
    return t * 1e3 / run.slice_steps if t > 0 else None
