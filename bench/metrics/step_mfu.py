"""Model FLOPs of every token the engine processed in the window (prompt
and generated, attention over each token's real context), over the
window's seconds times the chip's bf16 peak, in percent."""


def read(run):
    if not run.window.boundaries:
        return None
    flops = sum(run.family.positions_flops(run.sizes, b.positions)
                for b in run.window.boundaries)
    if flops <= 0:
        return None
    return 100.0 * flops / (run.seconds * run.peaks["bf16_flops"]
                            * run.chips)
