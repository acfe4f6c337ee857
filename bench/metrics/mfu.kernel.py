"""Kernels, the whole call: the FLOPs the window's calls require (every
row through its one expert) over window x chips x the bf16 peak, in
percent."""


def read(rec):
    flops = rec.calls * rec.dispatch.flops()
    return 100.0 * flops / (rec.trace.window_s * rec.chips
                            * rec.peaks["bf16_flops_per_s"])
