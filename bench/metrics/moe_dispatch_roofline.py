"""Kernels, moe_dispatch: the least time of one call on the busiest rank
(the largest of its FLOPs over the bf16 peak, its HBM bytes over HBM
bandwidth and its ICI bytes over the interconnect) over the kernel's
device time per call, averaged over the ranks, in percent."""


def read(rec):
    red = rec.trace
    per_call = sum(red.busy_s(d) for d in red.devices) / len(red.devices) \
        / rec.calls
    least, _ = rec.dispatch.least_time(rec.peaks)
    return 100.0 * least / per_call if per_call > 0 else None
