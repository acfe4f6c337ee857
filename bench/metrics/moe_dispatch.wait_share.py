"""Kernels, moe_dispatch phases: the exposed collective time, the share of
the kernel's time spent waiting for dispatch arrivals (``arrival_wait``)
and combine arrivals (``combine_wait``) over its ``moe_dispatch`` region,
each rank's complete calls summed, averaged over the ranks, in percent."""
from benchlib import phases


def read(rec):
    return phases.share(phases.by_rank(rec), "arrival_wait", "combine_wait")
