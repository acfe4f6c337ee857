"""Kernels, moe_dispatch phases: the share of the kernel's time in its
HBM <-> VMEM staging (the ``stage_in`` and ``stage_out`` regions) over its
``moe_dispatch`` region, each rank's complete calls summed, averaged over
the ranks, in percent."""
from benchlib import phases


def read(rec):
    return phases.share(phases.by_rank(rec), "stage_in", "stage_out")
