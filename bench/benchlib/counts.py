"""Operations and bytes that a call needs, reckoned from shapes alone.

These count what the kernel's algorithm requires, never what a program
happens to do: no padded capacity slots, no dummy experts, no rounding of
rows up to microblocks. They are the numerators of every roofline share
and of ``mfu``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass


@dataclass(frozen=True)
class Dispatch:
    """One call of a fused MoE dispatch -> expert FFN (SwiGLU) -> combine
    over ``n`` ranks, one expert per rank, with static per-expert row
    counts ``counts`` identical on every rank."""
    n: int
    d: int
    f: int
    counts: tuple
    elem_bytes: int = 4
    wire_bytes: int = 4

    @property
    def flops_per_row(self):
        return 2 * self.d * 2 * self.f + 2 * self.f * self.d

    def flops(self):
        """All ranks: every row through its one expert."""
        return self.n * sum(self.counts) * self.flops_per_row

    def rank_flops(self, e):
        """Expert ``e``'s rank computes the rows every rank sends it."""
        return self.n * self.counts[e] * self.flops_per_row

    def rank_hbm_bytes(self, e):
        """Read the rank's rows and its expert's weights, write its rows."""
        t = sum(self.counts)
        return self.elem_bytes * (2 * t * self.d + 3 * self.d * self.f)

    def rank_ici_bytes(self, e):
        """Rows that cross the wire at rank ``e``, both ways: it receives
        its expert's rows from the other ranks and sends them back, and
        it sends its own rows for the other experts and gets them back."""
        row = self.d * self.wire_bytes
        mine = (self.n - 1) * self.counts[e]
        theirs = sum(self.counts) - self.counts[e]
        return 2 * row * (mine + theirs)

    def least_time(self, peaks):
        """The least time of one call on the busiest rank, and the bound
        that sets it: the largest of FLOPs over the peak, HBM bytes over
        its bandwidth and ICI bytes over the interconnect's."""
        best = (0.0, "")
        for e in range(self.n):
            for bound, t in (
                    ("flops", self.rank_flops(e) / peaks["bf16_flops_per_s"]),
                    ("hbm", self.rank_hbm_bytes(e) / peaks["hbm_bytes_per_s"]),
                    ("ici", self.rank_ici_bytes(e) / peaks["ici_bytes_per_s"])):
                if t > best[0]:
                    best = (t, bound)
        return best


def skew_counts(rows, n, skew):
    """Rows per expert under a static skew law: shares proportional to
    ``skew ** -e``, floored, with the remainder on expert 0."""
    w = [skew ** (-e) for e in range(n)]
    c = [math.floor(v / sum(w) * rows) for v in w]
    c[0] += rows - sum(c)
    return c
