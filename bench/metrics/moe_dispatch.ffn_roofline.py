"""Kernels, moe_dispatch phases: the expert FFN's least time on the rank
that holds expert 0, the busiest (its FLOPs over the bf16 peak), over the
time that rank spends in its ``ffn`` regions per complete call, in
percent. The rank is its index along the mesh's rank axis."""
from benchlib import phases


def read(rec):
    cs = phases.by_rank(rec).get(0)
    if not cs:
        return None
    ffn_s = phases.mean_us(cs, "ffn") / 1e6
    least = rec.dispatch.rank_flops(0) / rec.peaks["bf16_flops_per_s"]
    return 100.0 * least / ffn_s if ffn_s > 0 else None
