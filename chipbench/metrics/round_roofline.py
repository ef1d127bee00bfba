"""Compiled round: the least time a round needs on this chip (the larger
of its FLOPs over peak FLOP/s and its least HBM bytes over peak
bandwidth, ``counts.py``) over the device time a round takes inside
the compiled chunk."""
from chipbench.readers import chunk_s_per_round


def read(ctx):
    s = chunk_s_per_round(ctx)
    if s is None:
        return None
    p = ctx["peak"]
    least = max(ctx["round_flops"] / p["flops_per_s"],
                ctx["round_bytes"] / p["hbm_bytes_per_s"])
    return 100.0 * least / s
