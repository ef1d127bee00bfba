"""Compiled round: the FLOPs of the rounds' forward and backward passes
over the traced window's length, as a share of the cell's chips' peak
(the whole step's share: idle time and the host count against it)."""


def read(ctx):
    lo, hi = ctx["window"]
    flops = ctx["round_flops"] * ctx["rounds"]
    return 100.0 * flops / ((hi - lo) / 1e9) / (
        ctx["chips"] * ctx["peak"]["flops_per_s"])
