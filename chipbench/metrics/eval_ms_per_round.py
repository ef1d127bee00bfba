"""Eval: the program's ``eval`` phase spans per round."""
from chipbench.readers import phase_ms_per_round


def read(ctx):
    return phase_ms_per_round(ctx, "eval")
