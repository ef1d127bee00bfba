"""Host control plane: the program's ``schedule`` phase spans (walk,
zones, mobility and links for a chunk) per round."""
from chipbench.readers import phase_ms_per_round


def read(ctx):
    return phase_ms_per_round(ctx, "schedule")
