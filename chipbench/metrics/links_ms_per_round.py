"""Host control plane: the program's ``repro.links`` spans (link-dropout
sampling on a rollout's graphs) inside the traced window, per round."""
from chipbench.scopes import host_ms_per_round


def read(ctx):
    return host_ms_per_round(ctx, "links")
