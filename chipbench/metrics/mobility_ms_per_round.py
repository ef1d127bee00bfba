"""Host control plane: the program's ``repro.mobility`` spans (positions,
range graphs, degree cap and min-degree patch of a rollout) inside the
traced window, per round."""
from chipbench.scopes import host_ms_per_round


def read(ctx):
    return host_ms_per_round(ctx, "mobility")
