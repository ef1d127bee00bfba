"""Host control plane: the program's ``repro.walk``, ``repro.zones`` and
``repro.price`` spans (the walk, zone planning and CommModel pricing of
a schedule) inside the traced window, per round."""
from chipbench.scopes import host_ms_per_round


def read(ctx):
    return host_ms_per_round(ctx, "walk", "zones", "price")
