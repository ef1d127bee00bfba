"""``run_simulation``: the program's ``repro.readback`` spans (a chunk's
losses and kappas read to the host and its per-round metric entries
built) inside the traced window, per round."""
from chipbench.scopes import host_ms_per_round


def read(ctx):
    return host_ms_per_round(ctx, "readback")
