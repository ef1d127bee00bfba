"""Compiled round: busy device time of the ``rwsadmm.grad`` scope
(batch sampling, forward and backward) inside runs of the compiled
chunk, per round."""
from chipbench.scopes import scope_ms_per_round


def read(ctx):
    return scope_ms_per_round(ctx, "rwsadmm.grad")
