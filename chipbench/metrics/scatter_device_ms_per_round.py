"""Compiled round: busy device time of the ``rwsadmm.scatter`` scope
(x and z written back into the client plane, the visited set) inside
runs of the compiled chunk, per round."""
from chipbench.scopes import scope_ms_per_round


def read(ctx):
    return scope_ms_per_round(ctx, "rwsadmm.scatter")
