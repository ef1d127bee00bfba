"""Compiled round: busy device time of the ``rwsadmm.zone_update`` scope
(the zone's x and z rows gathered, the Eq. 31 update, the y fold)
inside runs of the compiled chunk, per round."""
from chipbench.scopes import scope_ms_per_round


def read(ctx):
    return scope_ms_per_round(ctx, "rwsadmm.zone_update")
