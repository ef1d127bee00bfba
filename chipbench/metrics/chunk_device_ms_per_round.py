"""Device: busy time of the ops inside runs of the compiled chunk
(``jit_chunk``) per round."""
from chipbench.readers import chunk_s_per_round


def read(ctx):
    s = chunk_s_per_round(ctx)
    return None if s is None else 1e3 * s
