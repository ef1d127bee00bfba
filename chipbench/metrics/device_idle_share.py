"""Device: share of the traced window in which no op ran on the device
(averaged over the cell's devices)."""
from chipbench.readers import device_ns


def read(ctx):
    busy = device_ns(ctx)
    if busy is None:
        return None
    lo, hi = ctx["window"]
    return 100.0 * (1.0 - busy / (hi - lo))
