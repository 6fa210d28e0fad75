"""Share of the measured window in which no op ran on the device, in %,
from the trace (1 - busy / window). Moves `analytic_s`."""


def read(ctx):
    t = ctx["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"]) if t["window_s"] > 0 else None
