"""Device milliseconds per superstep: the trace's busy time in the window
over the supersteps the window's runs made (device counter `_supersteps`).
Moves `analytic_s`."""
import program_trace


def read(ctx):
    total = program_trace.counter_sum(ctx, "_supersteps")
    return 1e3 * ctx["trace"]["busy_s"] / total if total else None
