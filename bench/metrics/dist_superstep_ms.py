"""Device milliseconds per superstep on the distributed backend: the
trace's busy time in the window, a mean over the cell's chips, over the
supersteps the window's runs made (the distributed device counter
`_supersteps`). Moves `analytic_s`. A program whose distributed backend
counts no supersteps gives no reading."""
import program_trace


def read(ctx):
    total = program_trace.counter_sum(ctx, "_supersteps")
    return 1e3 * ctx["trace"]["busy_s"] / total if total else None
