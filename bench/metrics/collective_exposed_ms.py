"""Device milliseconds per run in collective ops (all-gather, all-reduce
and the like) that no other op on the same chip overlaps, a mean over the
cell's chips (the trace reader's `collective_exposed_s`). Moves
`analytic_s`."""


def read(ctx):
    s = ctx["trace"].get("collective_exposed_s")
    return None if s is None or not ctx["runs"] else 1e3 * s / ctx["runs"]
