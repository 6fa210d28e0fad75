"""Device milliseconds per run in gather ops (the edge-sized gathers
`x[g.rev_indices]`, `x[g.edge_src]`), from the trace. Moves `analytic_s`."""


def read(ctx):
    s = ctx["trace"]["class_s"].get("gather")
    return None if not s else 1e3 * s / ctx["runs"]
