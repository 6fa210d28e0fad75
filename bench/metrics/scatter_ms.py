"""Device milliseconds per run in scatter ops (scatter-min, and the
scatter-add XLA makes of a segment sum or min), from the trace. Moves
`analytic_s`."""


def read(ctx):
    s = ctx["trace"]["class_s"].get("scatter")
    return None if not s else 1e3 * s / ctx["runs"]
