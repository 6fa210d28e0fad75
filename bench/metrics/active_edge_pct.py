"""Share of the edges the frontier relax swept that start at a frontier
vertex, in %, over the window's runs: device counters `_edges_active`
(out-degrees of the frontier's vertices, per superstep) over
`_edges_swept` (the edges the chosen push or pull branch swept). Moves
`analytic_s`: a low share is work a frontier-compacted relax would skip."""
import program_trace


def read(ctx):
    active = program_trace.counter_sum(ctx, "_edges_active")
    swept = program_trace.counter_sum(ctx, "_edges_swept")
    return 100.0 * active / swept if active is not None and swept else None
