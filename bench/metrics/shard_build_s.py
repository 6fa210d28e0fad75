"""Host seconds to slice the 1-D partition out of the host CSR and put
each shard on its own chip: the program's span `graph.shard`, before the
window. Part of `graph_build_s`; moves `setup_s`. A program without the
span gives no reading."""
import program_trace


def read(ctx):
    return program_trace.setup_seconds(ctx, {"graph.shard"})
