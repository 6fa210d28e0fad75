"""Host seconds of the graph build's sorts: the program's spans
`graph.dedup` (`np.unique` of the edge keys) and `graph.csr` (each CSR
build's `np.lexsort` and row pointers), before the window. Part of
`graph_build_s`; moves `setup_s`."""
import program_trace


def read(ctx):
    return program_trace.setup_seconds(ctx, {"graph.dedup", "graph.csr"})
