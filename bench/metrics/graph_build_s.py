"""Host seconds in the program's graph build: `from_edges` and `prepare`
(the benchmark's span `graph_build`, host clock). Moves `setup_s`."""


def read(ctx):
    return ctx["spans"].get("graph_build")
