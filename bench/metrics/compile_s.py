"""Host seconds to compile, bind and warm up the program: `compile_bundled`
(parse, analysis, codegen), `bind`, and the first call, which traces and
compiles with XLA or loads from the cache (the benchmark's span `compile`,
host clock). Moves `setup_s`."""


def read(ctx):
    return ctx["spans"].get("compile")
