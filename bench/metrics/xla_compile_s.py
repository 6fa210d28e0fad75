"""Host seconds XLA spent compiling the program, or loading it from the
persistent compilation cache, during set-up: the program's `xla_compile`
records inside its own spans (the warm-up run's compile), before the
window. Part of `compile_s`; moves `setup_s`."""
import program_trace


def read(ctx):
    return program_trace.setup_seconds(ctx, {"xla_compile"}, in_span=True)
