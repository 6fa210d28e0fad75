"""Supersteps per run: loop bodies the program's top-level loop ran, the
mean over the window's runs (the program's device counter `_supersteps`,
fetched after the window). Moves `analytic_s`."""
import program_trace


def read(ctx):
    total = program_trace.counter_sum(ctx, "_supersteps")
    return None if total is None else total / ctx["runs"]
