"""Share of the HBM roofline that one analytic run reaches, in %: the
least time its work could take (the bytes the algorithm must move, from N
and E alone, over the chip's peak HBM bandwidth) over the device's busy
time per run in the trace. Moves `analytic_s`."""


def read(ctx):
    busy = ctx["trace"]["busy_s"] / ctx["runs"]
    if busy <= 0 or not ctx["work_bytes"]:
        return None
    return 100.0 * ctx["work_bytes"] / ctx["peaks"]["hbm_bytes_per_s"] / busy
