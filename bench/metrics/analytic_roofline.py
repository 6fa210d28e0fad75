"""Share of the HBM roofline that one analytic run reaches, in %: the
least time its work could take (the bytes the algorithm must move, from N
and E alone, over the peak HBM bandwidth of the chips that run it) over
the device's busy time per run in the trace, a mean over those chips. The
same work reads the same share on one chip or several. Moves
`analytic_s`."""


def read(ctx):
    busy = ctx["trace"]["busy_s"] / ctx["runs"]
    if busy <= 0 or not ctx["work_bytes"]:
        return None
    bandwidth = ctx["trace"]["chips"] * ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * ctx["work_bytes"] / bandwidth / busy
