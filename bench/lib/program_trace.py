"""What the program records about itself (`repro.trace`), for the metric
readers: its host-span records and the device counters of the window's
runs. The window's runs are the last `ctx["runs"]` records of the
program's `run` span (one per `BoundProgram` call); set-up is every record
that opened before the first of them. Each function returns None where the
program records nothing, as a program without `repro.trace` does.
"""
import importlib


def _trace():
    try:
        return importlib.import_module("repro.trace")
    except ImportError:
        return None


def _records():
    trace = _trace()
    return None if trace is None else trace.records()


def window_runs(ctx, records=None):
    """The `run` records of the window's runs, oldest first."""
    records = _records() if records is None else records
    n = ctx.get("runs") or 0
    runs = [r for r in records or () if r["name"] == "run"]
    return runs[-n:] if 0 < n <= len(runs) else None


def window_counters(ctx):
    """Each window run's device counters as host numbers (one transfer for
    all of them), or None if any run returned none."""
    runs = window_runs(ctx)
    if runs is None or not all(r.get("counters") for r in runs):
        return None
    import jax
    got = jax.device_get([r["counters"] for r in runs])
    return [{k: float(v) for k, v in c.items()} for c in got]


def setup_seconds(ctx, names, *, in_span=False):
    """Seconds in the records named `names` that opened before the window's
    first run; with `in_span`, only those inside one of the program's own
    spans. None if the program recorded none of them."""
    records = _records()
    runs = window_runs(ctx, records)
    if runs is None:
        return None
    first = runs[0]["start_ns"]
    picked = [r for r in records if r["name"] in names and r["start_ns"] < first
              and (r["parent"] is not None or not in_span)]
    return sum(r["end_ns"] - r["start_ns"] for r in picked) / 1e9 if picked else None


def counter_sum(ctx, key):
    """A device counter summed over the window's runs."""
    runs = window_counters(ctx)
    if runs is None or any(key not in c for c in runs):
        return None
    return sum(c[key] for c in runs)
