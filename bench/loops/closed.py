"""Closed loop of whole analytic runs, back to back.

Runs start one after another, each ended by `block_until_ready`, until
`seconds` have passed; no run is cut, so the window runs past `seconds` by
less than one run. `analytic_s` is (end of the last run - start of the
first) / runs: every stall between runs counts. Inputs cycle through the
plan's list, in its order; `ran` gives each run's.
"""
import time

import jax


def measure(bound, inputs: list, seconds: float, spans) -> dict:
    outputs, ran, run_s = [], [], []
    with spans("window"):
        t_first = time.perf_counter()
        t_end = t_first
        while True:
            params = inputs[len(outputs) % len(inputs)]
            t0 = time.perf_counter()
            with spans("run"):
                out = jax.block_until_ready(bound(**params))
            t_end = time.perf_counter()
            outputs.append(out)
            ran.append(params)
            run_s.append(t_end - t0)
            if t_end - t_first >= seconds:
                break
    runs = len(outputs)
    return {"metrics": {"analytic_s": (t_end - t_first) / runs},
            "runs": runs, "outputs": outputs, "ran": ran,
            "info": {"runs": runs, "window_s": t_end - t_first, "run_s": run_s}}
