"""Schedule autotuner benchmark: tuned vs default per graph family.

For each (graph family × program) pair, `repro.autotune.autotune` sweeps
candidate schedules derived from the graph's statistics (degree skew /
frontier probe — so the power-law and grid graphs explore *different*
candidate sets), then the winning schedule is re-measured head-to-head
against the default `Schedule()` with identical methodology. This is the
GraphIt claim reproduced end-to-end: the algorithm text never changes,
only the schedule, and the right schedule is graph-dependent.

    PYTHONPATH=src python benchmarks/bench_autotune.py [--tiny]

Emits BENCH_autotune.json next to the repo root (full run only).
Reported per pair: default_ms, tuned_ms, speedup, the chosen schedule,
and the tuner's own trial log; plus each family's GraphContext stats.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import jax

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from common import timeit as _timeit_us  # noqa: E402  (shared methodology)

from repro.autotune import autotune, default_params, schedule_to_dict
from repro.core import Schedule, compile_bundled, get_context
from repro.graph import preferential_attachment
from repro.graph.generators import road
from repro.xla_cache import use_persistent_cache

OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_autotune.json")


def measure_ms(bound, params, reps):
    us, _ = _timeit_us(lambda: bound(**params), reps=reps)
    return us / 1e3


def bench_pair(fam_name, g, prog_name, results, *, backend="local",
               budget=12, reps=3):
    default = compile_bundled(prog_name, backend=backend,
                              schedule=Schedule())
    res = autotune(default, g, budget=budget, seed=0, reps=reps)
    params = default_params(default, g, seed=0)

    # head-to-head re-measure (identical methodology for both sides, after
    # the sweep, so trial ordering can't bias the headline numbers)
    d_ms = measure_ms(default.bind(g), params, reps)
    t_ms = measure_ms(res.program.bind(g), params, reps)

    key = f"{fam_name}_{prog_name}"
    results[key] = dict(
        family=fam_name, program=prog_name, backend=backend,
        default_ms=round(d_ms, 3), tuned_ms=round(t_ms, 3),
        speedup=round(d_ms / t_ms, 3),
        tuned_schedule=schedule_to_dict(res.schedule),
        sweep=dict(budget=budget, num_trials=len(res.record.trials),
                   best_ms=res.record.best_ms,
                   default_ms=res.record.default_ms,
                   trials=res.record.trials),
    )
    print(f"[{key}] default={d_ms:9.1f}ms  tuned={t_ms:9.1f}ms  "
          f"speedup={d_ms / t_ms:5.2f}x  ({res.schedule})")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI-sized graphs + budget (no JSON emitted)")
    ap.add_argument("--backend", default="local",
                    choices=["local", "pallas"])
    args = ap.parse_args()

    if args.tiny:
        fams = {"powerlaw": preferential_attachment(800, m=6, seed=1),
                "grid": road(28, seed=7)}
        budget, reps, progs = 4, 1, ["sssp"]
    else:
        fams = {"powerlaw": preferential_attachment(12000, m=8, seed=1),
                "grid": road(110, seed=7)}
        budget, reps, progs = 12, 3, ["sssp", "bc"]

    results = {"backend": jax.default_backend(),
               "config": {"tiny": args.tiny, "budget": budget, "reps": reps,
                          "codegen_backend": args.backend},
               "families": {}}
    for name, g in fams.items():
        stats = get_context(g).stats()
        results["families"][name] = stats
        print(f"{name}: N={g.num_nodes} E={g.num_edges} "
              f"deg_cv={stats['deg_cv']} skew={stats['skew']} "
              f"probe_depth={stats['probe_depth']}")
    for name, g in fams.items():
        for prog in progs:
            bench_pair(name, g, prog, results, backend=args.backend,
                       budget=budget, reps=reps)

    wins = [k for k, v in results.items()
            if isinstance(v, dict) and v.get("speedup", 0) > 1.05]
    print(f"tuned wins (>1.05x): {wins or 'none'}")
    if not args.tiny:
        with open(OUT_PATH, "w") as f:
            json.dump(results, f, indent=2)
        print(f"wrote {os.path.normpath(OUT_PATH)}")


if __name__ == "__main__":
    use_persistent_cache()
    main()
