"""Effect-analysis-driven exchange planning: measured volume win.

The distributed codegen consumes the analyzer's effect sets to classify
each BSP loop's read properties: read-AND-written properties are the real
per-superstep exchange set, while read-but-never-written properties are
loop-invariant and are gathered exactly once before the loop. This
benchmark measures what that hoist is worth on the 8-shard distributed
backend by running the SAME workloads twice — once with the hoist
(current codegen) and once with `codegen.distributed.HOIST_INVARIANT`
flipped off, which reproduces the previous exchange plan exactly — and
comparing the `_gather_elems` counters the generated programs accumulate
on device.

Workloads (12k-node power-law graph, 8 virtual host devices):

  * **bc** — the headline win. The reverse (dependency-accumulation) pass
    reads `sigma` but only writes `delta`/`BC`, so `sigma`'s full view is
    invariant across the reverse supersteps: per source, one gather
    replaces depth-many. The forward pass writes `sigma` and keeps its
    per-superstep exchange — the win is surgical, not a blanket skip.
  * **cc** — the honest control. Its fixedPoint reads exactly the
    properties it writes (`comp`, `modified`), the invariant set is empty,
    and the volumes must come out IDENTICAL. A nonzero delta here would
    mean the hoist misclassified something.

Outputs are also cross-checked for equality between the two plans (the
hoist is a pure communication-plan change).

    PYTHONPATH=src python benchmarks/bench_analysis.py [--tiny]

Emits BENCH_analysis.json next to the repo root (full run only).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

# 8 virtual devices — must precede the first jax import
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from common import timeit as _timeit_us  # noqa: E402

from repro.core import Schedule, compile_bundled, dist  # noqa: E402
from repro.core.api import bind_cache_clear, compile_cache_clear  # noqa: E402
from repro.core.codegen import distributed as distmod  # noqa: E402
from repro.graph import preferential_attachment  # noqa: E402
from repro.xla_cache import use_persistent_cache  # noqa: E402

OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_analysis.json")
P = 8
POLICIES = ("dense", "auto")


def _run(name, g, mesh, sched, params, hoist, reps):
    """Compile+run one workload under one exchange plan; returns the
    device gather counter, wall time, and the comparable outputs."""
    distmod.HOIST_INVARIANT = hoist
    # the plan is not part of the compile-cache key (it is an ablation
    # flag, not a Schedule knob) — clear so both plans really codegen
    compile_cache_clear()
    bind_cache_clear()
    try:
        bound = compile_bundled(name, backend="distributed",
                                schedule=sched).bind(g, mesh=mesh)
        us, out = _timeit_us(lambda: bound(**params), reps=reps)
    finally:
        distmod.HOIST_INVARIANT = True
        compile_cache_clear()
        bind_cache_clear()
    return {"wall_ms": round(us / 1e3, 3),
            "gather_elems": int(out["_gather_elems"]),
            "out": {k: np.asarray(v) for k, v in out.items()
                    if k != "_gather_elems"}}


def bench_workload(name, g, mesh, params, reps, results):
    entry = {}
    for policy in POLICIES:
        sched = Schedule(dist_frontier=policy)
        hoisted = _run(name, g, mesh, sched, params, True, reps)
        baseline = _run(name, g, mesh, sched, params, False, reps)
        for k, v in hoisted["out"].items():
            assert np.allclose(v, baseline["out"][k], atol=1e-3), (
                f"{name}/{policy}: outputs diverge on {k!r} — the hoist "
                "must be a pure communication-plan change")
        he, be = hoisted["gather_elems"], baseline["gather_elems"]
        entry[policy] = {
            "gather_elems_hoisted": he,
            "gather_elems_baseline": be,
            "volume_ratio": round(he / max(be, 1), 4),
            "wall_ms_hoisted": hoisted["wall_ms"],
            "wall_ms_baseline": baseline["wall_ms"],
        }
        print(f"[{name}] {policy:6s} elems {be} -> {he}"
              f"  (x{he / max(be, 1):.3f})"
              f"  wall {baseline['wall_ms']:.1f} -> "
              f"{hoisted['wall_ms']:.1f} ms")
    results["workloads"][name] = entry
    return entry


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI-sized graph + reps (no JSON emitted)")
    args = ap.parse_args()
    assert len(jax.devices()) >= P, "expected 8 forced host devices"
    mesh = dist.make_mesh_1d(P)

    n = 800 if args.tiny else 12000
    g = preferential_attachment(n, m=8, seed=1)
    reps = 1 if args.tiny else 3
    srcs = np.arange(4, dtype=np.int32)

    results = {"backend": jax.default_backend(), "num_shards": P,
               "graph": {"num_nodes": g.num_nodes, "num_edges": g.num_edges},
               "config": {"tiny": args.tiny, "reps": reps,
                          "bc_sources": int(srcs.size)},
               "note": ("gather_elems = property-exchange elements the "
                        "generated program's collectives moved, from the "
                        "on-device counter. baseline = invariant-gather "
                        "hoist disabled (the pre-analysis exchange plan); "
                        "outputs are asserted equal between plans."),
               "workloads": {}}

    bc = bench_workload("bc", g, mesh, {"sourceSet": srcs}, reps, results)
    cc = bench_workload("cc", g, mesh, {}, reps, results)

    # bc's reverse pass must show a real reduction; cc's invariant set is
    # empty so its plan — and volume — must be bit-identical
    for policy in POLICIES:
        assert bc[policy]["volume_ratio"] < 1.0, (
            f"bc/{policy}: expected an exchange-volume win from hoisting "
            f"sigma out of the reverse pass, got {bc[policy]}")
        assert cc[policy]["gather_elems_hoisted"] \
            == cc[policy]["gather_elems_baseline"], (
            f"cc/{policy}: volumes must be identical (empty invariant "
            f"set), got {cc[policy]}")
    print(f"bc volume ratio (hoisted/baseline): "
          f"dense {bc['dense']['volume_ratio']}, "
          f"auto {bc['auto']['volume_ratio']}; cc unchanged (control)")

    if not args.tiny:
        with open(OUT_PATH, "w") as f:
            json.dump(results, f, indent=2)
        print(f"wrote {os.path.normpath(OUT_PATH)}")


if __name__ == "__main__":
    use_persistent_cache()
    main()
