"""Personalized-PageRank batch serving: B-user sweeps vs one user at a time.

Personalization is the serving workload the batched [N, B] runtime was
built for: every user carries their own restart vector, so B concurrent
users are B independent PPR solves — but the pull step for all of them is
one SpMM over the shared graph. This benchmark measures exactly that
amortization:

* **batched** — one `rt.ppr_multi(g, sources[:B])` sweep ranks B users in
  a single while_loop (lanes freeze independently as they converge);
* **per_user** — the same B users ranked one sweep each through the
  identical single-lane kernel (what serving looks like without lane
  packing).

Reported per batch width B: wall-clock per sweep, users/sec both ways,
and the amortization ratio. Every batched rank row is asserted against
the NumPy oracle (`ppr_matrix_ref`) before any number is reported — a
fast wrong kernel would be worthless. The full run emits BENCH_ppr.json
with a headline batched/per-user throughput ratio at the widest B.

    PYTHONPATH=src python benchmarks/bench_ppr.py [--tiny]
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import get_context, runtime as rt
from repro.graph import preferential_attachment
from repro.graph.algorithms_ref import ppr_matrix_ref
from repro.xla_cache import use_persistent_cache

OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_ppr.json")
DELTA, BETA, MAX_ITER = 0.85, 1e-4, 100


def _time(fn, reps: int) -> float:
    """Best-of-reps wall clock for an already-warm jitted callable."""
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def bench_width(g, ppr_jit, sources: np.ndarray, b: int, reps: int) -> dict:
    """One row of the sweep: B users batched vs the same B one at a time."""
    srcs = jnp.asarray(sources[:b])
    batched = lambda: ppr_jit(g, srcs)
    jax.block_until_ready(batched())                       # pay the trace
    t_batch = _time(batched, reps)

    # per-user: identical kernel, one lane — the shape is traced once and
    # every user reuses it, so the gap measured is lane packing, not jit
    lone = lambda s: ppr_jit(g, jnp.asarray([s]))
    jax.block_until_ready(lone(int(sources[0])))
    t_seq = 0.0
    for _ in range(reps):
        t0 = time.perf_counter()
        for s in sources[:b]:
            jax.block_until_ready(lone(int(s)))
        t_seq = min(t_seq or float("inf"), time.perf_counter() - t0)

    return {
        "batch_users": b,
        "batched_ms": round(t_batch * 1e3, 3),
        "per_user_ms": round(t_seq * 1e3, 3),
        "batched_qps": round(b / t_batch, 1),
        "per_user_qps": round(b / t_seq, 1),
        "speedup": round(t_seq / t_batch, 2),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI-sized graph + sweep (no JSON emitted)")
    ap.add_argument("--reps", type=int, default=None)
    args = ap.parse_args()

    if args.tiny:
        g = preferential_attachment(800, m=6, seed=1)
        widths, reps = [1, 4, 8], args.reps or 2
    else:
        g = preferential_attachment(12000, m=8, seed=1)
        widths, reps = [1, 4, 8, 16, 32], args.reps or 3

    rng = np.random.default_rng(7)
    sources = rng.choice(g.num_nodes, size=max(widths),
                         replace=False).astype(np.int32)
    ppr_jit = jax.jit(lambda gg, ss: rt.ppr_multi(
        gg, ss, delta=DELTA, beta=BETA, max_iter=MAX_ITER))

    # oracle first: the widest batch covers every narrower one's lanes
    got = np.asarray(jax.block_until_ready(
        ppr_jit(g, jnp.asarray(sources))))
    ref = ppr_matrix_ref(g, sources, delta=DELTA, beta=BETA,
                         max_iter=MAX_ITER)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)
    print(f"oracle: all {len(sources)} user rank rows match ppr_matrix_ref")

    stats = get_context(g).stats()
    print(f"graph: N={g.num_nodes} E={g.num_edges} "
          f"skew={stats['skew']} | widths={widths} reps={reps}")
    results = {
        "backend": jax.default_backend(),
        "config": {"tiny": args.tiny, "widths": widths, "reps": reps,
                   "delta": DELTA, "beta": BETA, "max_iter": MAX_ITER},
        "graph": stats,
        "oracle": {"users_verified": int(len(sources))},
        "runs": [],
    }
    for b in widths:
        run = bench_width(g, ppr_jit, sources, b, reps)
        results["runs"].append(run)
        print(f"[B={b:3d}] batched {run['batched_ms']:9.2f} ms "
              f"({run['batched_qps']:8.1f} users/s)  per-user "
              f"{run['per_user_ms']:9.2f} ms ({run['per_user_qps']:8.1f} "
              f"users/s)  -> {run['speedup']:5.2f}x")

    top = results["runs"][-1]
    results["headline"] = {
        "batch_users": top["batch_users"],
        "batched_qps": top["batched_qps"],
        "per_user_qps": top["per_user_qps"],
        "qps_ratio": top["speedup"],
        "oracle_verified": True,
    }
    print(f"headline @ B={top['batch_users']}: {top['batched_qps']} users/s "
          f"batched vs {top['per_user_qps']} users/s one-at-a-time "
          f"-> {top['speedup']}x, oracle-verified")

    if not args.tiny:
        with open(OUT_PATH, "w") as f:
            json.dump(results, f, indent=2)
        print(f"wrote {os.path.normpath(OUT_PATH)}")


if __name__ == "__main__":
    use_persistent_cache()
    main()
