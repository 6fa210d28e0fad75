"""Batched multi-source traversal benchmark: the sequential per-source
fori_loop (one full BFS + reverse pass per source) vs the batched engine
(`Schedule.batch_sources`: per-source [N] properties become [B, N]
matrices, every per-bucket SpMV an SpMM with B lanes). The two variants
are two explicit `Schedule`s compiled side by side — the API the schedule
separation exists for.

    PYTHONPATH=src python benchmarks/bench_batch.py [--smoke]

Emits BENCH_batch.json next to the repo root. Measured quantities:
  * BC over S ∈ {32, 64} sources: sequential_ms vs batched_ms (+ speedup),
    outputs asserted to agree within float tolerance;
  * multi-query SSSP: S=64 queries answered by a per-source loop of the
    single-source frontier engine vs one batched `rt.sssp_multi` sweep,
    reported as queries/second.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from common import timeit as _timeit_us  # noqa: E402  (shared methodology)

from repro.core import Schedule, compile_bundled, runtime as rt
from repro.graph import preferential_attachment
from repro.xla_cache import use_persistent_cache

OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_batch.json")


def timeit(fn, reps=3):
    """ms wrapper over benchmarks/common.py's timeit (min-of-reps, µs)."""
    us, out = _timeit_us(fn, reps=reps)
    return us / 1e3, out


def bench_bc(g, num_sources, batch, results, backend="local", reps=3):
    srcs = np.linspace(0, g.num_nodes - 1, num_sources).astype(np.int32)
    seq = compile_bundled("bc", backend=backend,
                          schedule=Schedule(batch_sources=1))
    bat = compile_bundled("bc", backend=backend,
                          schedule=Schedule(batch_sources=batch))
    assert "bfs_levels_batch" in bat.source and "bfs_levels_batch" not in seq.source

    s_ms, s_out = timeit(lambda: seq(g, sourceSet=srcs)["BC"], reps)
    b_ms, b_out = timeit(lambda: bat(g, sourceSet=srcs)["BC"], reps)
    np.testing.assert_allclose(np.asarray(b_out), np.asarray(s_out),
                               rtol=1e-3, atol=1e-3)
    key = f"bc_S{num_sources}"
    results[key] = dict(num_sources=num_sources, batch=batch, backend=backend,
                        sequential_ms=round(s_ms, 3), batched_ms=round(b_ms, 3),
                        speedup=round(s_ms / b_ms, 2))
    print(f"[{key}] seq={s_ms:9.1f}ms  batched(B={batch})={b_ms:9.1f}ms  "
          f"speedup={s_ms / b_ms:5.2f}x")


def bench_sssp_multi(g, num_queries, results, reps=3):
    srcs = np.linspace(0, g.num_nodes - 1, num_queries).astype(np.int32)
    single = compile_bundled("sssp", backend="local")

    def seq():
        return [single(g, src=int(s))["dist"] for s in srcs]

    batched = jax.jit(rt.sssp_multi)

    s_ms, s_out = timeit(seq, reps)
    b_ms, b_out = timeit(lambda: batched(g, jnp.asarray(srcs)), reps)
    for i in range(num_queries):
        assert np.array_equal(np.asarray(b_out)[i], np.asarray(s_out[i])), i
    key = f"sssp_multi_S{num_queries}"
    results[key] = dict(
        num_queries=num_queries,
        sequential_ms=round(s_ms, 3), batched_ms=round(b_ms, 3),
        sequential_qps=round(num_queries / (s_ms / 1e3), 1),
        batched_qps=round(num_queries / (b_ms / 1e3), 1),
        speedup=round(s_ms / b_ms, 2))
    print(f"[{key}] seq={s_ms:9.1f}ms ({results[key]['sequential_qps']} q/s)  "
          f"batched={b_ms:9.1f}ms ({results[key]['batched_qps']} q/s)  "
          f"speedup={s_ms / b_ms:5.2f}x")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for CI (no JSON emitted)")
    args = ap.parse_args()

    if args.smoke:
        g = preferential_attachment(800, m=6, seed=1)
        bc_sizes, batch, nq, reps = [8], 4, 8, 1
    else:
        g = preferential_attachment(12000, m=8, seed=1)
        bc_sizes, batch, nq, reps = [32, 64], 32, 64, 3

    sched = Schedule(batch_sources=batch)
    results = {"backend": jax.default_backend(),
               "config": {"smoke": args.smoke, "num_nodes": g.num_nodes,
                          "num_edges": g.num_edges, "batch_sources": batch,
                          "engine": {"num_buckets": sched.num_buckets,
                                     "push_threshold_frac": sched.push_threshold_frac}}}
    for s in bc_sizes:
        bench_bc(g, s, batch, results, reps=reps)
    bench_sssp_multi(g, nq, results, reps=reps)

    if not args.smoke:
        with open(OUT_PATH, "w") as f:
            json.dump(results, f, indent=2)
        print(f"wrote {os.path.normpath(OUT_PATH)}")
    sp = results[f"bc_S{bc_sizes[0]}"]["speedup"]
    print(f"BC S={bc_sizes[0]} batched speedup: {sp}x")


if __name__ == "__main__":
    use_persistent_cache()
    main()
