"""Frontier-aware engine benchmark: dense full-graph sweeps vs the
degree-bucketed sliced-ELL + direction-optimized engine, on a road-like
graph (large diameter, uniform degree) and a power-law graph (hub-skewed —
the case the old `[N, max_deg]` ELL view pads catastrophically).

    PYTHONPATH=src python benchmarks/bench_frontier.py [--smoke]

Emits BENCH_frontier.json next to the repo root so the perf trajectory
accumulates across PRs. Measured quantities per (graph, algo):
  * dense_ms     — fixed point of full dense sweeps (old engine)
  * frontier_ms  — fixed point of frontier-masked hybrid steps (new engine)
  * plus the padded-cells memory footprint of both layouts.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import runtime as rt
from repro.graph import preferential_attachment, road
from repro.graph.csr import INF_I32
from repro.kernels.ell_spmv import ops as kops
from repro.xla_cache import use_persistent_cache

OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_frontier.json")


def timeit(fn, reps=3):
    out = jax.block_until_ready(fn())       # warmup + compile
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return min(ts) * 1e3, out               # ms


# --- SSSP ------------------------------------------------------------------

def sssp_dense(g, cols, wts, src):
    """Old engine: full-graph pull sweeps over the single-width ELL view."""
    dist0 = jnp.full((g.num_nodes,), INF_I32, jnp.int32).at[src].set(0)

    def cond(s):
        return s[1]

    def body(s):
        d, _ = s
        d2 = kops._relax_dense(cols, wts, d)
        return d2, jnp.any(d2 < d)

    dist, _ = jax.lax.while_loop(cond, body, (dist0, jnp.bool_(True)))
    return dist


def sssp_frontier(g, ell, src):
    """New engine: frontier-masked sliced-ELL pull / scatter push hybrid."""
    n = g.num_nodes
    dist0 = jnp.full((n,), INF_I32, jnp.int32).at[src].set(0)
    fr0 = jnp.zeros((n,), jnp.bool_).at[src].set(True)

    def cond(s):
        return jnp.any(s[1])

    def body(s):
        d, fr = s
        d2 = kops.relax_minplus(ell, d, frontier=fr, csr=g)
        return d2, d2 < d

    dist, _ = jax.lax.while_loop(cond, body, (dist0, fr0))
    return dist


# --- BFS -------------------------------------------------------------------

def bfs_dense(g, root):
    """Old bfs_levels: one segment-max over ALL edges per level."""
    n = g.num_nodes
    level0 = jnp.full((n,), -1, jnp.int32).at[root].set(0)

    def cond(s):
        return s[2]

    def body(s):
        level, cur, _ = s
        src_on = level[g.edge_src] == cur
        unseen = level[g.indices] < 0
        reach = rt.segment_max((src_on & unseen).astype(jnp.int32), g.indices, n) > 0
        newly = reach & (level < 0)
        return jnp.where(newly, cur + 1, level), cur + 1, jnp.any(newly)

    level, depth, _ = jax.lax.while_loop(cond, body, (level0, jnp.int32(0), jnp.bool_(True)))
    return level, depth


# --- PR gather -------------------------------------------------------------

def pr_dense(g, cols, iters):
    n = g.num_nodes
    x0 = jnp.full((n,), 1.0 / n, jnp.float32)
    inv_deg = 1.0 / jnp.maximum(g.out_degree, 1).astype(jnp.float32)

    def body(_, x):
        y = kops._gather_dense(cols, x * inv_deg)[:n]
        return 0.15 / n + 0.85 * y

    return jax.lax.fori_loop(0, iters, body, x0)


def pr_sliced(g, ell, iters):
    n = g.num_nodes
    x0 = jnp.full((n,), 1.0 / n, jnp.float32)
    inv_deg = 1.0 / jnp.maximum(g.out_degree, 1).astype(jnp.float32)

    def body(_, x):
        y = kops.gather_plustimes(ell, x * inv_deg)
        return 0.15 / n + 0.85 * y

    return jax.lax.fori_loop(0, iters, body, x0)


# --- driver ----------------------------------------------------------------

def bench_graph(gname, g, results):
    n = g.num_nodes
    cols, wts, _ = kops.prepare_ell(g, reverse=True)
    ell = kops.prepare_sliced_ell(g, reverse=True)

    dense_cells = int(cols.shape[0]) * int(cols.shape[1])
    sliced_cells = ell.padded_cells()
    mem = dict(dense_padded_cells=dense_cells, sliced_padded_cells=sliced_cells,
               sliced_over_dense=round(sliced_cells / dense_cells, 4),
               max_in_degree=int(g.max_in_degree), num_edges=g.num_edges,
               bucket_widths=list(ell.widths))
    results[gname] = {"num_nodes": n, "memory": mem}
    print(f"[{gname}] n={n} E={g.num_edges} max_in_deg={g.max_in_degree} "
          f"padded cells dense={dense_cells} sliced={sliced_cells} "
          f"({100 * sliced_cells / dense_cells:.1f}%)")

    d_ms, d_out = timeit(lambda: sssp_dense(g, cols, wts, 0))
    f_ms, f_out = timeit(lambda: sssp_frontier(g, ell, 0))
    assert np.array_equal(np.asarray(d_out), np.asarray(f_out)), "SSSP mismatch"
    results[gname]["sssp"] = dict(dense_ms=round(d_ms, 3), frontier_ms=round(f_ms, 3),
                                  speedup=round(d_ms / f_ms, 2))
    print(f"[{gname}] sssp  dense={d_ms:9.2f}ms  frontier={f_ms:9.2f}ms  "
          f"speedup={d_ms / f_ms:5.2f}x")

    d_ms, (dl, dd) = timeit(lambda: bfs_dense(g, 0))
    f_ms, (fl, fd) = timeit(lambda: rt.bfs_levels(g, 0))
    assert np.array_equal(np.asarray(dl), np.asarray(fl)), "BFS mismatch"
    results[gname]["bfs"] = dict(dense_ms=round(d_ms, 3), frontier_ms=round(f_ms, 3),
                                 speedup=round(d_ms / f_ms, 2))
    print(f"[{gname}] bfs   dense={d_ms:9.2f}ms  frontier={f_ms:9.2f}ms  "
          f"speedup={d_ms / f_ms:5.2f}x")

    iters = 30
    d_ms, d_pr = timeit(lambda: pr_dense(g, cols, iters))
    f_ms, f_pr = timeit(lambda: pr_sliced(g, ell, iters))
    assert np.allclose(np.asarray(d_pr), np.asarray(f_pr), atol=1e-6), "PR mismatch"
    results[gname]["pr"] = dict(dense_ms=round(d_ms, 3), frontier_ms=round(f_ms, 3),
                                speedup=round(d_ms / f_ms, 2))
    print(f"[{gname}] pr    dense={d_ms:9.2f}ms  frontier={f_ms:9.2f}ms  "
          f"speedup={d_ms / f_ms:5.2f}x")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for CI (no JSON emitted)")
    args = ap.parse_args()

    if args.smoke:
        graphs = {"powerlaw": preferential_attachment(800, m=6, seed=1),
                  "road": road(24, seed=2)}
    else:
        graphs = {"powerlaw": preferential_attachment(12000, m=8, seed=1),
                  "road": road(110, seed=2)}

    results = {"backend": jax.default_backend(),
               "config": {"smoke": args.smoke}}
    for gname, g in graphs.items():
        bench_graph(gname, g, results)

    if not args.smoke:
        with open(OUT_PATH, "w") as f:
            json.dump(results, f, indent=2)
        print(f"wrote {os.path.normpath(OUT_PATH)}")
    sp = results["powerlaw"]["sssp"]["speedup"]
    mem = results["powerlaw"]["memory"]["sliced_over_dense"]
    print(f"powerlaw SSSP speedup: {sp}x, sliced/dense padded memory: {mem:.2%}")


if __name__ == "__main__":
    use_persistent_cache()
    main()
