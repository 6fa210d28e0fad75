"""Bring-up smoke test: the graph-analytics main path on a TPU, checked.

One process drives the system through the entry points a user calls —
`compile_bundled(name)` -> `prepare` -> `prog.bind(g)`, `GraphService`, and
`g.update` + `bound.refresh` — on an RMAT graph (SNAP parameters, 16 edges
per node, 2^21 nodes unless `--scale` says otherwise), and checks every
answer against an independent scipy reference. Each phase prints one JSON
line with its name, its seconds and `ok`; device work inside a phase is
timed up to `block_until_ready`. The last line is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Usage (from the root of a checkout):

    python chip_smoke.py               # one chip: sssp, pr, bc, tc, serve, refresh
    python chip_smoke.py --chips 4     # only the distributed 1-D and 2-D paths
    JAX_PLATFORMS=cpu python chip_smoke.py --scale 10      # CPU rehearsal
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python chip_smoke.py --chips 4 --scale 10          # 4-device rehearsal

Off a TPU the last line says `"ok": false` and the exit code is 1. Given
`--scale`, every phase still runs there first (a rehearsal of the script at
a small size); without it the script stops after the device phase rather
than build a full-size graph on the host's CPU.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time
import traceback

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import scipy.sparse as sp  # noqa: E402
from scipy.sparse.csgraph import dijkstra  # noqa: E402

from repro.core import compile_bundled, dist, prepare  # noqa: E402
from repro.core.dist2d import pagerank_2d, sssp_2d  # noqa: E402
from repro.graph import rmat, small_world  # noqa: E402
from repro.graph.csr import INF_I32  # noqa: E402
from repro.schedule import Schedule  # noqa: E402
from repro.serve import GraphService, ServiceConfig  # noqa: E402
from repro.xla_cache import use_persistent_cache  # noqa: E402

EDGE_FACTOR = 16
FULL_SCALE = 21
PR_PARAMS = dict(beta=1e-4, delta=0.85, maxIter=100)
PR_ATOL = 1e-4        # per entry, against the float64 reference
BC_RTOL = 1e-3        # relative, against the float64 reference
BC_SOURCES = 4
SERVE_SSSP, SERVE_PPR, SERVE_CHECKED = 32, 8, 4
REFRESH_INSERTS = 1000
TC_NODES, TC_K = 2 ** 20, 8


# --------------------------------------------------------------------------
# independent references (scipy, float64)
# --------------------------------------------------------------------------

class Reference:
    """scipy views of one graph, built from its CSR arrays on the host."""

    def __init__(self, g):
        n = g.num_nodes
        indptr, indices = np.asarray(g.indptr), np.asarray(g.indices)
        self.n = n
        self.weighted = sp.csr_matrix(
            (np.asarray(g.weights, np.float64), indices, indptr), shape=(n, n))
        self.adj = sp.csr_matrix(
            (np.ones(indices.shape[0]), indices, indptr), shape=(n, n))
        self.adj_t = self.adj.T.tocsr()
        self.inv_out = 1.0 / np.maximum(np.diff(indptr), 1)

    def sssp(self, sources) -> np.ndarray:
        d = dijkstra(self.weighted, directed=True, indices=sources)
        return np.where(np.isinf(d), INF_I32, d).astype(np.int64)

    def pagerank(self, iters: int, damping: float) -> np.ndarray:
        """`iters` sweeps of pr.sp's update: pull over in-edges of
        rank / out-degree (nodes without out-edges contribute nothing)."""
        pr = np.full(self.n, 1.0 / self.n)
        for _ in range(iters):
            pr = (1 - damping) / self.n + damping * (self.adj_t @ (pr * self.inv_out))
        return pr

    def ppr(self, src: int, damping: float, beta: float, max_iter: int) -> np.ndarray:
        restart = np.zeros(self.n)
        restart[src] = 1.0
        rank = restart
        for _ in range(max_iter):
            nxt = (1 - damping) * restart + damping * (self.adj_t @ (rank * self.inv_out))
            diff, rank = np.abs(nxt - rank).sum(), nxt
            if not diff > beta:
                break
        return rank

    def bc(self, sources) -> np.ndarray:
        """Brandes over the unweighted BFS DAG of each source, all sources
        as columns of one matrix; sources themselves accumulate nothing."""
        s = len(sources)
        cols = np.arange(s)
        sigma = np.zeros((self.n, s))
        depth = np.full((self.n, s), -1)
        sigma[sources, cols], depth[sources, cols] = 1.0, 0
        frontier = depth == 0
        level = 0
        while frontier.any():
            paths = self.adj_t @ np.where(frontier, sigma, 0.0)
            new = (paths > 0) & (depth < 0)
            level += 1
            depth[new] = level
            sigma = np.where(new, paths, sigma)
            frontier = new
        delta = np.zeros((self.n, s))
        for lv in range(level, 0, -1):
            coef = np.where(depth == lv, (1 + delta) / np.where(sigma > 0, sigma, 1), 0.0)
            delta = np.where(depth == lv - 1, sigma * (self.adj @ coef), delta)
        return np.where(depth > 0, delta, 0.0).sum(axis=1)


def triangles_ref(g) -> int:
    """Undirected triangles of a symmetric graph: sum((A @ A) * A) / 6."""
    n = g.num_nodes
    a = sp.csr_matrix((np.ones(g.num_edges, np.int64), np.asarray(g.indices),
                       np.asarray(g.indptr)), shape=(n, n))
    return int((a @ a).multiply(a).sum()) // 6


def _pr_errors(got, ref) -> dict:
    err = np.abs(np.asarray(got, np.float64) - ref)
    return {"max_abs_err": float(err.max()),
            "max_rel_err": float((err / np.maximum(ref, 1e-30)).max()),
            "ok": bool(err.max() <= PR_ATOL)}


def _bc_errors(got, ref) -> dict:
    got = np.asarray(got, np.float64)
    # relative per entry; entries the float64 answer puts at (near) zero
    # are held to the same fraction of the largest centrality
    tol = BC_RTOL * np.maximum(np.abs(ref), 1e-6 * np.abs(ref).max())
    err = np.abs(got - ref)
    return {"max_rel_err": float((err / np.maximum(np.abs(ref), 1e-30)).max()),
            "bc_max": float(ref.max()), "ok": bool((err <= tol).all())}


# --------------------------------------------------------------------------
# phase runner
# --------------------------------------------------------------------------

def _timed(fn):
    """(result, seconds) of `fn()`, timed up to block_until_ready."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def _peak_bytes() -> list:
    stats = [d.memory_stats() for d in jax.local_devices()]
    return [s.get("peak_bytes_in_use") if s else None for s in stats]


class Smoke:
    def __init__(self):
        self.ok = True
        self.state: dict = {}

    def phase(self, name, fn, *needs) -> None:
        """Run one phase and print its line. A phase whose inputs an earlier
        failed phase should have made does not run, and counts as failed."""
        t0 = time.perf_counter()
        if any(k not in self.state for k in needs):
            info = {"ok": False, "error": f"needs {', '.join(needs)}"}
        else:
            try:
                info = fn(self.state) or {}
            except Exception as e:   # report, fail the run, go on
                traceback.print_exc()
                info = {"ok": False, "error": f"{type(e).__name__}: {e}"[:800]}
        ok = bool(info.pop("ok", True))
        self.ok &= ok
        line = {"phase": name, "seconds": time.perf_counter() - t0, "ok": ok}
        line.update(info)
        line["peak_bytes_in_use"] = _peak_bytes()
        print(json.dumps(line), flush=True)


# --------------------------------------------------------------------------
# phases shared by both paths
# --------------------------------------------------------------------------

def phase_device(chips: int, allow_cpu: bool):
    def run(st):
        devs = jax.devices()
        st["device"] = {"platform": devs[0].platform,
                        "kind": devs[0].device_kind, "count": len(devs)}
        ok = devs[0].platform == "tpu" and len(devs) >= chips
        if ok or (allow_cpu and len(devs) >= chips):
            st["devices"] = devs[:chips]
        return {**st["device"], "ok": ok}
    return run


def phase_graph(scale: int, seed: int):
    def run(st):
        t0 = time.perf_counter()
        g = jax.block_until_ready(rmat(scale, edge_factor=EDGE_FACTOR, seed=seed))
        host_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        st["ref"] = Reference(g)
        st["g"] = g
        return {"scale": scale, "nodes": g.num_nodes, "edges": g.num_edges,
                "host_setup_s": host_s, "reference_setup_s": time.perf_counter() - t0,
                "pull_split": _pull_split(g)}
    return run


def _pull_split(g) -> dict:
    """How the in-edges fall into the sliced-ELL buckets of the default
    Schedule, and how many land in the COO hub tail."""
    s = Schedule()
    deg = np.asarray(g.in_degree)
    buckets, lo = [], 0
    for i in range(s.num_buckets):
        w = s.min_width * s.growth ** i
        sel = (deg > lo) & (deg <= w)
        buckets.append({"width": w, "rows": int(sel.sum()),
                        "edges": int(deg[sel].sum())})
        lo = w
    hub = deg > lo
    return {"buckets": buckets, "hub_rows": int(hub.sum()),
            "hub_edges": int(deg[hub].sum())}


def _sources(g, k: int, seed: int) -> np.ndarray:
    """k distinct vertices with out-edges (an RMAT graph has many isolated
    vertices, from which every answer is trivial)."""
    live = np.flatnonzero(np.asarray(g.out_degree) > 0)
    return np.random.default_rng(seed).choice(live, size=k, replace=False).astype(np.int32)


def _bind(name, g, backend="local", mesh=None):
    prog = compile_bundled(name, backend=backend)
    prepare(g, program=prog, mesh=mesh)
    return prog.bind(g, mesh=mesh)


def _local(st, name, key, params):
    """The local backend's answer for one program, computed once."""
    if key not in st:
        bound = _bind(name, st["g"])
        st[key] = jax.block_until_ready(bound(**params))
    return st[key]


# --------------------------------------------------------------------------
# one chip: the local backend, serving, dynamic refresh
# --------------------------------------------------------------------------

def phase_sssp(st):
    g, ref = st["g"], st["ref"]
    bound = _bind("sssp", g)
    out, first = _timed(lambda: bound(src=0))
    _, second = _timed(lambda: bound(src=0))
    st["sssp"] = out
    t0 = time.perf_counter()
    want = ref.sssp(0)
    got = np.asarray(out["dist"])
    return {"first_call_s": first, "second_call_s": second,
            "reference_s": time.perf_counter() - t0,
            "reached": int((got < INF_I32).sum()),
            "ok": bool(np.array_equal(got, want))}


def phase_pr(st):
    g, ref = st["g"], st["ref"]
    bound = _bind("pr", g)
    out, first = _timed(lambda: bound(**PR_PARAMS))
    _, second = _timed(lambda: bound(**PR_PARAMS))
    iters = int(out["iterCount"])
    t0 = time.perf_counter()
    want = ref.pagerank(iters, PR_PARAMS["delta"])
    return {"first_call_s": first, "second_call_s": second,
            "reference_s": time.perf_counter() - t0, "iterations": iters,
            **_pr_errors(out["pageRank"], want)}


def phase_bc(seed: int):
    def run(st):
        g, ref = st["g"], st["ref"]
        srcs = _sources(g, BC_SOURCES, seed)
        bound = _bind("bc", g)
        out, first = _timed(lambda: bound(sourceSet=srcs))
        _, second = _timed(lambda: bound(sourceSet=srcs))
        t0 = time.perf_counter()
        want = ref.bc(srcs)
        return {"sources": srcs.tolist(), "first_call_s": first,
                "second_call_s": second, "reference_s": time.perf_counter() - t0,
                **_bc_errors(out["BC"], want)}
    return run


def phase_tc(seed: int, nodes: int):
    def run(st):
        t0 = time.perf_counter()
        g = small_world(nodes, k=TC_K, seed=seed)
        host_s = time.perf_counter() - t0
        bound = _bind("tc", g)
        out, first = _timed(lambda: bound())
        got = int(out["triangle_count"])
        t0 = time.perf_counter()
        want = triangles_ref(g)
        return {"nodes": g.num_nodes, "edges": g.num_edges, "host_setup_s": host_s,
                "first_call_s": first, "reference_s": time.perf_counter() - t0,
                "triangles": got, "ok": got == want}
    return run


def phase_serve(seed: int):
    def run(st):
        g, ref = st["g"], st["ref"]
        srcs = _sources(g, SERVE_SSSP + SERVE_PPR, seed + 1)
        sssp_srcs, ppr_srcs = srcs[:SERVE_SSSP], srcs[SERVE_SSSP:]
        service = GraphService(ServiceConfig(default_timeout_s=None))
        t0 = time.perf_counter()
        service.register_graph("rmat", g, kinds=["sssp", "ppr"])
        register_s = time.perf_counter() - t0

        async def traffic():
            async with service:
                return await asyncio.gather(
                    *(service.query("rmat", "sssp", src=int(s)) for s in sssp_srcs),
                    *(service.query("rmat", "ppr", src=int(s)) for s in ppr_srcs))

        answers, serve_s = _timed(lambda: asyncio.run(traffic()))
        stats = service.stats()
        t0 = time.perf_counter()
        want = ref.sssp(sssp_srcs[:SERVE_CHECKED])
        sssp_ok = all(np.array_equal(np.asarray(answers[i]), want[i])
                      for i in range(SERVE_CHECKED))
        # both sides stop once an L1 step is <= beta, so each is within
        # beta * d / (1 - d) of the fixed point; twice that bounds the gap
        d, beta = PR_PARAMS["delta"], PR_PARAMS["beta"]
        ppr_l1 = [float(np.abs(np.asarray(answers[SERVE_SSSP + i], np.float64)
                               - ref.ppr(int(s), d, beta, PR_PARAMS["maxIter"])).sum())
                  for i, s in enumerate(ppr_srcs[:2])]
        ppr_tol = 2 * beta * d / (1 - d) + 1e-5
        return {"register_s": register_s, "serve_s": serve_s,
                "reference_s": time.perf_counter() - t0,
                "sssp_queries": SERVE_SSSP, "ppr_queries": SERVE_PPR,
                "sweeps": stats["sweeps"], "mean_batch": stats["mean_batch"],
                "max_batch": stats["max_batch"], "served": stats["served"],
                "sssp_checked": SERVE_CHECKED, "ppr_l1": ppr_l1,
                "ok": bool(sssp_ok and max(ppr_l1) <= ppr_tol
                           and stats["served"] == SERVE_SSSP + SERVE_PPR)}
    return run


def phase_refresh(seed: int):
    def run(st):
        g, prev = st["g"], st["sssp"]
        rng = np.random.default_rng(seed + 2)
        adds = rng.integers(0, g.num_nodes, size=(REFRESH_INSERTS, 2))
        wts = rng.integers(1, 101, size=REFRESH_INSERTS)
        t0 = time.perf_counter()
        delta = g.update(adds=adds, weights=wts)
        update_s = time.perf_counter() - t0
        bound = _bind("sssp", delta.graph)
        warm, refresh_s = _timed(lambda: bound.refresh(prev, delta, src=0))
        cold, scratch_s = _timed(lambda: bound(src=0))
        plan = delta.plan()
        return {"inserted": REFRESH_INSERTS, "update_host_s": update_s,
                "refresh_s": refresh_s, "scratch_s": scratch_s,
                "affected_frac": float(plan.affected_frac),
                "incremental": bool(plan.affected_frac
                                    <= bound.program.schedule.refresh_threshold_frac),
                "ok": bool(np.array_equal(np.asarray(warm["dist"]),
                                          np.asarray(cold["dist"])))}
    return run


# --------------------------------------------------------------------------
# four chips: the distributed backends against the local one
# --------------------------------------------------------------------------

def phase_dist(name: str, seed: int):
    def run(st):
        g, ref = st["g"], st["ref"]
        mesh = dist.make_mesh_1d(len(st["devices"]))
        if name == "sssp":
            params, key, out_key = dict(src=0), "local_sssp", "dist"
        elif name == "pr":
            params, key, out_key = PR_PARAMS, "local_pr", "pageRank"
        else:
            params = dict(sourceSet=_sources(g, BC_SOURCES, seed))
            key, out_key = "local_bc", "BC"
        local = _local(st, name, key, params)
        bound = _bind(name, g, backend="distributed", mesh=mesh)
        out, first = _timed(lambda: bound(**params))
        _, second = _timed(lambda: bound(**params))
        got, base = out[out_key], local[out_key]
        info = {"shards": len(st["devices"]), "first_call_s": first,
                "second_call_s": second}
        t0 = time.perf_counter()
        if name == "sssp":
            ok = np.array_equal(got, base) and np.array_equal(got, ref.sssp(0))
            info["ok"] = bool(ok)
        elif name == "pr":
            vs_local = _pr_errors(got, np.asarray(base, np.float64))
            vs_ref = _pr_errors(got, ref.pagerank(int(out["iterCount"]), PR_PARAMS["delta"]))
            info.update(iterations=int(out["iterCount"]),
                        local_iterations=int(local["iterCount"]),
                        max_abs_err_local=vs_local["max_abs_err"],
                        max_abs_err_ref=vs_ref["max_abs_err"],
                        ok=vs_local["ok"] and vs_ref["ok"])
        else:
            vs_local = _bc_errors(got, np.asarray(base, np.float64))
            vs_ref = _bc_errors(got, ref.bc(params["sourceSet"]))
            info.update(max_rel_err_local=vs_local["max_rel_err"],
                        max_rel_err_ref=vs_ref["max_rel_err"],
                        ok=vs_local["ok"] and vs_ref["ok"])
        info["reference_s"] = time.perf_counter() - t0
        return info
    return run


def phase_2d(name: str):
    def run(st):
        g, ref = st["g"], st["ref"]
        side = int(round(len(st["devices"]) ** 0.5))
        mesh = dist.make_mesh((side, side), ("data", "model"),
                              devices=np.asarray(st["devices"]))
        if name == "sssp_2d":
            local = np.asarray(_local(st, "sssp", "local_sssp", dict(src=0))["dist"])
            got, first = _timed(lambda: sssp_2d(g, mesh, 0))
            _, second = _timed(lambda: sssp_2d(g, mesh, 0))
            got = np.asarray(got)
            return {"mesh": [side, side], "first_call_s": first, "second_call_s": second,
                    "ok": bool(np.array_equal(got, local)
                               and np.array_equal(got, ref.sssp(0)))}
        local = _local(st, "pr", "local_pr", PR_PARAMS)
        kw = dict(delta=PR_PARAMS["delta"], beta=PR_PARAMS["beta"],
                  max_iter=PR_PARAMS["maxIter"])
        got, first = _timed(lambda: pagerank_2d(g, mesh, **kw))
        _, second = _timed(lambda: pagerank_2d(g, mesh, **kw))
        vs_local = _pr_errors(got, np.asarray(local["pageRank"], np.float64))
        vs_ref = _pr_errors(got, ref.pagerank(int(local["iterCount"]), PR_PARAMS["delta"]))
        return {"mesh": [side, side], "first_call_s": first, "second_call_s": second,
                "max_abs_err_local": vs_local["max_abs_err"],
                "max_abs_err_ref": vs_ref["max_abs_err"],
                "ok": vs_local["ok"] and vs_ref["ok"]}
    return run


# --------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the distributed paths, on four chips")
    ap.add_argument("--scale", type=int, default=None,
                    help=f"RMAT scale, 2^scale nodes (default {FULL_SCALE})")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_persistent_cache()

    smoke = Smoke()
    scale = FULL_SCALE if args.scale is None else args.scale
    smoke.phase("device", phase_device(args.chips, allow_cpu=args.scale is not None))
    if "devices" in smoke.state:
        smoke.phase("graph", phase_graph(scale, args.seed))
        if args.chips == 1:
            smoke.phase("sssp", phase_sssp, "g")
            smoke.phase("pr", phase_pr, "g")
            smoke.phase("bc", phase_bc(args.seed), "g")
            # the wedge kernel pads rows to the max degree: a small-world
            # graph, not the power-law one, with nodes in step with the scale
            smoke.phase("tc", phase_tc(args.seed, TC_NODES >> (FULL_SCALE - scale)))
            smoke.phase("serve", phase_serve(args.seed), "g")
            smoke.phase("refresh", phase_refresh(args.seed), "sssp")
        else:
            for name in ("sssp", "pr", "bc"):
                smoke.phase(f"dist_{name}", phase_dist(name, args.seed), "g")
            smoke.phase("sssp_2d", phase_2d("sssp_2d"), "g")
            smoke.phase("pagerank_2d", phase_2d("pagerank_2d"), "g")

    device = smoke.state.get("device", {})
    ok = smoke.ok and device.get("platform") == "tpu" and device.get("count", 0) >= args.chips
    print(json.dumps({"ok": bool(ok), "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
