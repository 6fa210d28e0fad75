"""Serving-layer benchmark: coalesced vs one-query-per-sweep SSSP serving.

Drives `repro.serve.GraphService` with an open-loop Poisson arrival
process (requests arrive on their own clock, whether or not the server
has kept up — the honest way to measure a service, since a closed loop
self-throttles and hides queueing collapse). At each arrival rate the
same query stream is served twice:

* **coalesced** — the dispatcher packs up to `Schedule.batch_sources`
  concurrent queries into one batched [N, B] SpMM sweep (waiting at most
  `max_wait_ms` for lane-mates);
* **per_query** — coalescing disabled: every query runs as its own sweep
  through the bound compiled program (what serving looked like before
  this layer).

Reported per (mode, rate): achieved queries/sec, p50/p99 latency from the
*scheduled* arrival time (so backlog shows up as latency), mean lane
occupancy, sweeps, and admission/timeout counts. Every served answer is
asserted equal to the numpy reference oracle (`sssp_ref`, memoized per
unique source). The full run emits BENCH_serve.json with a headline
coalesced/per-query throughput ratio at the saturating (top) rate.

    PYTHONPATH=src python benchmarks/bench_serve.py [--tiny]
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os

import jax
import numpy as np

from repro.core import get_context
from repro.graph import preferential_attachment
from repro.graph.algorithms_ref import sssp_ref
from repro.schedule import Schedule
from repro.serve import (GraphService, ServiceConfig, ServiceOverloaded,
                         ServiceTimeout)
from repro.xla_cache import use_persistent_cache

OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_serve.json")
TIMEOUT_S = 60.0          # per-request deadline the p99 must stay under


def make_service(g, *, coalesce: bool, width: int, max_wait_ms: float):
    svc = GraphService(ServiceConfig(
        backend="local", schedule=Schedule(batch_sources=width),
        coalesce=coalesce, max_wait_ms=max_wait_ms, max_pending=1 << 16,
        default_timeout_s=TIMEOUT_S))
    svc.register_graph("g", g, kinds=["sssp"])
    return svc


async def warmup(svc, width: int):
    """Pay every jit trace before timing: bursts of exactly k concurrent
    queries for each power-of-two lane occupancy the load can produce."""
    k = 1
    while k <= width:
        await asyncio.gather(*(svc.query("g", "sssp", src=s % 7)
                               for s in range(k)))
        k *= 2


async def run_load(svc, srcs: np.ndarray, rate: float, seed: int) -> dict:
    """Open-loop Poisson load: query i arrives at t_i (exponential gaps at
    `rate`/s) regardless of server progress; latency is measured from the
    scheduled arrival, so a backlog is charged to the server."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / rate, size=len(srcs))
    arrivals = np.cumsum(gaps)
    loop = asyncio.get_running_loop()
    t0 = loop.time() + 0.05          # small lead so task 0 isn't already late

    async def one(i):
        at = t0 + arrivals[i]
        delay = at - loop.time()
        if delay > 0:
            await asyncio.sleep(delay)
        try:
            res = await svc.query("g", "sssp", src=int(srcs[i]))
        except ServiceOverloaded:
            return ("rejected", i, None, loop.time() - at)
        except ServiceTimeout:
            return ("timeout", i, None, loop.time() - at)
        return ("ok", i, res, loop.time() - at)

    st0 = svc.stats()       # counters are service-cumulative: diff per run
    outcomes = await asyncio.gather(*(one(i) for i in range(len(srcs))))
    end = loop.time()
    st1 = svc.stats()
    lat = np.array([o[3] for o in outcomes if o[0] == "ok"])
    served = [(o[1], o[2]) for o in outcomes if o[0] == "ok"]
    sweeps = st1["sweeps"] - st0["sweeps"]
    return {
        "offered_rate_qps": rate,
        "queries": len(srcs),
        "served": len(served),
        "rejected": sum(o[0] == "rejected" for o in outcomes),
        "timeouts": sum(o[0] == "timeout" for o in outcomes),
        "qps": round(len(served) / (end - t0), 2),
        "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 2),
        "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 2),
        "max_ms": round(float(lat.max()) * 1e3, 2),
        "sweeps": sweeps,
        "mean_batch": round(len(served) / sweeps, 2) if sweeps else 0.0,
        "_served": served,    # stripped before JSON; oracle-checked by caller
    }


def verify(g, srcs, served, oracle_cache) -> int:
    """Assert every served distance row equals the reference oracle."""
    for i, res in served:
        s = int(srcs[i])
        if s not in oracle_cache:
            oracle_cache[s] = sssp_ref(g, s).astype(np.int32)
        assert np.array_equal(np.asarray(res), oracle_cache[s]), \
            f"served SSSP from {s} != oracle"
    return len(served)


async def bench(args, g, rates, n_queries, width, results):
    rng = np.random.default_rng(0)
    pool = rng.integers(0, g.num_nodes,
                        size=args.unique_sources).astype(np.int32)
    srcs = pool[rng.integers(0, len(pool), size=n_queries)]
    oracle_cache: dict = {}
    checked = 0

    for mode, coalesce in (("coalesced", True), ("per_query", False)):
        svc = make_service(g, coalesce=coalesce, width=width,
                           max_wait_ms=args.max_wait_ms)
        async with svc:
            await warmup(svc, width if coalesce else 1)
            for rate in rates:
                run = await run_load(svc, srcs, rate, seed=42)
                checked += verify(g, srcs, run.pop("_served"), oracle_cache)
                results["runs"][f"{mode}@{rate}"] = run
                print(f"[{mode:>9} @ {rate:5g} q/s] served {run['served']:4d}"
                      f"  qps={run['qps']:8.1f}  p50={run['p50_ms']:8.1f}ms"
                      f"  p99={run['p99_ms']:8.1f}ms"
                      f"  sweeps={run['sweeps']:4d}"
                      f"  lane occupancy={run['mean_batch']:5.2f}")
    results["oracle"] = {"unique_sources": len(oracle_cache),
                        "results_verified": checked}
    print(f"oracle: all {checked} served results verified against sssp_ref "
          f"({len(oracle_cache)} unique sources)")

    top = rates[-1]
    co, pq = (results["runs"][f"{m}@{top}"] for m in ("coalesced",
                                                      "per_query"))
    results["headline"] = {
        "saturating_rate_qps": top,
        "coalesced_qps": co["qps"],
        "per_query_qps": pq["qps"],
        "qps_ratio": round(co["qps"] / pq["qps"], 2),
        "coalesced_p99_ms": co["p99_ms"],
        "deadline_ms": TIMEOUT_S * 1e3,
        "p99_under_deadline": co["p99_ms"] < TIMEOUT_S * 1e3
        and co["timeouts"] == 0,
    }
    h = results["headline"]
    print(f"headline @ {top} q/s: coalesced {h['coalesced_qps']} q/s vs "
          f"per-query {h['per_query_qps']} q/s -> {h['qps_ratio']}x; "
          f"coalesced p99 {h['coalesced_p99_ms']} ms < deadline "
          f"{h['deadline_ms']:.0f} ms: {h['p99_under_deadline']}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI-sized graph + load (no JSON emitted)")
    ap.add_argument("--max-wait-ms", type=float, default=10.0)
    ap.add_argument("--unique-sources", type=int, default=None,
                    help="distinct query sources (each oracle-checked once)")
    args = ap.parse_args()

    if args.tiny:
        g = preferential_attachment(800, m=6, seed=1)
        rates, n_queries, width = [50.0, 400.0], 48, 8
        args.unique_sources = args.unique_sources or 12
    else:
        g = preferential_attachment(12000, m=8, seed=1)
        rates, n_queries, width = [50.0, 200.0, 800.0], 320, 32
        args.unique_sources = args.unique_sources or 32

    stats = get_context(g).stats()
    print(f"graph: N={g.num_nodes} E={g.num_edges} deg_cv={stats['deg_cv']} "
          f"skew={stats['skew']} | width={width} "
          f"max_wait={args.max_wait_ms}ms queries={n_queries}")
    results = {
        "backend": jax.default_backend(),
        "config": {"tiny": args.tiny, "width": width,
                   "max_wait_ms": args.max_wait_ms, "rates": rates,
                   "queries": n_queries, "timeout_s": TIMEOUT_S,
                   "unique_sources": args.unique_sources},
        "graph": stats,
        "runs": {},
    }
    asyncio.run(bench(args, g, rates, n_queries, width, results))

    if not args.tiny:
        with open(OUT_PATH, "w") as f:
            json.dump(results, f, indent=2)
        print(f"wrote {os.path.normpath(OUT_PATH)}")


if __name__ == "__main__":
    use_persistent_cache()
    main()
