"""Multi-tenant graph query serving: `GraphService` end to end.

This example drives the async serving layer the engine API exists for
(`repro.serve.GraphService`): a server answering SSSP/BFS/BC queries for
many concurrent users, across several registered graphs, must never
re-parse DSL source, re-generate code, or rebuild per-graph views on the
query path — and should *coalesce* concurrent compatible queries into one
batched [N, B]-lane sweep. Everything expensive happens at registration:

  * `register_graph(name, g)` — fingerprints the graph, warm-reloads any
    persisted `TuningStore` record (tuned schedule without a measurement
    sweep), compiles the bundled programs through the compile cache,
    prepares the graph's derived views, and memoizes `prog.bind(g)`;
  * `await service.query(graph, kind, src=...)` — admission-checked,
    coalesced with concurrent lane-mates (up to `Schedule.batch_sources`
    per sweep, waiting at most `max_wait_ms`), answered from one batched
    sweep's per-source rows.

With `--autotune`, the server tunes the schedule per (program, graph)
before registering (`repro.autotune`); `--tune-store PATH` persists the
records so the next server start warm-reloads instead of re-measuring.
Every served answer is verified against the numpy reference oracles.

    PYTHONPATH=src python examples/query_server.py [--smoke] [--autotune]
"""
import argparse
import asyncio
import time

import numpy as np

from repro.autotune import TuningStore, autotune
from repro.core import compile_bundled
from repro.graph import preferential_attachment
from repro.graph.algorithms_ref import bc_ref, sssp_ref
from repro.serve import GraphService, ServiceConfig
from repro.xla_cache import use_persistent_cache


async def serve(args, svc: GraphService, graphs: dict):
    rng = np.random.default_rng(0)

    # ---- fire concurrent SSSP queries across users AND graphs -----------
    queries = []   # (graph name, src)
    for name, g in graphs.items():
        for s in rng.integers(0, g.num_nodes, args.queries):
            queries.append((name, int(s)))
    rng.shuffle(queries)

    t0 = time.perf_counter()
    results = await asyncio.gather(
        *(svc.query(name, "sssp", src=s) for name, s in queries))
    total = time.perf_counter() - t0
    st = svc.stats()
    print(f"SSSP: {len(queries)} concurrent queries over {len(graphs)} "
          f"graphs in {total:.2f} s ({len(queries) / total:.1f} q/s; "
          f"first sweep pays the jit trace)")
    print(f"  coalescing: {st['sweeps']} sweeps, mean lane occupancy "
          f"{st['mean_batch']:.1f}, max {st['max_batch']}")

    # verify EVERY served answer against the reference oracle
    oracle = {}
    for (name, s), dist in zip(queries, results):
        key = (name, s)
        if key not in oracle:
            oracle[key] = sssp_ref(graphs[name], s).astype(np.int32)
        assert np.array_equal(np.asarray(dist), oracle[key]), key
    print(f"  verified: all {len(queries)} answers == numpy oracle")

    # ---- a BC request serves its own source set through the [N, B] lanes
    name, g = next(iter(graphs.items()))
    srcs = rng.integers(0, g.num_nodes, args.batch).astype(np.int32)
    t0 = time.perf_counter()
    bc = await svc.query(name, "bc", sourceSet=srcs)
    print(f"BC: {len(srcs)}-source aggregate on {name!r} in "
          f"{1e3 * (time.perf_counter() - t0):.1f} ms "
          f"(top node {int(np.asarray(bc).argmax())})")
    np.testing.assert_allclose(np.asarray(bc), bc_ref(g, srcs.tolist()),
                               atol=1e-3)
    print("  verified: BC == numpy oracle")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="pallas", choices=["local", "pallas"])
    ap.add_argument("--nodes", type=int, default=4000)
    ap.add_argument("--batch", type=int, default=16,
                    help="Schedule.batch_sources — lanes per coalesced sweep")
    ap.add_argument("--queries", type=int, default=64,
                    help="concurrent SSSP queries per graph")
    ap.add_argument("--max-wait-ms", type=float, default=10.0,
                    help="coalescing deadline for a partial lane")
    ap.add_argument("--smoke", action="store_true", help="CI-sized run")
    ap.add_argument("--autotune", action="store_true",
                    help="tune the schedule per (program, graph) at startup")
    ap.add_argument("--tune-budget", type=int, default=8,
                    help="candidate schedules measured per program")
    ap.add_argument("--tune-store", default=None, metavar="PATH",
                    help="persist tuning records; later starts warm-reload "
                         "instead of re-measuring")
    args = ap.parse_args()
    if args.smoke:
        args.nodes, args.batch, args.queries = 600, 8, 16
        args.tune_budget = min(args.tune_budget, 4)

    from repro.schedule import Schedule
    sched = Schedule(batch_sources=args.batch)
    graphs = {
        "social": preferential_attachment(args.nodes, m=6, seed=3),
        "web": preferential_attachment(max(args.nodes // 2, 200), m=4, seed=11),
    }
    for name, g in graphs.items():
        print(f"graph {name!r}: {g.num_nodes} nodes, {g.num_edges} edges")
    print(f"backend={args.backend} | batch_sources={sched.batch_sources} | "
          f"max_wait_ms={args.max_wait_ms}")

    store = TuningStore(args.tune_store) if args.tune_store else None
    if args.autotune:
        # tune once per (program, graph); the service then WARM-RELOADS the
        # records at registration (keyed source digest + graph fingerprint),
        # so a restarted server never re-measures. NB: `store or ...` would
        # discard an EMPTY path-backed store (TuningStore has __len__)
        if store is None:
            store = TuningStore()
        t0 = time.perf_counter()
        for pname in ("sssp", "bc"):
            prog = compile_bundled(pname, backend=args.backend, schedule=sched)
            for gname, g in graphs.items():
                res = autotune(prog, g, budget=args.tune_budget, seed=0,
                               store=store)
                how = ("warm-reloaded" if res.from_store
                       else f"{len(res.record.trials)} trials")
                print(f"autotune[{pname}/{gname}]: {how}, best "
                      f"{res.speedup:.2f}x -> {res.schedule}")
        print(f"autotune total: {time.perf_counter() - t0:.1f} s")

    svc = GraphService(
        ServiceConfig(backend=args.backend, schedule=sched,
                      max_wait_ms=args.max_wait_ms),
        tune_store=store)
    t0 = time.perf_counter()
    for name, g in graphs.items():
        h = svc.register_graph(name, g)
        tuned = f" (tuned: {', '.join(h.tuned)})" if h.tuned else ""
        print(f"register_graph({name!r}): "
              f"{1e3 * (time.perf_counter() - t0):.0f} ms — compiled, "
              f"prepared, bound{tuned}")
        t0 = time.perf_counter()

    async def run():
        async with svc:
            await serve(args, svc, graphs)

    asyncio.run(run())


if __name__ == "__main__":
    use_persistent_cache()
    main()
