"""End-to-end driver for the paper's workload: all four algorithms on the
(scaled) ten-graph Table-2 suite, local + pallas backends, with oracle
verification — the graph-analytics equivalent of a training run.

    PYTHONPATH=src python examples/graph_analytics.py [--backend local|pallas]
"""
import argparse
import time

import numpy as np

from repro.core import compile_bundled
from repro.graph import load_suite
from repro.graph.algorithms_ref import sssp_ref
from repro.xla_cache import use_persistent_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="local", choices=["local", "pallas"])
    ap.add_argument("--graphs", default="TW,PK,US,GR,RM,UR")
    args = ap.parse_args()

    graphs = load_suite(args.graphs.split(","))
    progs = {n: compile_bundled(n, backend=args.backend)
             for n in ["sssp", "pr", "tc", "bc"]}
    srcs = np.array([0, 3, 11, 17], np.int32)

    print(f"backend={args.backend}")
    print(f"{'graph':6s} {'algo':5s} {'ms':>10s}  result")
    for gname, g in graphs.items():
        t0 = time.perf_counter()
        out = progs["sssp"](g, src=0)
        dist = np.asarray(out["dist"])
        ms = (time.perf_counter() - t0) * 1e3
        # the pure-Python Bellman-Ford oracle is too slow past 4096 nodes
        ok = (np.array_equal(dist, sssp_ref(g, 0).astype(np.int32))
              if g.num_nodes <= 4096 else "unchecked")
        print(f"{gname:6s} sssp  {ms:10.1f}  reached={int((dist < 2**30).sum())} verified={ok}")

        t0 = time.perf_counter()
        pr = np.asarray(progs["pr"](g, beta=1e-4, delta=0.85, maxIter=100)["pageRank"])
        ms = (time.perf_counter() - t0) * 1e3
        print(f"{gname:6s} pr    {ms:10.1f}  sum={pr.sum():.4f} max={pr.max():.5f}")

        t0 = time.perf_counter()
        tc = int(progs["tc"](g)["triangle_count"])
        ms = (time.perf_counter() - t0) * 1e3
        print(f"{gname:6s} tc    {ms:10.1f}  triangles={tc}")

        t0 = time.perf_counter()
        bc = np.asarray(progs["bc"](g, sourceSet=srcs)["BC"])
        ms = (time.perf_counter() - t0) * 1e3
        print(f"{gname:6s} bc    {ms:10.1f}  top_node={int(bc.argmax())} bc_max={bc.max():.2f}")


if __name__ == "__main__":
    use_persistent_cache()
    main()
