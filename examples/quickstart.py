"""Quickstart: compile a StarPlat program and run it on three backends.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core import compile_program
from repro.graph import uniform_random
from repro.xla_cache import use_persistent_cache

SSSP_SOURCE = """
// Single-source shortest paths (paper Fig. 3)
function Compute_SSSP(Graph g, node src) {
  propNode<int> dist;
  propNode<bool> modified;
  g.attachNodeProperty(dist = INF, modified = False);
  src.dist = 0;
  src.modified = True;
  bool finished = False;
  fixedPoint until (finished : !modified) {
    forall(v in g.nodes().filter(modified == True)) {
      forall(nbr in g.neighbors(v)) {
        edge e = g.getEdge(v, nbr);
        <nbr.dist, nbr.modified> = <Min(nbr.dist, v.dist + e.weight), True>;
      }
    }
  }
}
"""


def main():
    g = uniform_random(1000, 8, seed=42)
    print(f"graph: {g.num_nodes} nodes, {g.num_edges} edges\n")

    print("=== DSL source ===")
    print(SSSP_SOURCE)

    local = compile_program(SSSP_SOURCE, backend="local")
    print("=== generated JAX (local backend, first 25 lines) ===")
    print("\n".join(local.source.splitlines()[:25]))
    print("    ...\n")

    # bind(g) is the uniform per-graph entry point on every backend
    out = local.bind(g)(src=0)
    dist = np.asarray(out["dist"])
    reach = dist < 2**30
    print(f"local backend:   reached {reach.sum()} nodes, "
          f"max dist {dist[reach].max()}")

    pallas = compile_program(SSSP_SOURCE, backend="pallas")
    out_p = pallas.bind(g)(src=0)
    same = np.array_equal(np.asarray(out_p["dist"]), dist)
    print(f"pallas backend:  identical result: {same} "
          f"(block-ELL min-plus kernel)")

    distp = compile_program(SSSP_SOURCE, backend="distributed")
    out_d = distp.bind(g)(src=0)   # single-shard mesh in this process
    same_d = np.array_equal(np.asarray(out_d["dist"]), dist)
    print(f"distributed backend: identical result: {same_d} "
          f"({len(distp.source.splitlines())}-line per-device body under "
          "shard_map; multi-device via bind(g, mesh=...) — see "
          "examples/graph_analytics.py)")


if __name__ == "__main__":
    use_persistent_cache()
    main()
