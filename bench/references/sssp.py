"""Plain single-source shortest paths, float64 Dijkstra (scipy), from the
benchmark's edge list. Unreachable vertices read inf.

`stopped_short` is a control: a frontier Bellman-Ford (sssp.sp's own
supersteps) that stops one superstep before its fixed point.
"""
import numpy as np
from scipy.sparse.csgraph import dijkstra

from edgelist import directed, matrices


def distances(edges: dict, roots: list) -> dict:
    """Distances from each root, and the edges out of the vertices each
    reaches."""
    _, weights = matrices(edges, weighted=True)
    dist = dijkstra(weights, directed=True, indices=[int(r) for r in roots])
    out_degree = np.diff(weights.indptr)
    return {"dist": list(dist), "num_edges": int(weights.nnz),
            "reached_edges": [int(out_degree[np.isfinite(d)].sum()) for d in dist]}


def stopped_short(edges: dict, root: int) -> dict:
    """Distances one changing superstep before the fixed point."""
    src, dst, w = directed(edges)
    order = np.argsort(dst, kind="stable")
    src, dst, w = src[order], dst[order], w[order].astype(np.float64)
    targets, starts = np.unique(dst, return_index=True)
    dist = np.full(edges["n"], np.inf)
    dist[int(root)] = 0.0
    frontier = np.zeros(edges["n"], bool)
    frontier[int(root)] = True
    before, supersteps = dist.copy(), 0
    while frontier.any():
        cand = np.where(frontier[src], dist[src] + w, np.inf)
        best = np.full(edges["n"], np.inf)
        best[targets] = np.minimum.reduceat(cand, starts)
        new = np.minimum(dist, best)
        frontier = new < dist
        if frontier.any():
            before, supersteps = dist, supersteps + 1
        dist = new
    return {"dist": before, "supersteps": supersteps}
