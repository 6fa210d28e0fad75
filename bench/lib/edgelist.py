"""The benchmark's own view of a generated edge list, as scipy matrices.

It follows the graph semantics the program documents for its build: with
`undirected` each edge is also taken in reverse, with `drop_self_loops`
self-loops go, and duplicates collapse to one edge. The generators give
every copy of an edge one weight, so which copy survives does not matter.
Nothing here reads what the program built.
"""
import numpy as np
import scipy.sparse as sp


def directed(edges: dict):
    src, dst, w = edges["src"], edges["dst"], edges["w"]
    if edges["undirected"]:
        src, dst, w = (np.concatenate([src, dst]), np.concatenate([dst, src]),
                       np.concatenate([w, w]))
    if edges["drop_self_loops"]:
        keep = src != dst
        src, dst, w = src[keep], dst[keep], w[keep]
    return src, dst, w


def matrices(edges: dict, weighted: bool):
    """(adjacency with 1 per edge, weights or None), both CSR float64."""
    n = edges["n"]
    src, dst, w = directed(edges)
    count = sp.csr_matrix((np.ones(len(src)), (src, dst)), shape=(n, n))
    count.sum_duplicates()
    weights = None
    if weighted:
        weights = sp.csr_matrix((w.astype(np.float64), (src, dst)), shape=(n, n))
        weights.sum_duplicates()
        weights.data /= count.data        # one weight per edge, summed per copy
    count.data[:] = 1.0
    return count, weights
