"""Plain PageRank, float64, from the benchmark's edge list.

pr.sp's semantics: rank starts at 1/N; each sweep pulls rank / out-degree
over in-edges, new = (1 - delta) / N + delta * sum; a do-while loop that
runs while the L1 change exceeds beta and fewer than max_iter sweeps have
run. The loop stops by its own rule, not the program's iteration count.

`store` rounds every stored vector to a narrower type (the lower-precision
control); sums are then accumulated in float32.
"""
import numpy as np

from edgelist import matrices


def pagerank(edges: dict, delta: float, beta: float, max_iter: int,
             store=np.float64) -> dict:
    adj, _ = matrices(edges, weighted=False)
    n = edges["n"]
    out_deg = np.diff(adj.indptr)
    inv_out = np.where(out_deg > 0, 1.0 / np.maximum(out_deg, 1), 0.0)
    pull = adj.T                       # in-edges: row v sums over sources u
    acc = np.float64 if store is np.float64 else np.float32
    if acc is not np.float64:
        pull = pull.astype(acc)
    rnd = (lambda x: x) if store is np.float64 else (lambda x: x.astype(store).astype(acc))
    rank = rnd(np.full(n, 1.0 / n, acc))
    iterations = 0
    while True:
        contrib = rnd((rank * inv_out).astype(acc))
        nxt = rnd(((1 - delta) / n + delta * (pull @ contrib)).astype(acc))
        diff = float(np.abs(nxt.astype(np.float64) - rank).sum())
        rank, iterations = nxt, iterations + 1
        if not (diff > beta and iterations < max_iter):
            break
    return {"rank": rank.astype(np.float64), "iterations": iterations,
            "num_edges": int(adj.nnz)}
