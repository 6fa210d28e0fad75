"""Graph-level wrapper: CSR → strict-lower dense tiles → MXU triangle count.

Counts each triangle once: L[i,j] = 1 iff (i,j) ∈ E∪Eᵀ and i > j (undirected
closure, strict lower triangle); triangles = Σ (L·L)⊙L.

NOTE: the paper's Fig. 20 counts *directed* wedge closures (u < v < w with
edges v→u, v→w, u→w), which equals the undirected triangle count only for
symmetric graphs. This op computes the undirected count; the DSL's Pallas
backend uses it only after symmetrizing — tests pin both against oracles.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ...graph.csr import CSRGraph
from .kernel import tc_matmul

def prepare_lower(g: CSRGraph, block: int = 128) -> jax.Array:
    """Dense strict-lower adjacency of the undirected closure, block-padded."""
    n = g.num_nodes
    n_pad = -(-n // block) * block
    a = np.zeros((n_pad, n_pad), np.float32)
    src = np.asarray(g.edge_src)
    dst = np.asarray(g.indices)
    lo = np.minimum(src, dst)
    hi = np.maximum(src, dst)
    keep = lo != hi
    a[hi[keep], lo[keep]] = 1.0
    return jnp.asarray(a)


@partial(jax.jit, static_argnames=("block",))
def count_triangles_dense(lower: jax.Array, *, block: int = 128) -> jax.Array:
    block = min(block, lower.shape[0])
    return tc_matmul(lower, block=block,
                     interpret=jax.default_backend() != "tpu").astype(jnp.int32)
