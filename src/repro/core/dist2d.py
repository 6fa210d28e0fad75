"""Beyond-paper distributed path: 2-D adjacency partitioning.

The paper's MPI backend is 1-D: every BSP step moves O(N) property bytes
per process (all-gather of the frontier + combine of candidates). That is
fine at 96 ranks and fatal at 512+. The classic fix (CombBLAS / 2-D SpMV)
blocks the adjacency over an R×C device grid so each step moves only

    all_gather along 'data'  : N/C   bytes per device (source block)
    reduce-scatter 'model'   : N/C   bytes per device (dest partials)

i.e. O(N/√P) for a square grid — a 16× collective-byte reduction on the
16×16 production mesh. State lives as N/(R·C) pieces per device; the edge
tiles carry pre-remapped local indices (graph/partition.py:partition_2d).

These steps are validated against the NumPy oracles across mesh shapes in
tests/test_dist2d.py (plus the single-shape checks in
tests/test_distributed.py); benchmarks/bench_table5_mpi.py times them
against the 1-D backend.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..graph.csr import CSRGraph, INF_I32
from ..graph.partition import partition_2d
from . import runtime as rt

DATA, MODEL = "data", "model"


def prepare_graph_2d(g: CSRGraph, rows: int, cols: int) -> dict:
    """Edge tiles + metadata, stacked [R, C, ...] for shard_map."""
    part = partition_2d(g, rows, cols)
    return {
        "src_local": part.src_local,
        "dst_local": part.dst_local,
        "weight": part.weight,
        "valid": part.valid,
        "piece": part.piece,            # static
        "rows": rows, "cols": cols,     # static
        "n_true": g.num_nodes,
        "out_degree": np.asarray(g.out_degree),
    }


def _place(mesh, spec, x):
    """Put a host array on `mesh` with its final sharding, so the jitted
    step never reshards it from the default device."""
    return jax.device_put(x, NamedSharding(mesh, spec))


def _own_global_ids(piece, c):
    i = jax.lax.axis_index(DATA)
    j = jax.lax.axis_index(MODEL)
    b = i * c + j
    return b * piece + jnp.arange(piece, dtype=jnp.int32)


def _reduce_scatter_min(part, c, piece):
    """Min-reduce-scatter along 'model' via all_to_all + local min.
    part: [C * piece] destination-block candidates."""
    chunks = part.reshape(c, piece)
    swapped = jax.lax.all_to_all(chunks, MODEL, split_axis=0, concat_axis=0)
    return jnp.min(swapped, axis=0)


def _reduce_scatter_sum(part, c, piece):
    return jax.lax.psum_scatter(part.reshape(c, piece), MODEL,
                                scatter_dimension=0, tiled=False).reshape(piece)


# --------------------------------------------------------------------------
# SSSP (2-D relax until fixed point)
# --------------------------------------------------------------------------

def sssp_2d(g: CSRGraph, mesh, src: int = 0):
    r, c = mesh.shape[DATA], mesh.shape[MODEL]
    gd = prepare_graph_2d(g, r, c)
    piece = gd["piece"]

    def body(src_local, dst_local, weight, valid, src_id):
        src_local, dst_local = src_local[0, 0], dst_local[0, 0]
        weight, valid = weight[0, 0], valid[0, 0]
        own = _own_global_ids(piece, c)
        dist = jnp.where(own == src_id, 0, INF_I32).astype(jnp.int32)
        block_rows = piece * c     # destination block size N/R

        def cond(state):
            return ~state[1]

        def step(state):
            dist, _ = state
            xj = jax.lax.all_gather(dist, DATA, tiled=True)       # [piece*R]
            cand = jnp.where(valid, xj[src_local] + weight, INF_I32)
            part = rt.segment_min(cand, dst_local, block_rows, sorted_ids=False)
            new = jnp.minimum(dist, _reduce_scatter_min(part, c, piece))
            changed = jnp.any(new < dist)
            changed = jax.lax.psum(changed.astype(jnp.int32), DATA)
            changed = jax.lax.psum(changed, MODEL) > 0
            return new, ~changed

        dist, _ = jax.lax.while_loop(cond, step, (dist, jnp.bool_(False)))
        return dist

    tile = P(DATA, MODEL, None)
    fn = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(tile,) * 4 + (P(),),
        out_specs=P((DATA, MODEL)), check_vma=False))
    out = fn(*(_place(mesh, tile, gd[k])
               for k in ("src_local", "dst_local", "weight", "valid")),
             jnp.int32(src))
    return out[: g.num_nodes]


# --------------------------------------------------------------------------
# PageRank (2-D gather until convergence)
# --------------------------------------------------------------------------

def pagerank_2d(g: CSRGraph, mesh, delta: float = 0.85, beta: float = 1e-4,
                max_iter: int = 100):
    # PR pulls over in-edges of v, i.e. exactly the original edge set u→v:
    # tile (i,j) holds edges with v=dst ∈ block_i (accumulator side, 'data')
    # and u=src ∈ colset_j (contributor side, 'model').
    r, c = mesh.shape[DATA], mesh.shape[MODEL]
    gd = partition_2d(g, r, c)
    piece = gd.piece
    n = g.num_nodes
    deg_pad = np.zeros(piece * r * c, np.float32)
    deg_pad[:n] = np.maximum(np.asarray(g.out_degree), 1)
    # out-degree of the gathered source block, in x_j (i-interleaved) order
    deg_blocks = deg_pad.reshape(r * c, piece)   # piece b
    # piece b = i*c + j → column j gathers pieces [j, c+j, 2c+j, ...] in i order
    deg_xj = np.stack([deg_blocks[np.arange(r) * c + j].reshape(-1)
                       for j in range(c)])       # [C, piece*R]

    def body(src_local, dst_local, valid, deg_j):
        src_local, dst_local, valid = src_local[0, 0], dst_local[0, 0], valid[0, 0]
        deg_j = deg_j[0]
        own = _own_global_ids(piece, c)
        pr = jnp.full((piece,), 1.0 / n, jnp.float32)
        block_rows = piece * c

        def cond(state):
            _, diff, it, first = state
            return first | ((diff > beta) & (it < max_iter))

        def step(state):
            pr, _, it, _ = state
            xj = jax.lax.all_gather(pr, DATA, tiled=True)         # [piece*R]
            contrib = xj / deg_j
            term = jnp.where(valid, contrib[src_local], 0.0)
            part = rt.segment_sum(term, dst_local, block_rows, sorted_ids=False)
            summ = _reduce_scatter_sum(part, c, piece)
            val = (1 - delta) / n + delta * summ
            val = jnp.where(own < n, val, 0.0)
            diff = jnp.sum(jnp.abs(val - pr))
            diff = jax.lax.psum(jax.lax.psum(diff, DATA), MODEL)
            return val, diff, it + 1, jnp.bool_(False)

        pr, diff, it, _ = jax.lax.while_loop(
            cond, step, (pr, jnp.float32(0), jnp.int32(0), jnp.bool_(True)))
        return pr

    tile = P(DATA, MODEL, None)
    fn = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(tile,) * 3 + (P(MODEL, None),),
        out_specs=P((DATA, MODEL)), check_vma=False))
    out = fn(_place(mesh, tile, gd.src_local), _place(mesh, tile, gd.dst_local),
             _place(mesh, tile, gd.valid), _place(mesh, P(MODEL, None), deg_xj))
    return out[: g.num_nodes]
