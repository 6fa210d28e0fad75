"""Graph partitioning for the distributed backend.

Two schemes:

1. `blocks_1d` — the paper's MPI scheme (§3.1/§4.2): contiguous
   equal-size vertex blocks per device ("index-based partitioning"), with the
   last block padded ("we pad temporary vertices for the last process").
   Every device owns the out-edges of its vertex block, which in a CSR are
   one contiguous slice. Per-device edge counts differ, so each device's
   edge array is padded to the global max with harmless sentinel edges
   (src=dst=0, weight=INF, valid=0); `runtime_dist.prepare_graph_1d` puts
   each device's slices on that device alone.

2. `partition_2d` — beyond-paper CombBLAS-style 2-D partitioning for the
   (data × model) mesh. The adjacency is blocked into R×C tiles; device
   (i, j) holds edges with dst ∈ block_i (contiguous, size N/R) and
   src ∈ colset_j (the interleaved pieces {b : b mod C == j}). Vertex state
   is sharded N/(R·C) per device (piece b = i*C + j). One relax step is then
     x_j  = all_gather(own piece, axis='data')          # N/C per device
     part = local semiring product over the tile        # N/R per device
     own' = reduce_scatter(part, axis='model', combiner)# N/(R·C)
   i.e. O(N/C + N/R) collective bytes/device/step instead of the 1-D O(N).

`partition_2d` produces host-side numpy arrays stacked on leading device
axes so they can be dropped straight into `shard_map` via NamedSharding.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from .csr import CSRGraph, INF_I32


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


@dataclasses.dataclass(frozen=True)
class Blocks1D:
    """Where each device's edges lie in a CSR: device d owns vertices
    [d*block, (d+1)*block), and the CSR being sorted by the blocked vertex,
    its edges are the one slice bounds[d]:bounds[d+1]."""
    block: int           # vertices per device (padded)
    bounds: np.ndarray   # int64[P+1] edge offsets
    emax: int            # the longest device's edge count (at least 1)

    def row(self, arr, d: int, fill) -> np.ndarray:
        """Device d's slice of the E-sized `arr`, padded with `fill` to
        Emax: int32[Emax]."""
        lo, hi = self.bounds[d], self.bounds[d + 1]
        out = np.empty(self.emax, np.int32)
        out[:hi - lo] = arr[lo:hi]
        out[hi - lo:] = fill
        return out

    def valid(self, d: int) -> np.ndarray:
        """bool[Emax]: which of device d's slots hold an edge."""
        return np.arange(self.emax) < self.bounds[d + 1] - self.bounds[d]


def blocks_1d(indptr, num_nodes: int, num_devices: int) -> Blocks1D:
    """The 1-D block layout of a CSR with row pointers `indptr` over
    `num_devices` devices, the last block padded."""
    block = _ceil_div(num_nodes, num_devices)
    first = np.minimum(np.arange(num_devices + 1) * block, num_nodes)
    bounds = np.asarray(indptr, np.int64)[first]
    emax = max(int(np.diff(bounds).max(initial=0)), 1)
    return Blocks1D(block, bounds, emax)


@dataclasses.dataclass(frozen=True)
class Partition2D:
    """Adjacency tiles for an R×C (data × model) mesh.

    Index remapping (all host-side, baked into the edge arrays):
      - `src_local[i,j,e]` = position of the edge's source inside the
        all-gathered x_j (the i-ordered concat of pieces {b*C + j}).
      - `dst_local[i,j,e]` = position of the edge's dest inside dst block i
        (contiguous range [i*N/R, (i+1)*N/R)).
    """
    src_local: np.ndarray   # int32[R, C, Emax]
    dst_local: np.ndarray   # int32[R, C, Emax]
    weight: np.ndarray      # int32[R, C, Emax]
    valid: np.ndarray       # bool [R, C, Emax]
    rows: int               # R (data axis size)
    cols: int               # C (model axis size)
    piece: int              # vertices per device piece (padded)
    num_nodes_padded: int

    @property
    def block_rows(self) -> int:   # dst block size N/R
        return self.piece * self.cols

    @property
    def block_cols(self) -> int:   # src block size N/C
        return self.piece * self.rows


def partition_2d(g: CSRGraph, rows: int, cols: int) -> Partition2D:
    r, c = rows, cols
    piece = _ceil_div(g.num_nodes, r * c)
    n_pad = piece * r * c
    src = np.asarray(g.edge_src).astype(np.int64)
    dst = np.asarray(g.indices).astype(np.int64)
    w = np.asarray(g.weights)

    # piece id of a vertex v: b = v // piece ; owner (i, j): i = b // c, j = b % c
    b_src = src // piece
    b_dst = dst // piece
    j_of = (b_src % c).astype(np.int64)          # src column set
    i_of = (b_dst // c).astype(np.int64)         # dst row block
    # position of src inside gathered x_j: pieces ordered by i' = b // c
    src_local = (b_src // c) * piece + (src % piece)
    # position of dst inside contiguous dst block i
    dst_local = dst - i_of * (piece * c)

    tile = i_of * c + j_of
    counts = np.bincount(tile, minlength=r * c)
    emax = max(int(counts.max()) if len(src) else 0, 1)
    o_src = np.zeros((r, c, emax), np.int32)
    o_dst = np.zeros((r, c, emax), np.int32)
    o_w = np.full((r, c, emax), int(INF_I32), np.int32)
    o_valid = np.zeros((r, c, emax), bool)
    for i in range(r):
        for j in range(c):
            sel = tile == (i * c + j)
            k = int(sel.sum())
            o_src[i, j, :k] = src_local[sel]
            o_dst[i, j, :k] = dst_local[sel]
            o_w[i, j, :k] = w[sel]
            o_valid[i, j, :k] = True
    return Partition2D(o_src, o_dst, o_w, o_valid, r, c, piece, n_pad)


def piece_order_to_global(part: Partition2D) -> np.ndarray:
    """global_id[i, j, k] for piece-sharded state: device (i,j) owns
    vertices [(i*C + j)*piece, ...+piece)."""
    r, c, piece = part.rows, part.cols, part.piece
    base = (np.arange(r * c) * piece).reshape(r, c)
    return base[..., None] + np.arange(piece)[None, None, :]
