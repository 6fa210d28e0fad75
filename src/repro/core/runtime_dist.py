"""Distributed runtime for the MPI-analogue backend (shard_map + collectives).

The paper's MPI backend (§3.2): 1-D block vertex partitioning, BSP steps of
local compute + communication, send-buffer aggregation ("a single message
with the local minimum" §4.2). Here:

  * each device owns a contiguous vertex block (`own_ids`), the last block
    padded — exactly the paper's scheme;
  * property exchange = `all_gather` (tiled) over the `data` axis, or the
    frontier-compressed `exchange` (changed entries only, through fixed
    per-shard buffers) when the compiled Schedule's `dist_frontier` policy
    asks for it;
  * update combining = `pmin`/`psum` over scattered candidate arrays — the
    communication-aggregation optimization is the collective itself;
  * the fixed-point flag = a global OR (psum of local any()).

`prepare_graph_1d` builds the device-stacked arrays consumed by the
generated per-device body. All collectives are `jax.lax` ops inside
`shard_map`, so the same generated code lowers to ICI collectives on a real
TPU mesh.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..graph.csr import CSRGraph
from ..graph.partition import block_partition_1d
from . import runtime as rt

AXIS = "data"


# --------------------------------------------------------------------------
# Graph preparation (host side)
# --------------------------------------------------------------------------

def prepare_graph_1d(g: CSRGraph, mesh, *, ell: bool = False) -> dict:
    """Partitioned arrays for the 1-D backend, placed on `mesh` once.

    Keys with leading [P] shard over the mesh 'data' axis; `*_rep` keys are
    replicated static graph structure (degree tables, the sorted edge key
    for is_an_edge). Each array is put with its `NamedSharding` here, so a
    jitted runner never reshards the graph from one device per call."""
    p = mesh.shape[AXIS]
    out = block_partition_1d(g, p)                      # out-edges by src block
    # in-edges partitioned by dst block: build from the reverse CSR
    rev = CSRGraph(
        indptr=g.rev_indptr, indices=g.rev_indices, weights=g.rev_weights,
        edge_src=g.rev_edge_dst, rev_indptr=g.indptr, rev_indices=g.indices,
        rev_weights=g.weights, rev_edge_dst=g.edge_src,
        out_degree=g.in_degree, in_degree=g.out_degree,
        edge_key=g.rev_edge_dst * jnp.int32(g.num_nodes) + g.rev_indices,
        num_nodes=g.num_nodes, num_edges=g.num_edges,
        max_out_degree=g.max_in_degree, max_in_degree=g.max_out_degree)
    inn = block_partition_1d(rev, p)                    # (dst, src) pairs by dst block
    block = out.block
    n_pad = out.num_nodes_padded
    own_ids = (np.arange(p)[:, None] * block + np.arange(block)[None, :]).astype(np.int32)

    deg_out = np.zeros(n_pad, np.int32)
    deg_out[: g.num_nodes] = np.asarray(g.out_degree)
    deg_in = np.zeros(n_pad, np.int32)
    deg_in[: g.num_nodes] = np.asarray(g.in_degree)

    gd = {
        "esrc": out.src, "edst": out.dst, "ew": out.weight, "evalid": out.valid,
        # local slot of the source vertex; padding edges clipped to 0 and
        # neutralized by the valid mask
        "esrc_local": np.clip(
            out.src - (np.arange(p) * block)[:, None], 0, block - 1).astype(np.int32),
        # in-edge arrays: src field of `inn` is the OWNED dst, dst field is the in-neighbor
        "idst": inn.src, "isrc": inn.dst, "iw": inn.weight, "ivalid": inn.valid,
        "idst_local": np.clip(
            inn.src - (np.arange(p) * block)[:, None], 0, block - 1).astype(np.int32),
        "own_ids": own_ids,
        "out_degree_rep": deg_out,
        "in_degree_rep": deg_in,
        "n_true_rep": np.asarray(g.num_nodes, np.int32),
        "edge_key_rep": np.asarray(g.edge_key),   # cached, built once in from_edges
    }
    if ell:
        from ..graph.csr import to_ell
        e = to_ell(g)
        cols = np.asarray(e.cols)
        cols_pad = np.full((n_pad, e.max_deg), n_pad, np.int32)
        cols_pad[: g.num_nodes] = np.where(cols == g.num_nodes, n_pad, cols)
        gd["ell_cols"] = cols_pad.reshape(p, block, e.max_deg)
    specs = partition_specs(gd, mesh)
    return {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
            for k, v in gd.items()}


def partition_specs(gd: dict, mesh):
    """PartitionSpec per gd key: stacked arrays shard on 'data', *_rep replicate."""
    specs = {}
    for k, v in gd.items():
        if k.endswith("_rep"):
            specs[k] = P()
        else:
            specs[k] = P(AXIS, *([None] * (v.ndim - 1)))
    return specs


# --------------------------------------------------------------------------
# Collective helpers (used by generated code)
# --------------------------------------------------------------------------

def gather(x):
    """Property exchange: every device receives the full array (BSP step)."""
    return jax.lax.all_gather(x, AXIS, tiled=True)


def gather_rows(x):
    """Batched property exchange: [S, B] lane blocks -> [S, N_pad] full rows
    (all-gather along the vertex axis; lanes ride along)."""
    return jax.lax.all_gather(x, AXIS, tiled=True, axis=1)


def compact_cap(block: int, frac: float) -> int:
    """Static per-shard compact-buffer capacity for a [block]-sized shard."""
    return max(min(int(block * frac), block), 1)


def exchange(full_prev, blk, own_ids, gather_frac: float = 0.25, *,
             skip_empty: bool = True, within=None, _dense=None):
    """Frontier-compressed BSP property exchange.

    `full_prev` is the [N_pad] view every shard agreed on last superstep;
    `blk` is this shard's current [B] block. Entries that differ are the
    communication frontier. Three regimes, chosen per superstep on device
    (the predicate is a collective scalar, so every shard branches the same
    way — the Beamer direction switch, applied to communication volume):

      * empty   — nothing changed anywhere: skip the collective entirely
                  (only when `skip_empty`, the "auto" policy);
      * compact — every shard's change count fits the fixed-size buffer
                  (`cap = compact_cap(B, gather_frac)`): all-gather only
                  (id, value) pairs — stacked into ONE [cap, 2] int32
                  buffer so the whole exchange is a single collective —
                  and scatter them into `full_prev`, moving 2*cap*P
                  elements instead of N_pad — the paper's §4.2 send-buffer
                  aggregation, volume edition;
      * dense   — overflow fallback: the classic full all-gather.

    `within` (optional bool [B]) restricts the exchange to a slice of the
    changed entries — the delta-stepping priority slice: only changes whose
    value sits in the current bucket window ship now. Out-of-window changes
    stay local; the caller must guarantee (and delta-stepping does, because
    values only decrease) that they still differ from `full_prev` when
    their bucket arrives, so they ship then. Stale out-of-window entries in
    the returned view are the caller's contract to mask.

    Returns `(full, gathered_elems)` where `gathered_elems` is the number
    of elements this superstep actually moved (int32, on device). Padded
    slots (own_ids >= num true nodes) are exchanged like any other only if
    they change, which initialized-but-never-written padding never does —
    so poison seeded into padding stays untouched (tested)."""
    n_pad = full_prev.shape[0]
    cap = compact_cap(blk.shape[0], gather_frac)
    p = jax.lax.axis_size(AXIS)
    chg = blk != full_prev[own_ids]
    if within is not None:
        chg = chg & within
    cnt = jnp.sum(chg.astype(jnp.int32))

    def skip(_):
        return full_prev, jnp.int32(0)

    def dense(_):
        # `_dense` overrides the fallback gather when the flat layout is a
        # view of something an all-gather cannot reproduce by concatenation
        # (the [S, B] lane blocks of `exchange_rows`). Under `within` the
        # dense gather publishes out-of-window entries EARLY — harmless:
        # they are fresh (not stale) values, and the slicing contract only
        # forbids serving stale in-window entries.
        return (gather(blk) if _dense is None else _dense()), jnp.int32(n_pad)

    def compact(_):
        order = jnp.argsort(~chg)            # stable: changed slots first
        sel = order[:cap]
        lane_ok = jnp.arange(cap) < cnt
        # out-of-range ids mark the padding lanes; scatter drops them
        ids = jnp.where(lane_ok, own_ids[sel], n_pad)
        vals = blk[sel]
        # one collective for the whole exchange: the (id, value) pairs ride
        # a single [cap, 2] int32 buffer (bool widens, float32 bitcasts —
        # both lossless round trips), halving collective launches without
        # changing the 2*cap*P element volume
        if vals.dtype == jnp.bool_:
            lane = vals.astype(jnp.int32)
        elif vals.dtype == jnp.int32:
            lane = vals
        else:
            lane = jax.lax.bitcast_convert_type(vals, jnp.int32)
        pairs = jax.lax.all_gather(
            jnp.stack([ids, lane], axis=1), AXIS, tiled=True)
        ids_all, vals_all = pairs[:, 0], pairs[:, 1]
        if vals.dtype == jnp.bool_:
            vals_all = vals_all.astype(jnp.bool_)
        elif vals.dtype != jnp.int32:
            vals_all = jax.lax.bitcast_convert_type(vals_all, vals.dtype)
        return full_prev.at[ids_all].set(vals_all), jnp.int32(2 * cap * p)

    if 2 * cap * p >= n_pad:   # compact cannot beat dense at this capacity
        if not skip_empty:
            return dense(None)
        total = psum(cnt)
        return jax.lax.cond(total == 0, skip, dense, 0)

    worst = pmax(cnt)
    fits = worst <= cap
    if not skip_empty:
        return jax.lax.cond(fits, compact, dense, 0)
    total = psum(cnt)
    return jax.lax.cond(
        total == 0, skip,
        lambda _: jax.lax.cond(fits, compact, dense, 0), 0)


def exchange_rows(full_prev, blk, own_ids, gather_frac: float = 0.25, *,
                  skip_empty: bool = True):
    """Batched-lane `exchange`: full_prev [S, N_pad], blk [S, B]. Lanes are
    flattened into one composite id space (lane * N_pad + vertex), so the
    compact buffer is shared across lanes — a lane whose frontier emptied
    donates its capacity to the others."""
    s, n_pad = full_prev.shape
    own2d = (jnp.arange(s, dtype=jnp.int32)[:, None] * n_pad
             + own_ids[None, :]).reshape(-1)
    full, elems = exchange(full_prev.reshape(-1), blk.reshape(-1), own2d,
                           gather_frac, skip_empty=skip_empty,
                           _dense=lambda: gather_rows(blk).reshape(-1))
    return full.reshape(s, n_pad), elems


def pmin(x):
    return jax.lax.pmin(x, AXIS)


def pmax(x):
    return jax.lax.pmax(x, AXIS)


def psum(x):
    return jax.lax.psum(x, AXIS)


def por(x):  # global OR of a local bool scalar
    return jax.lax.psum(x.astype(jnp.int32), AXIS) > 0


def any_global(x):  # global OR over a local bool array
    return por(jnp.any(x))


def min_global(x):  # global min over a local array (delta bucket advance)
    return pmin(jnp.min(x))


def combine_scatter_min(n_pad: int, idx, cand, dtype):
    """Paper §4.2 'communication aggregation': local scatter-min into a
    full-size buffer, then a single min-combine across devices."""
    buf = jnp.full((n_pad,), rt.inf_for(dtype), dtype)
    return pmin(buf.at[idx].min(cand))


def combine_scatter_add(n_pad: int, idx, vals, dtype):
    buf = jnp.zeros((n_pad,), dtype)
    return psum(buf.at[idx].add(vals))


def combine_scatter_max(n_pad: int, idx, cand, dtype):
    buf = jnp.full((n_pad,), -rt.inf_for(dtype) if jnp.dtype(dtype).kind != "b" else False, dtype)
    return pmax(buf.at[idx].max(cand))


def combine_scatter_add_rows(n_pad: int, idx, vals, dtype):
    """Batched-lane combine: vals [S, E] scattered by idx [E] into a
    [S, n_pad] buffer, psum'd across shards (one combine for all lanes)."""
    buf = jnp.zeros((vals.shape[0], n_pad), dtype)
    return psum(buf.at[:, idx].add(vals))


def dist_should_push(frontier_full, threshold_frac: float):
    """Replicated-frontier occupancy test: True when the frontier is sparse
    enough that a push superstep (scatter + global combine) beats the pull
    form (local segment reduction over the gathered arrays). The input is
    a full [N_pad] (or [S, N_pad]) mask every shard holds identically, so
    the predicate is shard-uniform by construction."""
    cap = max(int(frontier_full.size * threshold_frac), 1)
    return jnp.sum(frontier_full.astype(jnp.int32)) <= jnp.int32(cap)


# --------------------------------------------------------------------------
# Distributed BFS (iterateInBFS construct)
# --------------------------------------------------------------------------

def bfs_levels_1d(esrc, edst, evalid, isrc, idst_local, ivalid, own_ids,
                  root, n_pad: int, *, frontier: str = "dense",
                  gather_frac: float = 0.25, direction: str = "auto",
                  threshold_frac: float = 1.0 / 16.0):
    """Level-synchronous distributed BFS over the 1-D partition.

    `frontier` is the Schedule's `dist_frontier` policy for the per-level
    exchange of the level array (dense gather vs changed-entry compact
    buffers); `direction` picks the expansion:

      push — scatter reached-flags over out-edges of frontier vertices and
             combine globally (a psum over [N_pad], the paper's scheme);
      pull — each shard segment-reduces over its *in*-edge partition from
             the replicated level array: no combine collective at all;
      auto — per-level Beamer switch on frontier occupancy against
             `threshold_frac` (shard-uniform: the frontier is replicated).

    Both directions mark exactly the unseen out-neighborhood of the
    frontier, so the choice never changes results. Returns
    (level_blk int32[B], depth, gathered_elems) — the element counter is
    f32 (exact to 2^24; int64 is unavailable under default jax config and
    int32 would wrap on deep large-N runs)."""
    B = own_ids.shape[0]
    level0 = jnp.where(own_ids == root, 0, -1).astype(jnp.int32)
    full0 = gather(level0)

    def cond(state):
        return state[3]

    def body(state):
        level_blk, level_full, cur, _, elems = state

        def push(_):
            src_on = (level_full[esrc] == cur) & evalid
            unseen = level_full[edst] < 0
            reach = combine_scatter_add(
                n_pad, edst, (src_on & unseen).astype(jnp.int32), jnp.int32)
            return reach[own_ids] > 0

        def pull(_):
            on = (level_full[isrc] == cur) & ivalid
            return rt.segment_max(on.astype(jnp.int32), idst_local, B,
                                  sorted_ids=False) > 0

        if direction == "push":
            reach_blk = push(0)
        elif direction == "pull":
            reach_blk = pull(0)
        else:
            reach_blk = jax.lax.cond(
                dist_should_push(level_full == cur, threshold_frac),
                push, pull, 0)
        newly = reach_blk & (level_blk < 0)
        level_blk = jnp.where(newly, cur + 1, level_blk)
        if frontier == "dense":
            level_full = gather(level_blk)
            elems = elems + jnp.int32(n_pad)
        else:
            level_full, step = exchange(level_full, level_blk, own_ids,
                                        gather_frac,
                                        skip_empty=(frontier == "auto"))
            elems = elems + step
        return level_blk, level_full, cur + 1, any_global(newly), elems

    level, _, depth, _, elems = jax.lax.while_loop(
        cond, body,
        (level0, full0, jnp.int32(0), jnp.bool_(True), jnp.float32(n_pad)))
    return level, depth, elems


def bfs_levels_1d_batch(esrc, edst, evalid, isrc, idst_local, ivalid,
                        own_ids, roots, n_pad: int, *,
                        frontier: str = "dense", gather_frac: float = 0.25,
                        direction: str = "auto",
                        threshold_frac: float = 1.0 / 16.0):
    """Batched `bfs_levels_1d`: one BSP loop serves all S roots. State is
    [S, B] per shard / [S, N_pad] replicated; the per-level exchange moves
    all lanes' frontiers through one shared compact buffer. `direction` is
    chosen once per level for the whole batch (the occupancy test sums over
    lanes). Returns (level_blk int32[S, B], depth, gathered_elems); depth
    is the deepest lane's level count — shallower lanes simply see empty
    frontiers at the tail levels, exactly like the local batch engine."""
    B = own_ids.shape[0]
    level0 = jnp.where(own_ids[None, :] == roots[:, None], 0, -1).astype(jnp.int32)
    full0 = gather_rows(level0)

    def cond(state):
        return state[3]

    def body(state):
        level_blk, level_full, cur, _, elems = state

        def push(_):
            src_on = (level_full[:, esrc] == cur) & evalid
            unseen = level_full[:, edst] < 0
            reach = combine_scatter_add_rows(
                n_pad, edst, (src_on & unseen).astype(jnp.int32), jnp.int32)
            return reach[:, own_ids] > 0

        def pull(_):
            on = (level_full[:, isrc] == cur) & ivalid
            return rt.segment_max_batch(on.astype(jnp.int32), idst_local, B,
                                        sorted_ids=False) > 0

        if direction == "push":
            reach_blk = push(0)
        elif direction == "pull":
            reach_blk = pull(0)
        else:
            reach_blk = jax.lax.cond(
                dist_should_push(level_full == cur, threshold_frac),
                push, pull, 0)
        newly = reach_blk & (level_blk < 0)
        level_blk = jnp.where(newly, cur + 1, level_blk)
        if frontier == "dense":
            level_full = gather_rows(level_blk)
            elems = elems + jnp.int32(level_full.size)
        else:
            level_full, step = exchange_rows(level_full, level_blk, own_ids,
                                             gather_frac,
                                             skip_empty=(frontier == "auto"))
            elems = elems + step
        return level_blk, level_full, cur + 1, any_global(newly), elems

    level, _, depth, _, elems = jax.lax.while_loop(
        cond, body,
        (level0, full0, jnp.int32(0), jnp.bool_(True),
         jnp.float32(full0.size)))
    return level, depth, elems


# --------------------------------------------------------------------------
# Distributed triangle counting (wedge pattern over own rows)
# --------------------------------------------------------------------------

def wedge_count_1d(ell_cols, own_ids, edge_key, n_true, chunk: int = 256):
    """Fig. 20 wedge count for the owned vertex block; caller psums."""
    b, d = ell_cols.shape
    chunk = min(chunk, b)
    num_chunks = -(-b // chunk)

    def chunk_count(c, acc):
        ridx = c * chunk + jnp.arange(chunk)
        row_ok = ridx < b
        ridx = jnp.clip(ridx, 0, b - 1)
        rows = ell_cols[ridx]
        vs = own_ids[ridx]
        valid = rows < n_true            # padding slots point past the graph
        u = rows[:, :, None]
        w = rows[:, None, :]
        vv = vs[:, None, None]
        mask = (valid[:, :, None] & valid[:, None, :] & (u < vv) & (w > vv)
                & (vv < n_true) & row_ok[:, None, None])
        q = u.astype(jnp.int32) * n_true + w.astype(jnp.int32)
        pos = jnp.clip(jnp.searchsorted(edge_key, q.ravel()), 0, edge_key.shape[0] - 1)
        hit = (edge_key[pos] == q.ravel()).reshape(q.shape)
        return acc + jnp.sum(jnp.where(mask, hit, False).astype(jnp.int32))

    local = jax.lax.fori_loop(0, num_chunks, chunk_count, jnp.int32(0))
    return psum(local)
