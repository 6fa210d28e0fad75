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

from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..graph.csr import INF_I32, CSRGraph, _workers
from ..graph.partition import blocks_1d
from ..trace import span
from . import runtime as rt

AXIS = "data"


# --------------------------------------------------------------------------
# Graph preparation (host side)
# --------------------------------------------------------------------------

def view_flags(meta) -> dict:
    """The optional views a distributed program's generated body reads
    (`dist_meta`), as keyword arguments of `prepare_graph_1d`."""
    meta = meta or {}
    return {"ell": bool(meta.get("needs_ell")),
            "edge_key": bool(meta.get("needs_edge_key"))}


def _place_rows(mesh, rows: dict) -> dict:
    """{key: (shape, row)} -> {key: [P, ...] array sharded over the mesh's
    'data' axis whose row d is `row(d)`}. Each row is made on the host and
    put on its own devices only, so no whole array is ever on one device.
    The rows are made and sent side by side on a pool of threads, each
    thread holding its row until the device has it."""
    def put(row, d, device):
        return jax.device_put(row(d)[None], device).block_until_ready()

    with ThreadPoolExecutor(_workers()) as pool:
        pending = {}
        for key, (shape, row) in rows.items():
            sharding = NamedSharding(mesh, P(AXIS, *([None] * (len(shape) - 1))))
            where = sharding.addressable_devices_indices_map(shape)
            pending[key] = (shape, sharding, [
                pool.submit(put, row, index[0].start or 0, device)
                for device, index in where.items()])
        return {key: jax.make_array_from_single_device_arrays(
                    shape, sharding, [f.result() for f in futures])
                for key, (shape, sharding, futures) in pending.items()}


def prepare_graph_1d(g: CSRGraph, mesh, *, ell: bool = False,
                     edge_key: bool = False) -> dict:
    """Partitioned arrays for the 1-D backend, placed on `mesh` once.

    Keys with leading [P] shard over the mesh 'data' axis; `*_rep` keys are
    replicated static graph structure (degree tables; with `edge_key` the
    sorted edge key for is_an_edge). Device d's out-edges are one slice of
    the host CSR, and its in-edges one slice of the reverse CSR; each is
    padded and put on device d's shard alone (span `graph.shard`), so a
    jitted runner never reshards the graph and no device holds another's
    edges."""
    p = mesh.shape[AXIS]
    n = g.num_nodes
    if edge_key and n * n >= 2**31:
        raise ValueError(
            f"the distributed edge key is int32 src*N + dst, which needs "
            f"N*N < 2**31 (N <= 46,340); this graph has N = {n} "
            "(ROADMAP.md, Design 2: a row search would lift the limit)")
    with span("graph.shard"):
        out = blocks_1d(g.indptr, n, p)                 # out-edges by src block
        inn = blocks_1d(g.rev_indptr, n, p)             # in-edges by dst block
        block = out.block
        n_pad = block * p
        esrc, edst, ew = (np.asarray(a) for a in (g.edge_src, g.indices, g.weights))
        # in-edges: `idst` is the OWNED destination, `isrc` the in-neighbor
        idst, isrc, iw = (np.asarray(a) for a in
                          (g.rev_edge_dst, g.rev_indices, g.rev_weights))

        def local(b, ids, d):
            # local slot of the owned endpoint; padding edges clipped to 0
            # and neutralized by the valid mask
            return np.clip(b.row(ids, d, 0) - d * block, 0, block - 1)
        rows = {
            "esrc": (out, lambda d: out.row(esrc, d, 0)),
            "edst": (out, lambda d: out.row(edst, d, 0)),
            "ew": (out, lambda d: out.row(ew, d, INF_I32)),
            "evalid": (out, out.valid),
            "esrc_local": (out, lambda d: local(out, esrc, d)),
            "idst": (inn, lambda d: inn.row(idst, d, 0)),
            "isrc": (inn, lambda d: inn.row(isrc, d, 0)),
            "iw": (inn, lambda d: inn.row(iw, d, INF_I32)),
            "ivalid": (inn, inn.valid),
            "idst_local": (inn, lambda d: local(inn, idst, d)),
        }
        rows = {k: ((p, b.emax), row) for k, (b, row) in rows.items()}
        rows["own_ids"] = ((p, block), lambda d: np.arange(
            d * block, (d + 1) * block, dtype=np.int32))
        if ell:
            from ..graph.csr import to_ell
            e = to_ell(g)
            cols = np.asarray(e.cols)
            cols_pad = np.full((n_pad, e.max_deg), n_pad, np.int32)
            cols_pad[:n] = np.where(cols == n, n_pad, cols)
            cols_pad = cols_pad.reshape(p, block, e.max_deg)
            rows["ell_cols"] = (cols_pad.shape, lambda d: cols_pad[d])
        gd = _place_rows(mesh, rows)

        deg_out = np.zeros(n_pad, np.int32)
        deg_out[:n] = np.asarray(g.out_degree)
        deg_in = np.zeros(n_pad, np.int32)
        deg_in[:n] = np.asarray(g.in_degree)
        rep = {"out_degree_rep": deg_out, "in_degree_rep": deg_in,
               "n_true_rep": np.asarray(n, np.int32)}
        if edge_key:
            rep["edge_key_rep"] = (esrc.astype(np.int64) * n + edst).astype(np.int32)
        replicated = NamedSharding(mesh, P())
        gd.update({k: jax.device_put(v, replicated) for k, v in rep.items()})
    return gd


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
