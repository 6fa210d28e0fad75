"""Runtime library for StarPlat-generated JAX code.

These are the "batteries included" utility functions of the paper (§2),
implemented TPU-natively: every primitive is shape-static, mask-based, and
free of data-dependent control flow, so one compiled program serves a graph
regardless of frontier contents.

Race handling (the paper's atomics) is structural here: `scatter_min` uses
XLA's associative scatter-min combinator (deterministic, no CAS needed) and
pull-reductions use sorted segment ops.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..graph.csr import CSRGraph, ENGINE, INF_I32

INF = jnp.int32(INF_I32)
# int32's maximum: what the generated integer relax reads at vertices off
# its frontier before its one edge gather; never a distance
SENT = jnp.int32(jnp.iinfo(jnp.int32).max)


# --- scatter / segment combine (the Min/Max construct, reductions) -----------

def scatter_min(current: jax.Array, idx: jax.Array, cand: jax.Array) -> jax.Array:
    """min-combine `cand` into `current` at positions `idx` (push relax)."""
    return current.at[idx].min(cand)


def scatter_max(current, idx, cand):
    return current.at[idx].max(cand)


def scatter_add(current, idx, vals):
    return current.at[idx].add(vals)


def scatter_or(current, idx, vals):
    return current.at[idx].max(vals)  # bool max == or


def segment_sum(vals, seg_ids, num_segments, sorted_ids=True):
    return jax.ops.segment_sum(vals, seg_ids, num_segments=num_segments,
                               indices_are_sorted=sorted_ids)


def segment_min(vals, seg_ids, num_segments, sorted_ids=True):
    return jax.ops.segment_min(vals, seg_ids, num_segments=num_segments,
                               indices_are_sorted=sorted_ids)


def segment_max(vals, seg_ids, num_segments, sorted_ids=True):
    return jax.ops.segment_max(vals, seg_ids, num_segments=num_segments,
                               indices_are_sorted=sorted_ids)


# --- batched (multi-source) scatter / segment combines -----------------------
#
# The batched engine carries per-source properties as [B, N] matrices; the
# per-edge values they induce are [B, E]. Segment ops segment over the
# LEADING axis, so the batched variants run on the [E, B] transpose — one
# fused segmented reduction with B lanes, not B reductions.

def _seg_batch(op, vals, seg_ids, num_segments, sorted_ids):
    return op(jnp.swapaxes(vals, 0, 1), seg_ids, num_segments=num_segments,
              indices_are_sorted=sorted_ids).swapaxes(0, 1)


def segment_sum_batch(vals, seg_ids, num_segments, sorted_ids=True):
    """vals [B, E], seg_ids [E] → [B, num_segments]."""
    return _seg_batch(jax.ops.segment_sum, vals, seg_ids, num_segments, sorted_ids)


def segment_min_batch(vals, seg_ids, num_segments, sorted_ids=True):
    return _seg_batch(jax.ops.segment_min, vals, seg_ids, num_segments, sorted_ids)


def segment_max_batch(vals, seg_ids, num_segments, sorted_ids=True):
    return _seg_batch(jax.ops.segment_max, vals, seg_ids, num_segments, sorted_ids)


def scatter_min_rows(current, idx, cand):
    """Row-wise scatter-min: current [B, N], idx [E], cand [B, E]."""
    return current.at[:, idx].min(cand)


def scatter_add_rows(current, idx, vals):
    return current.at[:, idx].add(vals)


def scatter_or_rows(current, idx, vals):
    return current.at[:, idx].max(vals)


# --- graph queries ------------------------------------------------------------

def _edge_key_fits_i32(n: int) -> bool:
    return n * n < 2**31


def _is_an_edge_keyed(g: CSRGraph, u, w):
    """Fast path: binary search over the cached sorted (src·N + dst) int32
    key — only valid while N² fits int32."""
    key = g.edge_key
    q = u.astype(jnp.int32) * g.num_nodes + w.astype(jnp.int32)
    pos = jnp.searchsorted(key, q)
    pos = jnp.clip(pos, 0, key.shape[0] - 1)
    return key[pos] == q


def _is_an_edge_rowsearch(g: CSRGraph, u, w):
    """Large-graph path (N² ≥ 2³¹): per-query binary search of `w` inside
    CSR row `u` — a fixed-iteration lower_bound over indices[indptr[u] :
    indptr[u+1]], so no composite key (and no int64) is ever formed."""
    e = g.num_edges
    n = g.num_nodes
    uc = jnp.clip(u, 0, n - 1)
    lo = g.indptr[uc].astype(jnp.int32)
    row_end = g.indptr[uc + 1].astype(jnp.int32)
    lo = jnp.broadcast_to(lo, jnp.broadcast_shapes(lo.shape, jnp.shape(w)))
    hi = jnp.broadcast_to(row_end, lo.shape)
    steps = max(int(g.max_out_degree), 1).bit_length() + 1

    def body(_, state):
        lo, hi = state
        active = lo < hi
        mid = (lo + hi) >> 1
        v = g.indices[jnp.clip(mid, 0, e - 1)]
        go_right = v < w
        return (jnp.where(active & go_right, mid + 1, lo),
                jnp.where(active & ~go_right, mid, hi))

    lo, _ = jax.lax.fori_loop(0, steps, body, (lo, hi))
    return ((lo < row_end) & (g.indices[jnp.clip(lo, 0, e - 1)] == w)
            & (u >= 0) & (u < n))   # match the keyed path on out-of-range u


def is_an_edge(g: CSRGraph, u: jax.Array, w: jax.Array) -> jax.Array:
    """Membership test — the paper's `is_an_edge` with sorted-CSR binary
    search (§5.1 TC). Small graphs search the cached composite int32 key;
    graphs whose N² would overflow int32 fall back to a row-range binary
    search (no key materialized). Broadcasts over u/w."""
    if g.num_edges == 0:
        return jnp.zeros(jnp.broadcast_shapes(jnp.shape(u), jnp.shape(w)),
                         jnp.bool_)
    u = jnp.asarray(u)
    w = jnp.asarray(w)
    if _edge_key_fits_i32(g.num_nodes):
        return _is_an_edge_keyed(g, u, w)
    return _is_an_edge_rowsearch(g, u, w)


# --- frontier engine (direction-optimizing traversal) --------------------------
#
# The paper gets performance per backend by restructuring the same IR; the
# TPU restructuring here is Beamer-style direction optimization with
# shape-static state: the frontier is a dense bool[N] threaded through the
# while_loop carry (the fixedPoint conv property IS the frontier), and each
# step picks push (scatter from frontier sources) or pull (gather/segment
# over in-edges) via an on-device occupancy test — both branches compute the
# identical relaxation, so lax.cond is exact, not approximate.

def frontier_size(frontier: jax.Array) -> jax.Array:
    """On-device occupancy count of a dense bool frontier."""
    return jnp.sum(frontier.astype(jnp.int32))


def frontier_should_push(frontier: jax.Array, n: int,
                         threshold_frac: float | None = None,
                         direction: str = "auto") -> jax.Array:
    """True when the frontier is sparse enough that push (scatter from the
    few active sources) beats a pull sweep. The knob is
    `Schedule.push_threshold_frac` (fraction of N) — generated code passes
    it explicitly; `None` falls back to the deprecated `ENGINE` shim. A
    pinned `direction` short-circuits the occupancy test."""
    if direction == "push":
        return jnp.bool_(True)
    if direction == "pull":
        return jnp.bool_(False)
    frac = ENGINE.push_threshold_frac if threshold_frac is None else threshold_frac
    return frontier_size(frontier) <= jnp.int32(max(int(n * frac), 1))


def _frontier_cand(d, frontier, idx, w=None):
    """Relax candidates d[u] (+ w) along the edges whose sources `idx`
    names, INF where u is off `frontier` (None: every vertex is on it).
    d and frontier may be [N] or [B, N] (gathered along the last axis).
    Integer d takes one gather: the frontier is folded into d first, off-
    frontier vertices read SENT (the dtype's maximum), and a gathered SENT
    becomes INF (unweighted, SENT itself: it loses every min as INF does).
    Floating d gathers the mask and the value separately."""
    if frontier is None:
        src = d[..., idx]
        return src if w is None else src + w
    if jnp.issubdtype(d.dtype, jnp.integer):
        sent = jnp.iinfo(d.dtype).max
        src = jnp.where(frontier, d, sent)[..., idx]
        return src if w is None else jnp.where(src == sent, INF, src + w)
    cand = d[..., idx] if w is None else d[..., idx] + w
    return jnp.where(frontier[..., idx], cand, INF)


def relax_minplus_hybrid(g: CSRGraph, dist: jax.Array,
                         frontier: jax.Array | None = None,
                         threshold_frac: float | None = None,
                         direction: str = "auto",
                         weighted: bool = True, counts: bool = False):
    """One SSSP/min-plus relaxation restricted to `frontier` sources, with
    push/pull direction chosen on-device.

      push: scatter-min dist[u]+w over out-edges of frontier vertices
      pull: per-vertex min over in-edges, sources masked to the frontier

    Both compute dist'[v] = min(dist[v], min_{(u,v)∈E, frontier[u]} dist[u]+w)
    exactly, so the switch never changes results. `frontier=None` is a dense
    sweep (every vertex contributes). `weighted=False` drops the `+ w` term
    (the candidate is just dist[u]) — the unweighted Min relax of connected
    components, which takes the same push/pull machinery. `counts=True`
    also returns whether the step pushed (a bool scalar) and the edges it
    swept (E, float32), for the superstep counters of generated code.

    NOTE: this push/pull relaxation pair exists in four places — here, the
    batched form below (`relax_minplus_hybrid_batch`), the kernel-backed
    ops (kernels/ell_spmv/ops.py `_relax_push`/`_relax_sliced_pull`), and
    inline in the local backend's generated source
    (local_jax.emit_relax_hybrid, kept inline so the lowering stays
    inspectable). All four gather one vertex array per edge sweep: integer
    distances fold the frontier into the source value first (off-frontier
    sources read SENT, the dtype's maximum, and a gathered SENT becomes an
    INF candidate), as `_frontier_cand` does; the sliced pull masks its
    gather operand the same way, with INF. A semantic change to any copy
    must be applied to all."""
    n = g.num_nodes

    def push(d):
        return scatter_min(d, g.indices, _frontier_cand(
            d, frontier, g.edge_src, g.weights if weighted else None))

    def pull(d):
        cand = _frontier_cand(d, frontier, g.rev_indices,
                              g.rev_weights if weighted else None)
        return jnp.minimum(d, segment_min(cand, g.rev_edge_dst, n))

    if frontier is None or direction == "pull":
        out, pushed = pull(dist), jnp.bool_(False)
    elif direction == "push":
        out, pushed = push(dist), jnp.bool_(True)
    else:
        pushed = frontier_should_push(frontier, n, threshold_frac)
        out = jax.lax.cond(pushed, push, pull, dist)
    return (out, pushed, jnp.float32(g.num_edges)) if counts else out


# --- delta-stepping (priority-bucketed) relaxation -----------------------------
#
# Schedule.priority == "delta" restricts each fixedPoint sweep to the
# vertices whose tentative value falls below the current bucket boundary
# (k + 1) * delta_bucket — Meyer/Sanders delta-stepping expressed over the
# same frontier machinery. Min relaxation is monotone, so any frontier
# restriction that eventually processes every modified vertex reaches the
# identical fixed point; the payoff is per-sweep WORK: a settled bucket's
# frontier is tiny, and the compact path below relaxes only its out-rows
# (O(cap * max_deg) via a padded ELL gather) instead of sweeping all E edges.

def relax_minplus_delta(g: CSRGraph, dist: jax.Array, frontier: jax.Array,
                        ell=None, cap: int | None = None,
                        threshold_frac: float | None = None,
                        direction: str = "auto",
                        weighted: bool = True, counts: bool = False):
    """One bucketed min relaxation over `frontier` sources (the caller has
    already restricted the frontier to the current delta bucket).

    When a padded forward ELL view and a static `cap` are supplied and the
    frontier fits, the compact path runs: frontier ids are compacted into a
    [cap] buffer by an O(N) cumsum (no sort), their padded out-rows
    gathered, and the candidates scatter-min'd. Pad cells (col == n) and
    unused slots are masked to INF and scattered out of bounds, which XLA
    drops. Overflowing frontiers — and `ell=None` (hub-heavy graphs where
    max_deg makes the ELL view uneconomical) — fall back to the dense
    hybrid sweep, which computes the same relaxation. `counts=True` also
    returns (pushed, swept) as `relax_minplus_hybrid` does; the compact
    path is a push that sweeps its cap x ELL-width slots."""
    if ell is None or cap is None or cap <= 0:
        return relax_minplus_hybrid(g, dist, frontier, threshold_frac,
                                    direction, weighted, counts)
    n = g.num_nodes
    cap = int(min(cap, n))

    def compact(d):
        pos = jnp.cumsum(frontier.astype(jnp.int32)) - 1
        slot = jnp.where(frontier & (pos < cap), pos, cap)   # cap = trash slot
        ids = jnp.full((cap + 1,), n, jnp.int32).at[slot].set(
            jnp.arange(n, dtype=jnp.int32))[:cap]
        row_ok = ids < n
        idc = jnp.where(row_ok, ids, 0)
        cols = ell.cols[idc]                                  # [cap, D]
        valid = row_ok[:, None] & (cols < n)
        src = d[idc][:, None]
        cand = src + ell.wts[idc] if weighted \
            else jnp.broadcast_to(src, cols.shape)
        cand = jnp.where(valid, cand, INF)
        tgt = jnp.where(valid, cols, n)                       # n → dropped
        out = d.at[tgt.ravel()].min(cand.ravel())
        return (out, jnp.bool_(True), jnp.float32(cols.size)) if counts else out

    def dense(d):
        return relax_minplus_hybrid(g, d, frontier, threshold_frac,
                                    direction, weighted, counts)

    return jax.lax.cond(frontier_size(frontier) <= jnp.int32(cap),
                        compact, dense, dist)


# --- BFS (iterateInBFS construct) ----------------------------------------------

def bfs_levels(g: CSRGraph, root, max_levels: int | None = None, *,
               threshold_frac: float | None = None,
               direction: str = "auto"):
    """Level-synchronous BFS with direction-optimizing expansion. Dense
    frontier: level[v] = -1 until visited; frontier = (level == cur).

      push (small frontier): scatter-or over out-edges of frontier vertices
      pull (large frontier): segment-or over in-edges from frontier sources

    Both mark exactly the unseen out-neighborhood of the frontier, so the
    switch is result-invariant. Returns (level[int32 N], num_levels)."""
    n = g.num_nodes
    level0 = jnp.full((n,), -1, jnp.int32).at[root].set(0)

    def cond(state):
        _, cur, changed = state
        return changed

    def body(state):
        level, cur, _ = state
        frontier = level == cur

        def push(fr):
            hit = scatter_or(jnp.zeros((n,), jnp.bool_), g.indices,
                             fr[g.edge_src])
            return hit

        def pull(fr):
            return segment_max(fr[g.rev_indices].astype(jnp.int32),
                               g.rev_edge_dst, n) > 0

        if direction == "push":
            reach = push(frontier)
        elif direction == "pull":
            reach = pull(frontier)
        else:
            reach = jax.lax.cond(
                frontier_should_push(frontier, n, threshold_frac),
                push, pull, frontier)
        newly = reach & (level < 0)
        level = jnp.where(newly, cur + 1, level)
        return level, cur + 1, jnp.any(newly)

    level, depth, _ = jax.lax.while_loop(cond, body, (level0, jnp.int32(0), jnp.bool_(True)))
    return level, depth


# --- batched multi-source traversal engine -------------------------------------
#
# S independent traversals over the same graph run the same kernels S times;
# batching B sources turns every per-bucket SpMV into an SpMM with B lanes
# (Brandes-style multi-source BC, multi-query SSSP). State is [B, N]: row b
# is source b's property vector. The direction choice generalizes per batch
# ROW — each source's frontier empties on its own schedule — with whole-batch
# fast paths (all-push / all-pull) so the homogeneous case, by far the most
# common, still evaluates only one direction.

def frontier_rows_should_push(frontier: jax.Array, n: int,
                              threshold_frac: float | None = None) -> jax.Array:
    """Per-row push/pull choice for a [B, N] batched frontier → bool[B].
    `None` falls back to the deprecated `ENGINE` shim; generated code
    always passes the compiled `Schedule`'s threshold explicitly."""
    frac = ENGINE.push_threshold_frac if threshold_frac is None else threshold_frac
    occ = jnp.sum(frontier.astype(jnp.int32), axis=1)
    return occ <= jnp.int32(max(int(n * frac), 1))


def _cond_by_rows(rows_push, push_all, pull_all, mixed, arg):
    """Dispatch on the per-row direction vector: homogeneous batches take a
    single-direction branch; mixed batches evaluate both, each masked to its
    rows (the masks make the two halves disjoint, so combining is exact)."""
    return jax.lax.cond(
        jnp.all(rows_push), push_all,
        lambda a: jax.lax.cond(jnp.any(rows_push), mixed, pull_all, a),
        arg)


def relax_minplus_hybrid_batch(g: CSRGraph, dist: jax.Array,
                               frontier: jax.Array | None = None,
                               threshold_frac: float | None = None,
                               direction: str = "auto",
                               weighted: bool = True) -> jax.Array:
    """Batched SSSP/min-plus relaxation: dist [B, N], frontier [B, N] bool.

    Row-for-row identical to `relax_minplus_hybrid` on each dist row with its
    frontier row — push rows scatter-min over out-edges, pull rows gather/
    segment-min over in-edges, and rows are routed independently. (One of
    the four push/pull copies — see the NOTE on `relax_minplus_hybrid`.)"""
    n = g.num_nodes

    def push(d, fr):
        return scatter_min_rows(d, g.indices, _frontier_cand(
            d, fr, g.edge_src, g.weights if weighted else None))

    def pull(d, fr):
        cand = _frontier_cand(d, fr, g.rev_indices,
                              g.rev_weights if weighted else None)
        return jnp.minimum(d, segment_min_batch(cand, g.rev_edge_dst, n))

    if frontier is None:
        return pull(dist, None)
    if direction == "push":
        return push(dist, frontier)
    if direction == "pull":
        return pull(dist, frontier)
    rows_push = frontier_rows_should_push(frontier, n, threshold_frac)
    return _cond_by_rows(
        rows_push,
        lambda d: push(d, frontier),
        lambda d: pull(d, frontier),
        lambda d: pull(push(d, frontier & rows_push[:, None]),
                       frontier & ~rows_push[:, None]),
        dist)


def relax_minplus_delta_batch(g: CSRGraph, dist: jax.Array,
                              frontier: jax.Array,
                              threshold_frac: float | None = None,
                              direction: str = "auto",
                              weighted: bool = True) -> jax.Array:
    """Batched bucketed min relaxation: dist [B, N], frontier [B, N] already
    restricted per row to that row's current delta bucket. Each source lane
    settles its own bucket sequence, so there is no whole-batch compact
    buffer — the restriction itself (far fewer active sources per sweep) is
    the win, and the relaxation routes through the batched hybrid."""
    return relax_minplus_hybrid_batch(g, dist, frontier, threshold_frac,
                                      direction, weighted)


def bfs_levels_batch(g: CSRGraph, roots: jax.Array,
                     threshold_frac: float | None = None,
                     direction: str = "auto"):
    """Batched level-synchronous BFS from roots[B] with per-row direction
    optimization. Returns (level int32[B, N], depth) — row b equals
    `bfs_levels(g, roots[b])[0]`; depth is the deepest row's level count, so
    shallower rows simply see empty frontiers at the tail levels."""
    n = g.num_nodes
    b = roots.shape[0]
    lanes = jnp.arange(b, dtype=jnp.int32)
    level0 = jnp.full((b, n), -1, jnp.int32).at[lanes, roots].set(0)

    def cond(state):
        _, cur, changed = state
        return changed

    def body(state):
        level, cur, _ = state
        frontier = level == cur

        def push(fr):
            return scatter_or_rows(jnp.zeros((b, n), jnp.bool_), g.indices,
                                   fr[:, g.edge_src])

        def pull(fr):
            return segment_max_batch(fr[:, g.rev_indices].astype(jnp.int32),
                                     g.rev_edge_dst, n) > 0

        if direction == "push":
            reach = push(frontier)
        elif direction == "pull":
            reach = pull(frontier)
        else:
            rows_push = frontier_rows_should_push(frontier, n, threshold_frac)
            reach = _cond_by_rows(
                rows_push, push, pull,
                lambda fr: push(fr & rows_push[:, None]) | pull(fr & ~rows_push[:, None]),
                frontier)
        newly = reach & (level < 0)
        level = jnp.where(newly, cur + 1, level)
        return level, cur + 1, jnp.any(newly)

    level, depth, _ = jax.lax.while_loop(
        cond, body, (level0, jnp.int32(0), jnp.bool_(True)))
    return level, depth


def sssp_multi(g: CSRGraph, sources: jax.Array,
               threshold_frac: float | None = None,
               direction: str = "auto",
               priority: str = "none",
               delta_bucket: int = 64) -> jax.Array:
    """Multi-query SSSP: one batched fixed point answering B source queries
    per sweep. Returns dist int32[B, N]; row b == SSSP from sources[b].

    `priority="delta"` runs each lane's fixed point as delta-stepping: a
    sweep relaxes only the lane's vertices below its current bucket
    boundary, and a lane whose bucket settled jumps straight to the bucket
    of its smallest pending value. The fixed point is unchanged (Min is
    monotone); only the per-sweep work shrinks."""
    n = g.num_nodes
    b = sources.shape[0]
    lanes = jnp.arange(b, dtype=jnp.int32)
    dist0 = jnp.full((b, n), INF, jnp.int32).at[lanes, sources].set(0)
    fr0 = jnp.zeros((b, n), jnp.bool_).at[lanes, sources].set(True)

    def cond(state):
        return jnp.any(state[1])

    if priority != "delta":
        def body(state):
            d, fr = state
            d2 = relax_minplus_hybrid_batch(g, d, fr, threshold_frac,
                                            direction)
            return d2, d2 < d

        dist, _ = jax.lax.while_loop(cond, body, (dist0, fr0))
        return dist

    delta = jnp.int32(delta_bucket)

    def body(state):
        d, mod, bk = state
        # fused bucket advance: a lane whose window emptied jumps to the
        # bucket of its smallest pending value (upper-bound-only window)
        pend_min = jnp.min(jnp.where(mod, d, INF), axis=1)
        bk = jnp.where(jnp.any(mod & (d < (bk + 1)[:, None] * delta), axis=1),
                       bk, pend_min // delta)
        fr = mod & (d < (bk + 1)[:, None] * delta)
        d2 = relax_minplus_delta_batch(g, d, fr, threshold_frac, direction)
        return d2, (d2 < d) | (mod & ~fr), bk

    dist, _, _ = jax.lax.while_loop(
        cond, body, (dist0, fr0, jnp.zeros((b,), jnp.int32)))
    return dist


def ppr_multi(g: CSRGraph, sources: jax.Array, delta: float = 0.85,
              beta: float = 1e-4, max_iter: int = 100) -> jax.Array:
    """Multi-query personalized PageRank: one batched sweep serving B
    personalization vectors. Returns float32[B, N]; row b is the PPR with
    the restart vector concentrated on sources[b] — the same per-source
    do-while ppr.sp lowers to, so lanes converge independently (per-lane L1
    diff vs `beta`) and converged lanes are frozen while the rest sweep."""
    n = g.num_nodes
    b = sources.shape[0]
    lanes = jnp.arange(b, dtype=jnp.int32)
    restart = jnp.zeros((b, n), jnp.float32).at[lanes, sources].set(1.0)
    inv_deg = 1.0 / jnp.maximum(g.out_degree, 1).astype(jnp.float32)

    def cond(state):
        _, act, _ = state
        return jnp.any(act)

    def body(state):
        rank, act, it = state
        contrib = (rank * inv_deg[None, :])[:, g.rev_indices]   # [B, E]
        pulled = segment_sum_batch(contrib, g.rev_edge_dst, n)
        nxt = (1.0 - delta) * restart + delta * pulled
        diff = jnp.sum(jnp.abs(nxt - rank), axis=1)
        rank = jnp.where(act[:, None], nxt, rank)
        act = act & (diff > beta) & (it + 1 < max_iter)
        return rank, act, it + 1

    rank, _, _ = jax.lax.while_loop(
        cond, body, (restart, jnp.ones((b,), jnp.bool_), jnp.int32(0)))
    return rank


# --- triangle counting (the paper's Fig. 20 wedge pattern) ----------------------

def wedge_count(g: CSRGraph, chunk: int = 512) -> jax.Array:
    """Vectorized node-iterator TC: for v, u in N(v) with u<v, w in N(v) with
    w>v, count (u, w) ∈ E. Wedges are enumerated on an ELL padded view in
    vertex chunks of `chunk` rows to bound memory (the OpenMP backend's
    parallel-for over v, restructured for a vector unit)."""
    n = g.num_nodes
    if g.num_edges == 0:
        return jnp.int32(0)
    max_deg = max(g.max_out_degree, 1)   # static (host-side) metadata

    def row_nbrs(vs):
        # [C, D] neighbor ids (n = padding)
        offs = g.indptr[vs][:, None] + jnp.arange(max_deg)[None, :]
        valid = jnp.arange(max_deg)[None, :] < g.out_degree[vs][:, None]
        cols = jnp.where(valid, g.indices[jnp.clip(offs, 0, g.num_edges - 1)], n)
        return cols, valid

    num_chunks = -(-n // chunk)

    def chunk_count(c, acc):
        vs = c * chunk + jnp.arange(chunk)
        vs_ok = vs < n
        vs_c = jnp.clip(vs, 0, n - 1)
        cols, valid = row_nbrs(vs_c)
        u = cols[:, :, None]                      # [C, D, 1]
        w = cols[:, None, :]                      # [C, 1, D]
        vv = vs_c[:, None, None]
        mask = (valid[:, :, None] & valid[:, None, :]
                & (u < vv) & (w > vv) & vs_ok[:, None, None])
        hit = is_an_edge(g, u, w)        # keyed or row-search, per graph size
        return acc + jnp.sum(jnp.where(mask, hit, False).astype(jnp.int32))

    return jax.lax.fori_loop(0, num_chunks, chunk_count, jnp.int32(0))


# --- property helpers ------------------------------------------------------------

def init_prop(n, dtype, value=None):
    dt = jnp.dtype(dtype)
    if value is None:
        return jnp.zeros((n,), dt)
    return jnp.full((n,), value, dt)


def warm_start(init, warm, reset=None):
    """Per-property warm start for an incremental refresh (`__refresh`
    codegen variants call this right before the iterative construct).

    `init` is the property AFTER the program's own init statements ran, so
    source writes (e.g. `dist[src] = 0`) survive for reset vertices. With
    no previous value the cold init stands; with one, `reset` marks the
    vertices whose previous value may be stale (the deletion cone) and
    falls back to the cold init there, keeping the still-exact warm values
    everywhere else."""
    if warm is None:
        return init
    warm = jnp.asarray(warm, init.dtype)
    if reset is None:
        return warm
    return jnp.where(jnp.asarray(reset), init, warm)


def init_prop_batch(b, n, dtype, value=None):
    """[B, N] per-source property block (batched set-loop chunk). `value`
    may be a scalar or an [N] vector (broadcast across the batch rows)."""
    dt = jnp.dtype(dtype)
    if value is None:
        return jnp.zeros((b, n), dt)
    return jnp.broadcast_to(jnp.asarray(value, dt), (b, n))


def inf_for(dtype):
    dt = jnp.dtype(dtype)
    if dt.kind == "i":
        return INF
    if dt.kind == "b":
        return jnp.bool_(True)
    return jnp.asarray(jnp.inf, dt)


def reduce_identity(op: str, dtype):
    dt = jnp.dtype(dtype)
    if op == "+":
        return jnp.zeros((), dt)
    if op == "*":
        return jnp.ones((), dt)
    if op == "&&":
        return jnp.bool_(True)
    if op == "||":
        return jnp.bool_(False)
    raise ValueError(op)
