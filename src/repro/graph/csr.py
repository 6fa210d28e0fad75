"""CSR graph representation — the storage format the paper standardizes on (§3.1).

The paper chose CSR because it (a) works across all backends, (b) suits
vertex-centric algorithms, and (c) splits easily for distribution. All three
hold on TPU, with one adaptation: TPU kernels want *rectangular* tiles, so we
additionally materialize a block-ELL view (padded neighbor lists) for the
Pallas backend, and we keep an explicit per-edge source array (`edge_src`)
so edge-parallel ops are a gather, not a searchsorted.
"""
from __future__ import annotations

import dataclasses
import os
import warnings
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..schedule import DEFAULT_SCHEDULE, Schedule
from ..trace import span

INF_I32 = np.int32(2**30)  # "infinity" that survives + weight without overflow

_ENGINE_DEPRECATION = (
    "mutating the module-level ENGINE is deprecated; construct an explicit "
    "repro.schedule.Schedule and pass it to compile_program(..., "
    "schedule=...) / prepare(g, schedule) instead. ENGINE is snapshotted "
    "into a Schedule at compile/prepare time, so mutating it afterwards "
    "never changes an already-compiled program."
)


@dataclasses.dataclass
class EngineConfig:
    """DEPRECATED mutable shim over the default `Schedule`.

    The engine knobs are a per-compile `repro.schedule.Schedule` now; this
    singleton only exists so pre-Schedule code keeps working. Reads are
    free; every mutation validates the would-be configuration (the same
    checks as `Schedule`), emits a `DeprecationWarning`, and only takes
    effect for *future* compiles/prepares via `snapshot()`. The shim will
    be removed once nothing in-tree mutates it (see README "Migration").
    """

    # field defaults come from DEFAULT_SCHEDULE — one source of truth, so
    # an unmutated shim always snapshots exactly the default Schedule
    num_buckets: int = DEFAULT_SCHEDULE.num_buckets
    min_width: int = DEFAULT_SCHEDULE.min_width
    growth: int = DEFAULT_SCHEDULE.growth
    push_threshold_frac: float = DEFAULT_SCHEDULE.push_threshold_frac
    batch_sources: int = DEFAULT_SCHEDULE.batch_sources

    def __post_init__(self):
        self.snapshot()           # validate the defaults once
        object.__setattr__(self, "_ready", True)

    def __setattr__(self, name, value):
        if getattr(self, "_ready", False) and not name.startswith("_"):
            knobs = {f.name: getattr(self, f.name)
                     for f in dataclasses.fields(self)}
            if name not in knobs:
                raise AttributeError(
                    f"ENGINE has no knob {name!r}; knobs: "
                    f"{', '.join(sorted(knobs))}")
            knobs[name] = value
            Schedule(**knobs)     # actionable ValueError before committing
            warnings.warn(_ENGINE_DEPRECATION, DeprecationWarning,
                          stacklevel=2)
        object.__setattr__(self, name, value)

    def snapshot(self, *, direction: str = "auto") -> Schedule:
        """Materialize the current knob values as a frozen `Schedule`."""
        return Schedule(num_buckets=self.num_buckets,
                        min_width=self.min_width, growth=self.growth,
                        push_threshold_frac=self.push_threshold_frac,
                        batch_sources=self.batch_sources,
                        direction=direction)


ENGINE = EngineConfig()


def resolve_schedule(schedule: Optional[Schedule] = None, *,
                     batch_sources: Optional[int] = None) -> Schedule:
    """The one place a default schedule is materialized.

    `schedule=None` snapshots the deprecated `ENGINE` shim (which, unless
    mutated, IS the default `Schedule`); the legacy per-compile
    `batch_sources=` override folds into the result."""
    sched = ENGINE.snapshot() if schedule is None else schedule
    if not isinstance(sched, Schedule):
        raise TypeError(
            f"schedule must be a repro.schedule.Schedule, got "
            f"{type(sched).__name__} — e.g. Schedule(batch_sources=16)")
    if batch_sources is not None:
        sched = dataclasses.replace(sched, batch_sources=int(batch_sources))
    return sched


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CSRGraph:
    """Static graph in CSR (out-edges) + CSC (in-edges) form.

    Matches the paper's Graph type: `indptr/indices` are
    `indexofNodes/edgeList`; `rev_*` is the transpose CSR the paper keeps
    for `nodesTo()` (needed by PR-pull and BC).
    """

    # --- out-CSR ---
    indptr: jax.Array      # int32[N+1]
    indices: jax.Array     # int32[E]   destination of each out-edge
    weights: jax.Array     # int32[E]   edge weights (SSSP); ones if unweighted
    edge_src: jax.Array    # int32[E]   source of each out-edge (expanded rows)
    # --- in-CSR (transpose) ---
    rev_indptr: jax.Array  # int32[N+1]
    rev_indices: jax.Array # int32[E]   source of each in-edge
    rev_weights: jax.Array # int32[E]
    rev_edge_dst: jax.Array# int32[E]   destination of each in-edge (expanded rows)
    # --- degrees ---
    out_degree: jax.Array  # int32[N]
    in_degree: jax.Array   # int32[N]
    # --- membership index ---
    # sorted (src*N + dst) key, built once so is_an_edge / wedge_count never
    # rebuild it per call; meaningful only while N*N fits int32 (the
    # consumers guard), but always present so the pytree shape is uniform.
    edge_key: jax.Array    # int32[E]
    # --- static metadata ---
    num_nodes: int = dataclasses.field(metadata=dict(static=True))
    num_edges: int = dataclasses.field(metadata=dict(static=True))
    max_out_degree: int = dataclasses.field(default=1, metadata=dict(static=True))
    max_in_degree: int = dataclasses.field(default=1, metadata=dict(static=True))
    # update generation: 0 for a freshly built graph, old.version + 1 for the
    # result of `update()`. Folded into the context fingerprint so a
    # post-update graph can never warm-reload a stale tuning record or
    # alias a pre-update memoized bind.
    version: int = dataclasses.field(default=0, metadata=dict(static=True))

    def num_nodes_(self) -> int:
        return self.num_nodes

    def update(self, adds=None, dels=None, weights=None):
        """Apply an edge write batch, returning a `repro.graph.dynamic.
        GraphDelta` whose `.graph` is the NEW graph version (this graph is
        immutable and untouched). `adds`/`dels` are (src, dst) pairs — a
        `[K, 2]` array or a pair of arrays; `weights` parallels `adds`
        (default 1; adding an existing edge replaces its weight). Deleting
        an absent edge is a no-op. Derived sliced-ELL views of this
        graph's `GraphContext` are delta-patched into the new graph's
        context rather than rebuilt."""
        from .dynamic import apply_update
        return apply_update(self, adds=adds, dels=dels, weights=weights)

    # Paper library functions -------------------------------------------------
    def count_outNbrs(self) -> jax.Array:
        return self.out_degree

    def minWt(self) -> jax.Array:
        return jnp.min(self.weights)

    def maxWt(self) -> jax.Array:
        return jnp.max(self.weights)


def _workers() -> int:
    """Host threads for the graph build: the CPUs this process may use, at
    most 16 (the build is bound by memory bandwidth well before that)."""
    if hasattr(os, "sched_getaffinity"):
        return min(len(os.sched_getaffinity(0)), 16)
    return min(os.cpu_count() or 1, 16)


# below this many edges the host build runs as one piece
_PARALLEL_MIN_EDGES = 1 << 20
# edges per task: the temporaries in flight are a few of these per thread,
# whatever the graph's size
_PIECE = 1 << 22


def _pieces(m: int, k: int) -> list:
    """`k` contiguous (start, stop) ranges covering [0, m)."""
    cut = np.linspace(0, m, k + 1).astype(np.int64)
    return list(zip(cut[:-1].tolist(), cut[1:].tolist()))


def _tasks(m: int) -> list:
    """[0, m) in ranges of at most `_PIECE` edges."""
    return _pieces(m, max(1, -(-m // _PIECE)))


def _sort_edges(n: int, chunks: list, dedup: bool, pool, workers: int):
    """Edges sorted by (major, minor), ties in input order; with `dedup`
    only the first edge of each run of equal (major, minor) pairs stays.

    `chunks` is the edge list as consecutive pieces of (major, minor,
    weight) int32 arrays; the list is emptied once they are scattered, so
    a caller that holds no other reference frees them. A bucket of 2**shift consecutive major ids holds
    the high bits of an edge's major id; one int64 key holds the rest of
    it, the minor id and the edge's position in the bucket, which comes
    last, so sorting the keys alone is a stable sort. Edges are scattered
    stably into their buckets, each bucket's keys are sorted, and the keys
    are unpacked. Every step runs over pieces on `pool`'s threads (NumPy's
    sorts, scatters and gathers release the GIL).

    Returns (major, minor, weight) int32 arrays and each major id's count.
    No step holds an array of all N ids per piece (`np.bincount` of the
    major ids would, a copy per piece), so the memory is O(E) + O(N)."""
    nbits = max(n - 1, 0).bit_length()
    # the major ids' histogram in 2**fine-id bins sizes the buckets
    fine = max(nbits - 16, 0)
    nfine = ((n - 1) >> fine) + 1 if n else 0
    hist = np.zeros(nfine, np.int64)
    for h in pool.map(lambda ch: np.bincount((ch[0] >> fine).astype(np.intp),
                                             minlength=nfine), chunks):
        hist += h
    m = int(hist.sum())
    if m == 0:
        empty = np.zeros(0, np.int32)
        return empty, empty, empty, np.zeros(n, np.int64)
    # as few buckets as keep the threads busy, as many as the key needs
    want = 4 * workers if m >= _PARALLEL_MIN_EDGES else 1
    shift = max(nbits - (want - 1).bit_length(), fine)
    while True:
        sizes = np.add.reduceat(hist, np.arange(0, nfine, 1 << (shift - fine)))
        pbits = (int(sizes.max()) - 1).bit_length()
        if shift + nbits + pbits <= 63 or shift == fine:
            break
        shift -= 1
    if shift + nbits + pbits > 63:
        raise ValueError(f"{m} edges over {n} vertices do not fit the "
                         "63-bit sort key")
    nb = len(sizes)
    bstart = np.zeros(nb + 1, np.int64)
    np.cumsum(sizes, out=bstart[1:])
    bdt = np.uint8 if nb <= 1 << 8 else np.uint16 if nb <= 1 << 16 else np.uint32

    def bucket(ch):
        b = (ch[0] >> shift).astype(bdt)
        return b, np.bincount(b.astype(np.intp), minlength=nb)
    parts = list(pool.map(bucket, chunks))
    per = np.array([c for _, c in parts], np.int64).reshape(len(chunks), nb)
    # where each chunk's run of each bucket starts, chunks in order
    off = bstart[:-1] + np.cumsum(per, axis=0) - per
    keys = np.empty(m, np.int64)
    wts = np.empty(m, np.int32)

    def scatter(k):
        (maj, mnr, w), (b, c) = chunks[k], parts[k]
        order = np.argsort(b, kind="stable")
        bs = b[order]
        dest = off[k][bs] + (np.arange(len(bs)) - (np.cumsum(c) - c)[bs])
        keys[dest] = (((maj[order] & ((1 << shift) - 1)).astype(np.int64)
                       << (nbits + pbits))
                      | (mnr[order].astype(np.int64) << pbits)
                      | (dest - bstart[bs]))
        wts[dest] = w[order]
    list(pool.map(scatter, range(len(chunks))))
    chunks.clear()
    del parts

    # buckets in groups of about m / (4 workers) edges, one task a group
    firsts = [a for a, _ in _pieces(m, 4 * workers)]
    cuts = np.unique(np.concatenate(
        ([0], np.searchsorted(bstart, firsts, side="right") - 1, [nb])))

    def sort(i):
        for b in range(cuts[i], cuts[i + 1]):
            keys[bstart[b]:bstart[b + 1]].sort()
    list(pool.map(sort, range(len(cuts) - 1)))

    pmask, nmask = (1 << pbits) - 1, (1 << nbits) - 1
    # without dedup every edge stays where it lies: write the results in place
    direct = None if dedup else [np.empty(m, np.int32) for _ in range(3)]

    def unpack(piece):
        a, z = piece
        lo = max(a - 1, 0)         # the edge before, to compare against
        b0 = int(np.searchsorted(bstart, lo, side="right")) - 1
        b1 = int(np.searchsorted(bstart, z - 1, side="right")) - 1
        bids = np.arange(b0, b1 + 1)
        bk = np.repeat(bids, np.minimum(bstart[bids + 1], z)
                       - np.maximum(bstart[bids], lo))
        k = keys[lo:z]
        pair = k >> pbits
        maj = ((bk << shift) + (pair >> nbits)).astype(np.int32)
        mnr = (pair & nmask).astype(np.int32)
        w = wts[bstart[bk] + (k & pmask)]
        if dedup:
            keep = np.ones(len(k), bool)
            full = (maj.astype(np.int64) << nbits) | mnr
            np.not_equal(full[1:], full[:-1], out=keep[1:])
            keep[0] = lo == a
            maj, mnr, w = maj[keep], mnr[keep], w[keep]
        elif lo < a:
            maj, mnr, w = maj[1:], mnr[1:], w[1:]
        # the piece's ids are sorted: count them over their own range
        first = int(maj[0]) if len(maj) else 0
        c = np.bincount((maj - first).astype(np.intp))
        if direct is not None:
            for res, got in zip(direct, (maj, mnr, w)):
                res[a:z] = got
            return [None, None, None, first, c]
        return [maj, mnr, w, first, c]
    out = list(pool.map(unpack, _tasks(m)))
    counts = np.zeros(n, np.int64)
    for _, _, _, first, c in out:
        counts[first:first + len(c)] += c
    if direct is not None:
        return (*direct, counts)

    def assemble(i):
        # one piece at a time, each freed once copied
        res = np.empty(sum(len(o[i]) for o in out), np.int32)
        pos = 0
        for o in out:
            res[pos:pos + len(o[i])] = o[i]
            pos += len(o[i])
            o[i] = None
        return res
    return assemble(0), assemble(1), assemble(2), counts


def _row_pointers(counts: np.ndarray) -> np.ndarray:
    indptr = np.zeros(len(counts) + 1, np.int64)
    np.cumsum(counts, out=indptr[1:])
    return indptr.astype(np.int32)


def from_edges(
    n: int,
    src: np.ndarray,
    dst: np.ndarray,
    weights: Optional[np.ndarray] = None,
    *,
    undirected: bool = False,
    dedup: bool = True,
    drop_self_loops: bool = False,
) -> CSRGraph:
    """Build a CSRGraph on the host: every array is numpy. Its device
    copies are views its `GraphContext` builds (`device_graph()` on one
    device, `dist_arrays(mesh)` partitioned), so no array of the whole
    graph goes to a device that does not run the whole graph.

    With `undirected` each edge is also taken in reverse (the reversed
    copies after all the given ones), with `drop_self_loops` self-loops
    go, and with `dedup` the first copy of each repeated edge stays."""
    src = np.asarray(src)
    dst = np.asarray(dst)
    if src.shape != dst.shape or src.ndim != 1:
        raise ValueError(f"src and dst must be 1-D of one length, got "
                         f"{src.shape} and {dst.shape}")
    for name, ids in (("src", src), ("dst", dst)):
        if len(ids) and (int(ids.min()) < 0 or int(ids.max()) >= n):
            raise ValueError(f"{name} holds vertex ids outside [0, {n})")
    src = src.astype(np.int32, copy=False)
    dst = dst.astype(np.int32, copy=False)
    w = np.ones(len(src), np.int32) if weights is None else np.asarray(weights)
    if w.dtype != np.int32:      # int64 first: floats truncate, as ever
        w = w.astype(np.int64).astype(np.int32)
    workers = _workers() if len(src) >= _PARALLEL_MIN_EDGES else 1

    def piece(args):
        (a, z), flip = args
        s, d, ww = src[a:z], dst[a:z], w[a:z]
        if flip:
            s, d = d, s
        if drop_self_loops:
            keep = s != d
            s, d, ww = s[keep], d[keep], ww[keep]
        return s, d, ww

    with ThreadPoolExecutor(workers) as pool:
        spans = _tasks(len(src))
        chunks = list(pool.map(piece, [(p, False) for p in spans]
                               + [(p, True) for p in spans if undirected]))
        # the forward CSR's one sort, which also drops the duplicates
        with span("graph.dedup" if dedup else "graph.csr"):
            edge_src, indices, w_s, out_deg = _sort_edges(n, chunks, dedup,
                                                          pool, workers)
        e = len(indices)
        with span("graph.csr"):
            indptr = _row_pointers(out_deg)
        with span("graph.csr"):
            rchunks = [(indices[a:z], edge_src[a:z], w_s[a:z])
                       for a, z in _tasks(e)]
            rev_edge_dst, rev_indices, rev_w, in_deg = _sort_edges(
                n, rchunks, False, pool, workers)
            rev_indptr = _row_pointers(in_deg)
            # CSR order is sorted by (src, dst), so the key array is sorted
            # by construction; int64 intermediate avoids silent wrap while
            # building
            edge_key = np.empty(e, np.int32)

            def key(p):
                a, z = p
                edge_key[a:z] = edge_src[a:z].astype(np.int64) * n + indices[a:z]
            list(pool.map(key, _tasks(e)))
    out_deg = out_deg.astype(np.int32)
    in_deg = in_deg.astype(np.int32)
    return CSRGraph(
        indptr=indptr, indices=indices, weights=w_s,
        edge_src=edge_src, rev_indptr=rev_indptr,
        rev_indices=rev_indices, rev_weights=rev_w, rev_edge_dst=rev_edge_dst,
        out_degree=out_deg, in_degree=in_deg, edge_key=edge_key,
        num_nodes=int(n),
        num_edges=int(e),
        max_out_degree=int(out_deg.max(initial=1)),
        max_in_degree=int(in_deg.max(initial=1)),
    )


def to_dense(g: CSRGraph, dtype=jnp.float32) -> jax.Array:
    """Dense adjacency (small graphs only — tests + the TC matmul path)."""
    a = jnp.zeros((g.num_nodes, g.num_nodes), dtype)
    return a.at[g.edge_src, g.indices].set(1)


# --- block-ELL view (Pallas backend) ----------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EllGraph:
    """Padded neighbor-list (ELL) view: rectangular, so a TPU kernel can tile it.

    cols[i, k] = k-th out-neighbor of i (or `n` for padding);
    wts [i, k] = its weight (or INF for padding).
    Rows are padded to `max_deg` rounded up to a multiple of 8 so the
    (row_block × deg_block) tiles line up with the 8×128 VPU lanes.
    """

    cols: jax.Array  # int32[N, D]
    wts: jax.Array   # int32[N, D]
    num_nodes: int = dataclasses.field(metadata=dict(static=True))
    max_deg: int = dataclasses.field(metadata=dict(static=True))


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def to_ell(g: CSRGraph, *, reverse: bool = False, pad_to: int = 8) -> EllGraph:
    indptr = np.asarray(g.rev_indptr if reverse else g.indptr)
    indices = np.asarray(g.rev_indices if reverse else g.indices)
    wts = np.asarray(g.rev_weights if reverse else g.weights)
    n = g.num_nodes
    deg = np.diff(indptr)
    d = max(int(deg.max()) if n else 0, 1)
    d = _round_up(d, pad_to)
    cols = np.full((n, d), n, np.int32)          # n == "no neighbor" sentinel
    w = np.full((n, d), int(INF_I32), np.int32)
    for i in range(n):
        s, e = indptr[i], indptr[i + 1]
        cols[i, : e - s] = indices[s:e]
        w[i, : e - s] = wts[s:e]
    return EllGraph(cols=jnp.asarray(cols), wts=jnp.asarray(w), num_nodes=n, max_deg=d)


# --- degree-bucketed sliced-ELL view (frontier-aware engine) ----------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SlicedEllGraph:
    """Degree-bucketed ELL: rows grouped by degree, each bucket padded only to
    its own width, hub rows (degree > the widest bucket) kept as flat COO.

    The single `[N, max_deg]` ELL view pads every row to the hub degree; on a
    power-law graph that is O(N·max_deg) work and memory for O(E) useful
    entries. Bucketing by degree (widths 8, 32, 128, 512 by default) brings
    padded work back to near O(E) while every bucket stays rectangular —
    still a TPU-tileable layout, just several small ones.

    Per bucket b: cols[b] is int32[Rb, Db] (sentinel `num_nodes` for padding,
    its x-slot holds 0), wts[b] is int32[Rb, Db] (INF padding), rows[b] is
    int32[Rb] (original row id; sentinel `num_nodes` for row padding —
    scatter-dropped). Hub edges: (hub_rows, hub_cols, hub_wts) int32[Eh].
    """

    cols: tuple      # tuple of int32[Rb, Db]
    wts: tuple       # tuple of int32[Rb, Db]
    rows: tuple      # tuple of int32[Rb]
    hub_rows: jax.Array  # int32[Eh]
    hub_cols: jax.Array  # int32[Eh]
    hub_wts: jax.Array   # int32[Eh]
    num_nodes: int = dataclasses.field(metadata=dict(static=True))
    widths: tuple = dataclasses.field(default=(), metadata=dict(static=True))

    def padded_cells(self) -> int:
        """Total padded (cols) slots — the memory/work proxy benchmarks track."""
        return sum(int(c.shape[0]) * int(c.shape[1]) for c in self.cols) \
            + int(self.hub_cols.shape[0])


def to_sliced_ell(
    g: CSRGraph,
    *,
    reverse: bool = False,
    schedule: Optional[Schedule] = None,
    num_buckets: Optional[int] = None,
    min_width: Optional[int] = None,
    growth: Optional[int] = None,
    row_pad: int = 8,
) -> SlicedEllGraph:
    """Build the degree-bucketed view (host side, once per graph).

    The bucket layout comes from `schedule` (default: the `ENGINE` shim's
    snapshot, i.e. the default `Schedule`); the explicit knob kwargs remain
    as per-call overrides. `reverse=True` buckets by in-degree with
    in-neighbor columns — the pull orientation both backends relax/gather
    over. Degree-0 rows are dropped entirely (they contribute the semiring
    identity).
    """
    cfg = resolve_schedule(schedule)
    num_buckets = cfg.num_buckets if num_buckets is None else num_buckets
    min_width = cfg.min_width if min_width is None else min_width
    growth = cfg.growth if growth is None else growth
    indptr = np.asarray(g.rev_indptr if reverse else g.indptr)
    indices = np.asarray(g.rev_indices if reverse else g.indices)
    wts = np.asarray(g.rev_weights if reverse else g.weights)
    n = g.num_nodes
    deg = np.diff(indptr)
    widths = [min_width * growth**i for i in range(max(num_buckets, 1))]
    hub_width = widths[-1]

    b_cols, b_wts, b_rows = [], [], []
    prev_w = 0
    for w_b in widths:
        sel = np.nonzero((deg > prev_w) & (deg <= w_b))[0]
        prev_w = w_b
        if len(sel) == 0:
            continue
        rb = _round_up(len(sel), row_pad)
        cols = np.full((rb, w_b), n, np.int32)
        vals = np.full((rb, w_b), int(INF_I32), np.int32)
        rows = np.full((rb,), n, np.int32)
        rows[: len(sel)] = sel
        for k, r in enumerate(sel):
            s, e = indptr[r], indptr[r + 1]
            cols[k, : e - s] = indices[s:e]
            vals[k, : e - s] = wts[s:e]
        b_cols.append(jnp.asarray(cols))
        b_wts.append(jnp.asarray(vals))
        b_rows.append(jnp.asarray(rows))

    hub_sel = np.nonzero(deg > hub_width)[0]
    hr, hc, hw = [], [], []
    for r in hub_sel:
        s, e = indptr[r], indptr[r + 1]
        hr.append(np.full(e - s, r, np.int32))
        hc.append(indices[s:e].astype(np.int32))
        hw.append(wts[s:e].astype(np.int32))
    cat = (lambda xs: np.concatenate(xs) if xs else np.zeros(0, np.int32))
    return SlicedEllGraph(
        cols=tuple(b_cols), wts=tuple(b_wts), rows=tuple(b_rows),
        hub_rows=jnp.asarray(cat(hr)), hub_cols=jnp.asarray(cat(hc)),
        hub_wts=jnp.asarray(cat(hw)),
        num_nodes=n, widths=tuple(int(c.shape[1]) for c in b_cols))


def pad_nodes(g: CSRGraph, multiple: int) -> CSRGraph:
    """Pad to a node-count multiple (the paper pads the last MPI shard; we pad
    so every device shard has identical extent). A host view, like `g`'s
    own arrays from `from_edges`."""
    n = g.num_nodes
    n_pad = _round_up(max(n, 1), multiple)
    if n_pad == n:
        return g
    extra = n_pad - n

    def pad(a, fill):
        a = np.asarray(a)
        return np.concatenate([a, np.full(extra, fill, a.dtype)])
    edge_src, indices = np.asarray(g.edge_src), np.asarray(g.indices)
    return dataclasses.replace(
        g,
        indptr=pad(g.indptr, np.asarray(g.indptr)[-1]),
        rev_indptr=pad(g.rev_indptr, np.asarray(g.rev_indptr)[-1]),
        out_degree=pad(g.out_degree, 0),
        in_degree=pad(g.in_degree, 0),
        # the key encodes num_nodes, so it must be rebuilt for the new N
        # (still sorted: CSR order is (src, dst)-lexicographic)
        edge_key=(edge_src.astype(np.int64) * n_pad + indices).astype(np.int32),
        num_nodes=n_pad,
    )
