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
import warnings
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


def _build_csr(n: int, src: np.ndarray, dst: np.ndarray, w: np.ndarray):
    with span("graph.csr"):
        order = np.lexsort((dst, src))
        src, dst, w = src[order], dst[order], w[order]
        indptr = np.zeros(n + 1, np.int64)
        np.add.at(indptr, src + 1, 1)
        indptr = np.cumsum(indptr)
        return indptr.astype(np.int32), dst.astype(np.int32), w.astype(np.int32), src.astype(np.int32)


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
    """Build a CSRGraph (host-side numpy; the result is a device pytree)."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    if weights is None:
        w = np.ones_like(src)
    else:
        w = np.asarray(weights, np.int64)
    if undirected:
        src, dst, w = np.concatenate([src, dst]), np.concatenate([dst, src]), np.concatenate([w, w])
    if drop_self_loops:
        keep = src != dst
        src, dst, w = src[keep], dst[keep], w[keep]
    if dedup and len(src):
        with span("graph.dedup"):
            key = src * np.int64(n) + dst
            _, first = np.unique(key, return_index=True)
            src, dst, w = src[first], dst[first], w[first]
    e = len(src)
    indptr, indices, w_s, edge_src = _build_csr(n, src, dst, w)
    rev_indptr, rev_indices, rev_w, rev_edge_dst = _build_csr(n, dst, src, w)
    out_deg = np.diff(indptr).astype(np.int32)
    in_deg = np.diff(rev_indptr).astype(np.int32)
    # CSR order is lexsorted by (src, dst), so the key array is sorted by
    # construction; int64 intermediate avoids silent wrap while building.
    edge_key = (edge_src.astype(np.int64) * n + indices.astype(np.int64)).astype(np.int32)
    with span("graph.to_device"):
        arrays = dict(
            indptr=jnp.asarray(indptr),
            indices=jnp.asarray(indices),
            weights=jnp.asarray(w_s),
            edge_src=jnp.asarray(edge_src),
            rev_indptr=jnp.asarray(rev_indptr),
            rev_indices=jnp.asarray(rev_indices),
            rev_weights=jnp.asarray(rev_w),
            rev_edge_dst=jnp.asarray(rev_edge_dst),
            out_degree=jnp.asarray(out_deg),
            in_degree=jnp.asarray(in_deg),
            edge_key=jnp.asarray(edge_key))
    return CSRGraph(
        **arrays,
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
    so every device shard has identical extent)."""
    n = g.num_nodes
    n_pad = _round_up(max(n, 1), multiple)
    if n_pad == n:
        return g
    extra = n_pad - n
    def pad_ptr(p):
        p = np.asarray(p)
        return jnp.asarray(np.concatenate([p, np.full(extra, p[-1], p.dtype)]))
    return dataclasses.replace(
        g,
        indptr=pad_ptr(g.indptr),
        rev_indptr=pad_ptr(g.rev_indptr),
        out_degree=jnp.concatenate([g.out_degree, jnp.zeros(extra, jnp.int32)]),
        in_degree=jnp.concatenate([g.in_degree, jnp.zeros(extra, jnp.int32)]),
        # the key encodes num_nodes, so it must be rebuilt for the new N
        # (still sorted: CSR order is (src, dst)-lexicographic)
        edge_key=g.edge_src * jnp.int32(n_pad) + g.indices,
        num_nodes=n_pad,
    )
