"""GraphContext: the per-graph registry of derived execution structures.

Every backend wants something built from a `CSRGraph` once and reused
across calls — the pallas backend its degree-bucketed sliced-ELL views
(forward or reverse, including the COO hub tail), the distributed backend
its 1-D partitioned device arrays, benchmarks the dense padded ELL view.
Before this module each consumer kept its own cache (the pallas codegen
hid one inside every compiled program's closure); now all derived state
for a graph lives in ONE `GraphContext`, found through a weakref-keyed
module registry:

    ctx = get_context(g)                 # registered on first touch
    dg  = ctx.device_graph()             # the whole CSR on one device, once
    ell = ctx.sliced_ell(schedule)       # built once per (layout, reverse)
    gd  = ctx.dist_arrays(mesh)          # built once per partitioning

`from_edges` builds the graph on the host (numpy arrays); every device
copy is a view here. The local and pallas programs run on
`device_graph()`, the distributed one on `dist_arrays(mesh)`, which
places each shard straight from the host arrays onto its own device, so
a distributed graph never has a whole copy on any one device.

Entries hold a WEAK reference to the graph: `id(g)` alone is unsafe (ids
are reused after GC, so a dead graph could alias a new one's views) and a
strong reference would leak every graph ever run. The weakref callback
evicts the entry the moment the graph is collected, and the `ref() is g`
check guards the window before the callback fires.

`prepare(g, schedule)` is the explicit warm-up entry point: call it before
serving traffic so the first query does not pay the host-side view build.

The context also owns the graph's *identity and shape* for the autotuner
(`repro.autotune`): `fingerprint()` is a stable content digest (keys
persisted `TuningRecord`s, so a stored schedule is never replayed against
a different graph), and `stats()` summarizes the degree distribution and
frontier growth (skew, average degree, a BFS probe) — the signals the
tuner's search-space pruning branches on. Both are memoized views like
everything else here. See `docs/architecture.md` for how the
Schedule / GraphContext / compile-cache triad fits together.
"""
from __future__ import annotations

import dataclasses
import hashlib
import weakref
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..graph.csr import (CSRGraph, pad_nodes, resolve_schedule, to_ell,
                         to_sliced_ell)
from ..schedule import Schedule
from ..trace import span


class GraphContext:
    """Owns every derived structure of one graph, keyed by (kind, layout).

    Views are built lazily and memoized; two schedules that share a
    `layout_key()` (same bucket structure) share the same sliced view, and
    all programs compiled against the graph share this one context."""

    __slots__ = ("_graph_ref", "_views")

    def __init__(self, graph: CSRGraph):
        self._graph_ref = weakref.ref(graph)
        self._views: dict = {}

    @property
    def graph(self) -> CSRGraph:
        g = self._graph_ref()
        if g is None:
            raise ReferenceError(
                "the graph behind this GraphContext was garbage-collected")
        return g

    def view(self, key, build):
        """Memoized derived structure: `build(graph)` runs at most once."""
        v = self._views.get(key)
        if v is None:
            v = self._views[key] = build(self.graph)
        return v

    def view_keys(self) -> list:
        """The (kind, ...) keys of every view built so far (introspection)."""
        return sorted(self._views, key=repr)

    # ---- memory accounting + eviction ------------------------------------
    # views that are metadata (a digest string, a stats dict), not device
    # memory: never worth evicting, and they key persisted tuning records
    _META_VIEWS = ("fingerprint", "stats")

    def view_nbytes(self) -> dict:
        """Approximate bytes held by each built view, keyed like `_views`.

        Counts array buffers (anything with `.nbytes`) reachable through
        dataclass fields / dicts / sequences; scalars and strings count as
        zero. The padded/dist views replicate the graph's own arrays, so
        this measures what *eviction would free*, not unique residency."""
        return {k: _approx_nbytes(v) for k, v in self._views.items()}

    def total_view_nbytes(self) -> int:
        """Approximate bytes held by every derived view (metadata views are
        ~0 by construction)."""
        return sum(self.view_nbytes().values())

    def drop_view(self, key) -> bool:
        """Forget one memoized view (it rebuilds lazily on next request).
        Returns True when the key was present."""
        return self._views.pop(key, None) is not None

    def drop_derived_views(self) -> int:
        """Evict every *derived* view (sliced-ELL, delta-ELL, padded ELL,
        padded graphs, distributed partitions), keeping the metadata views
        (`fingerprint`, `stats`) that key tuning records. Returns the
        approximate bytes freed. Consumers resolve views through the
        context per call, so the next query transparently re-prepares."""
        freed = 0
        for key in list(self._views):
            if key[0] in self._META_VIEWS:
                continue
            freed += _approx_nbytes(self._views.pop(key))
        return freed

    # ---- the derived structures ------------------------------------------
    def device_graph(self) -> CSRGraph:
        """The graph with every array on the default device: the one
        device copy the local and pallas programs run on, made once."""
        def build(g):
            with span("graph.to_device"):
                return dataclasses.replace(g, **{
                    f.name: jnp.asarray(getattr(g, f.name))
                    for f in dataclasses.fields(g)
                    if not f.metadata.get("static")})
        return self.view(("device",), build)

    def sliced_ell(self, schedule: Optional[Schedule] = None, *,
                   reverse: bool = True):
        """Degree-bucketed sliced-ELL view (+ COO hub tail). `reverse=True`
        is the pull orientation the engine relaxes/gathers over."""
        sched = resolve_schedule(schedule)
        key = ("sliced_ell", bool(reverse), sched.layout_key())
        return self.view(key, lambda g: to_sliced_ell(
            g, reverse=reverse, schedule=sched))

    def ell(self, *, reverse: bool = False):
        """Dense padded `[N, max_deg]` ELL view (benchmark baseline)."""
        return self.view(("ell", bool(reverse)),
                         lambda g: to_ell(g, reverse=reverse))

    # a padded forward ELL costs N * round8(max_deg) cells; past this many
    # multiples of E (hub-heavy degree distributions) the compact bucket
    # relax would gather mostly padding, so delta-stepping falls back dense
    DELTA_ELL_MAX_BLOWUP = 8

    def delta_ell(self):
        """Forward padded ELL view for the delta-stepping compact relax
        (`rt.relax_minplus_delta` gathers frontier out-rows from it), or
        None when the padding blowup makes it uneconomical — the relax then
        takes its dense fallback, which computes the same fixed point."""
        def build(g):
            cells = g.num_nodes * max(-(-max(int(g.max_out_degree), 1) // 8) * 8, 8)
            if cells > self.DELTA_ELL_MAX_BLOWUP * max(g.num_edges, 1):
                return None
            return to_ell(g, reverse=False)
        return self.view(("delta_ell",), build)

    def padded(self, multiple: int) -> CSRGraph:
        """Node-count-padded copy of the graph (device-shard alignment)."""
        return self.view(("padded", int(multiple)),
                         lambda g: pad_nodes(g, multiple))

    def dist_arrays(self, mesh, *, ell: bool = False,
                    edge_key: bool = False) -> dict:
        """1-D block-partitioned arrays for the distributed backend, placed
        on `mesh` (one view per mesh; key[1] is its shard count), with the
        replicated edge key only for a program that reads it."""
        from . import runtime_dist as rtd
        key = ("dist_1d", int(mesh.shape[rtd.AXIS]), bool(ell),
               bool(edge_key), mesh)
        return self.view(key, lambda g: rtd.prepare_graph_1d(
            g, mesh, ell=ell, edge_key=edge_key))

    def fingerprint(self) -> str:
        """Stable content digest of the graph (structure + weights).

        Keys persisted autotuning records: two CSRGraphs with identical
        edges hash equal regardless of object identity, and any edit to
        the graph yields a different fingerprint, so a stored schedule is
        re-tuned rather than silently replayed against the wrong graph."""
        return self.view(("fingerprint",), _graph_fingerprint)

    def stats(self) -> dict:
        """Degree-distribution + frontier-growth summary (host-side, memoized).

        The autotuner's search-space pruning branches on these: a power-law
        graph (high ``skew``/``deg_cv``, explosive ``probe_growth``) wants
        deep bucket layouts and direction switching; a road-like graph
        (uniform degree, ``probe_depth`` at the cap, flat frontier) wants a
        single narrow bucket and a pinned sparse-frontier direction."""
        return self.view(("stats",), _graph_stats)


# --------------------------------------------------------------------------
# view memory accounting
# --------------------------------------------------------------------------

def _approx_nbytes(v, _seen=None) -> int:
    """Bytes of array buffer reachable from a derived view: walks dataclass
    fields (CSRGraph, EllGraph, SlicedEllGraph are all frozen dataclasses),
    dicts (dist partitions), and sequences; an object with `.nbytes` is a
    buffer and counted directly. Shared buffers are counted once."""
    if _seen is None:
        _seen = set()
    if id(v) in _seen:
        return 0
    _seen.add(id(v))
    nb = getattr(v, "nbytes", None)
    if isinstance(nb, (int, np.integer)):
        return int(nb)
    if dataclasses.is_dataclass(v) and not isinstance(v, type):
        return sum(_approx_nbytes(getattr(v, f.name), _seen)
                   for f in dataclasses.fields(v))
    if isinstance(v, dict):
        return sum(_approx_nbytes(x, _seen) for x in v.values())
    if isinstance(v, (list, tuple)):
        return sum(_approx_nbytes(x, _seen) for x in v)
    return 0


# --------------------------------------------------------------------------
# graph identity + statistics (autotuner inputs)
# --------------------------------------------------------------------------

PROBE_MAX_LEVELS = 64   # frontier probe cap: deep graphs saturate the signal


def _graph_fingerprint(g: CSRGraph) -> str:
    """sha256 over (N, E, version, indptr, indices, weights), truncated to
    16 hex chars. Content-addressed up to the update generation:
    independent of object identity and of every derived view, but an
    `update()` bumps `version` so even a content-identical successor (e.g.
    delete-then-reinsert) keys fresh tuning records and bind-cache
    entries instead of aliasing the pre-update graph's."""
    h = hashlib.sha256()
    h.update(f"{g.num_nodes}:{g.num_edges}:{g.version}:".encode())
    for arr in (g.indptr, g.indices, g.weights):
        h.update(np.ascontiguousarray(np.asarray(arr)).tobytes())
    return h.hexdigest()[:16]


def _graph_stats(g: CSRGraph) -> dict:
    """Host-side numpy summary of the degree distribution plus a capped
    level-synchronous BFS probe from the highest-out-degree vertex."""
    n, e = g.num_nodes, g.num_edges
    out_deg = np.asarray(g.out_degree)
    avg = e / n if n else 0.0
    std = float(out_deg.std()) if n else 0.0
    weights = np.asarray(g.weights)
    avg_w = float(weights.mean()) if e else 0.0
    stats = {
        "num_nodes": n,
        "num_edges": e,
        "avg_degree": round(avg, 3),
        "max_out_degree": int(g.max_out_degree),
        "max_in_degree": int(g.max_in_degree),
        # degree skew: how far the heaviest hub sits above the mean
        "skew": round(g.max_out_degree / avg, 3) if avg else 1.0,
        # coefficient of variation: 0 for regular graphs, >1 for power laws
        "deg_cv": round(std / avg, 3) if avg else 0.0,
        # weight scale: candidate delta_bucket widths are multiples of the
        # mean edge weight (a bucket spans ~avg_weight * k relaxed hops)
        "avg_weight": round(avg_w, 3),
        "max_weight": int(weights.max()) if e else 0,
    }
    if e == 0:
        stats.update(probe_depth=0, probe_max_frontier_frac=0.0,
                     probe_growth=1.0, probe_reach_frac=0.0)
        return stats
    # frontier-growth probe: BFS from the heaviest hub, recording per-level
    # frontier sizes (edge-parallel sweep per level — O(E) each, capped)
    edge_src = np.asarray(g.edge_src)
    indices = np.asarray(g.indices)
    root = int(out_deg.argmax())
    level = np.full(n, -1, np.int32)
    level[root] = 0
    front = np.zeros(n, bool)
    front[root] = True
    sizes = [1]
    for lvl in range(PROBE_MAX_LEVELS):
        hit = np.zeros(n, bool)
        hit[indices[front[edge_src]]] = True
        newly = hit & (level < 0)
        if not newly.any():
            break
        level[newly] = lvl + 1
        front = newly
        sizes.append(int(newly.sum()))
    growth = max((b / a for a, b in zip(sizes, sizes[1:])), default=1.0)
    stats.update(
        probe_depth=len(sizes) - 1,                  # levels until exhaustion/cap
        probe_max_frontier_frac=round(max(sizes) / n, 4),
        probe_growth=round(growth, 2),               # peak level-over-level ratio
        probe_reach_frac=round(sum(sizes) / n, 4),   # fraction reached from hub
    )
    return stats


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------

_REGISTRY: dict = {}   # id(graph) -> (weakref(graph), GraphContext)


def get_context(g: CSRGraph) -> GraphContext:
    """The graph's `GraphContext`, creating (and registering) it on first
    touch. Cheap enough to call per query: one dict probe + one weakref
    deref on the hot path."""
    key = id(g)
    entry = _REGISTRY.get(key)
    if entry is None or entry[0]() is not g:
        ref = weakref.ref(g, lambda _r, _k=key: _REGISTRY.pop(_k, None))
        _REGISTRY[key] = entry = (ref, GraphContext(g))
    return entry[1]


def contains(g: CSRGraph) -> bool:
    """True if `g` currently has a live registered context."""
    entry = _REGISTRY.get(id(g))
    return entry is not None and entry[0]() is g


def registry_size() -> int:
    return len(_REGISTRY)


def clear() -> None:
    """Drop every registered context (tests / memory pressure)."""
    _REGISTRY.clear()


def prepare(g: CSRGraph, schedule: Optional[Schedule] = None, *,
            backend: str = "pallas", mesh=None, program=None) -> GraphContext:
    """Explicit warm-up: build the derived structures `backend` needs so the
    first query served against `g` pays no view construction or transfer.

    * ``local`` — the graph's one device copy (`device_graph()`);
    * ``pallas`` — that copy and the reverse sliced-ELL view for
      `schedule`'s layout;
    * ``distributed`` — the 1-D partition for `mesh` (default: one shard
      per local device), and no whole copy on any device; pass `program=`
      so the views its generated body reads (`dist_meta`: the replicated
      ELL view and edge key of TC) are the ones `bind` will request.

    `program=` also supplies the schedule/backend defaults:
    `prepare(g, program=prog)` warms precisely what `prog.bind(g)` needs.
    It waits until the views are on their devices, so their transfer is
    part of the warm-up and not of the first query.

    Returns the graph's `GraphContext` (the same object every consumer of
    `g` sees). Idempotent and cheap when already warm."""
    if program is not None:
        if schedule is None:
            schedule = getattr(program, "schedule", None)
        backend = getattr(program, "backend", backend)
    sched = resolve_schedule(schedule)
    with span("prepare"):
        ctx = get_context(g)
        if backend == "local":
            views = ctx.device_graph()
        elif backend == "pallas":
            views = (ctx.device_graph(), ctx.sliced_ell(sched, reverse=True))
        elif backend == "distributed":
            from . import runtime_dist as rtd
            if mesh is None:
                from .dist import make_mesh_1d
                mesh = make_mesh_1d()
            meta = getattr(program, "dist_meta", None)
            views = ctx.dist_arrays(mesh, **rtd.view_flags(meta))
        else:
            raise ValueError(
                f"unknown backend {backend!r}; expected 'local', 'pallas', or "
                "'distributed'")
        jax.block_until_ready(views)
    return ctx


def on_device(g: CSRGraph) -> CSRGraph:
    """`g` as a jitted program takes it: a graph built on the host maps to
    its one device copy (`GraphContext.device_graph`); a graph whose
    arrays are already jax arrays, or tracers, is returned as it is."""
    if isinstance(g.indptr, np.ndarray):
        return get_context(g).device_graph()
    return g


def adopt_patched_views(delta) -> GraphContext:
    """Carry the old graph's sliced-ELL views across a `g.update()`.

    `apply_update` calls this eagerly with the `GraphDelta` it built: every
    `("sliced_ell", reverse, layout)` view the OLD graph's context holds is
    delta-patched (`repro.graph.dynamic.patch_sliced_ell` — in-place bucket
    row rewrites, hub-tail absorption of degree-class migrations) and
    installed into the NEW graph's context, so post-update queries skip the
    O(N + E) view rebuild. Other derived views (dense/delta ELL, padded
    graphs, distributed partitions) are left to rebuild lazily — they are
    either whole-graph reshapes with no cheap patch or benchmark-only.

    Returns the new graph's context (registered even when the old graph
    never had one, so the fingerprint/bind machinery sees the new
    `version` immediately)."""
    from ..graph.dynamic import patch_sliced_ell
    new_ctx = get_context(delta.graph)
    if contains(delta.old):
        old_ctx = get_context(delta.old)
        for key in old_ctx.view_keys():
            if key[0] != "sliced_ell" or key in new_ctx._views:
                continue
            _, rev, _layout = key
            new_ctx._views[key] = patch_sliced_ell(
                old_ctx._views[key], delta, reverse=rev)
    return new_ctx
