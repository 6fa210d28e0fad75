"""Distributed backend — the paper's MPI code generator, on shard_map.

Faithful to the paper's §3.2 BSP structure with 1-D block vertex
partitioning (§4.2 "quick index-based partitioning", last block padded):

  paper MPI                         generated JAX (per device, in shard_map)
  ---------                         ----------------------------------------
  local vertex block                property arrays of shape [B]
  scatter/gather send-recv          jax.lax.all_gather (tiled) of properties
  send-buffer + aggregation (§4.2)  local scatter-min into [N_pad] + lax.pmin
  MPI_Barrier / BSP step            the collective itself (BSP by construction)
  is_finished over all ranks        psum of the local OR (global OR)

The backend is schedule-driven like the local/pallas engines — every knob
is baked into the generated source as a literal (same `Schedule` =>
byte-identical source):

  * `dist_frontier` / `dist_gather_frac` pick the BSP property-exchange
    policy per superstep: the dense full all-gather (the paper's scheme),
    or frontier-compressed exchange of only the entries that changed since
    the last superstep (`rtd.exchange`), with a skip when the global
    frontier is empty ("auto"). The `{p}_full` gathered views ride in the
    BSP loop carry so each superstep applies deltas to them.
  * `direction` / `push_threshold_frac` pick the relax/BFS direction for
    the frontier-relax pattern: push (local scatter + one global min/add
    combine — §4.2 aggregation) vs pull (a purely local segment reduction
    over the shard's in-edge partition), switched per superstep by the
    replicated frontier's occupancy when "auto".
  * `priority="delta"` lowers the monotonic Min-relax fixedPoint to
    delta-stepping: the frontier becomes the current bucket window
    (`delta_bucket` wide; bucket advance = global any/min collectives over
    the blocks), and the value prop's changed-entry exchange is
    priority-SLICED — only in-window changes ship each superstep, cutting
    `_gather_elems` further. Out-of-window changes ship when their bucket
    is reached (values only decrease, so they keep registering as changed).
  * `batch_sources` batches `forall(src in sourceSet)` into S-lane chunks
    (pod-parallel-style lanes): per-source [B] blocks become [S, B], the
    gathered views [S, N_pad], and each superstep's exchange/combine moves
    all lanes at once. Bodies outside the batched subset fall back to the
    sequential per-source loop automatically, exactly like the local
    backend.

Every generated program additionally returns `_gather_elems`, the number
of property-exchange elements its collectives actually moved — the
communication-volume measurement `benchmarks/bench_dist.py` reports — and
a program with a top-level loop `_supersteps`, the loop bodies it ran, as
on the local backend.

The generated function body runs per device; `repro.core.dist.run()` wraps
it in `jax.shard_map` over the mesh's 'data' axis.
"""
from __future__ import annotations

import contextlib

from .. import ir as I
from ..ir import read_props
from .base import (BFSCtx, CodegenError, EdgeCtx, ExprEmitter, HostCtx,
                   VertexCtx, only_reads_side, relax_candidate)
from .local_jax import LocalCodegen, has_refresh_variant

# Ablation switch for the loop-invariant gather hoist: properties a BSP
# loop body reads but never writes are gathered once before the loop
# instead of once per superstep. `benchmarks/bench_analysis.py` flips this
# off (with a compile-cache clear) to measure the pre-hoist exchange plan
# on the same graph; it is not part of the Schedule because it is never
# the better plan — only a measurement baseline.
HOIST_INVARIANT = True

_PARTITIONED_KEYS = ["esrc", "edst", "ew", "evalid", "esrc_local",
                     "idst", "isrc", "iw", "ivalid", "idst_local", "own_ids"]
_REPLICATED_KEYS = ["out_degree_rep", "in_degree_rep", "n_true_rep"]


class DistExprEmitter(ExprEmitter):
    """Property reads: block arrays in vertex context, gathered `_full`
    arrays when indexed by global edge-endpoint ids. Inside a batched
    source region, per-source arrays are [S, B] blocks / [S, N_pad] fulls
    and gathers move to the vertex axis (`arr_full[:, idx]`)."""

    full_mode = False   # filter emission over the full (gathered) arrays

    def expr(self, e, ctx):
        if isinstance(e, I.IProp):
            arr = self.prop_read(e.prop)
            if e.target is None:
                return arr
            idx = self.index_of(e.target, ctx)
            if idx == "_vids":
                return f"{arr}_full" if self.full_mode else arr
            b = self.batch
            if b is not None and e.prop in b.arrays:
                if idx == b.srcs2d:
                    raise CodegenError(
                        "reading a per-source property at the set iterator "
                        "is outside the batched distributed subset")
                return f"{arr}_full[:, {idx}]"
            return f"{arr}_full[{idx}]"
        if isinstance(e, (I.IIterId, I.INodeParam)):
            sidx = self.index_of(e.name, ctx)
            if sidx == "_vids" and self.full_mode:
                return "_vids_full"
            return sidx
        return super().expr(e, ctx)

    def call(self, e, ctx):
        if e.fn == "num_nodes":
            return "n_true"
        if e.fn in ("count_out_nbrs", "count_in_nbrs"):
            table = "out_degree_rep" if e.fn == "count_out_nbrs" else "in_degree_rep"
            idx = self.expr(e.args[0], ctx)
            if idx == "_vids":
                return f"{table}[own_ids]"
            if idx == "_vids_full":
                return table
            return f"{table}[{idx}]"
        return super().call(e, ctx)


class DistCodegen(LocalCodegen):
    backend_name = "distributed"
    VLEN = "B"
    # `forall(src in sourceSet)` batches into [S, B] lane blocks (the
    # pod-parallel lanes, fused into one program); bodies outside the
    # batched subset fall back to the sequential loop like the local backend
    supports_source_batching = True
    # delta-stepping here reshapes the EXCHANGE, not the relax: the bucketed
    # frontier flows through the partitioned push/pull supersteps unchanged,
    # so no `_dell` padded view is taken
    supports_delta_ell = False
    # per-source while/do-while loops (and their lane scalars) stay on the
    # sequential per-source fallback: fused lanes would need shard-uniform
    # per-lane trip counts threaded through every BSP superstep
    supports_batched_scalar_loops = False
    # top-level BSP loops count their supersteps; the relax counters of the
    # local backend have no distributed twin (see `_count_relax`)
    counters = (("_supersteps", "jnp.int32"),)

    def __init__(self, irfn: I.IRFunction, schedule=None):
        super().__init__(irfn, schedule=schedule)
        self.ex = DistExprEmitter(irfn, graph_var=irfn.graph_param)
        self.needs_ell = False
        self.needs_edge_key = False
        # stack of property groups whose `{p}_full` views are carried
        # through the enclosing BSP loop (compact/auto exchange policies)
        self._full_stack = []
        # stack of property groups the effect analysis proved loop-invariant
        # (read but never written inside the BSP loop): gathered once before
        # the loop under every policy, never re-exchanged per superstep
        self._invariant_stack = []
        # (value_prop, window_mask_var) of the active delta-stepping
        # fixedPoint: emit_gathers priority-slices that prop's exchange
        self._delta_within = None

    # ------------------------------------------------------------------ entry
    def generate(self) -> str:
        f, em = self.f, self.em
        args = [p.name for p in f.params]
        sig = ", ".join([args[0]] + [f"{a}=None" for a in args[1:]])
        em.w(f"def {f.name}({sig}):")
        with em.block():
            gd = f.graph_param
            for k in _PARTITIONED_KEYS:
                em.w(f"{k} = {gd}['{k}'][0]")
            em.w(f"if 'ell_cols' in {gd}: ell_cols = {gd}['ell_cols'][0]")
            for k in _REPLICATED_KEYS:
                em.w(f"{k} = {gd}['{k}']")
            em.w("n_true = n_true_rep")
            em.w("B = own_ids.shape[0]")
            em.w("P = jax.lax.axis_size('data')")
            em.w("N_PAD = B * P")
            em.w("_vids = own_ids")
            em.w("_vids_full = jnp.arange(N_PAD, dtype=jnp.int32)")
            # property-exchange volume accounting (elements moved by the
            # gather/exchange collectives; returned alongside the results).
            # Accumulated in f32: per-step counts are int32 <= N_PAD, but a
            # long BSP run can total past 2^31 and int64 is unavailable
            # under jax's default x64-disabled config — f32 stays exact to
            # 2^24 elements and degrades gracefully instead of wrapping.
            self.declare("_gather_elems", "float32")
            em.w("_gather_elems = jnp.float32(0)")
            for p in f.params:
                if p.kind == "prop_node":
                    self.declare(p.name, p.dtype)
                    em.w(f"if {p.name} is None:")
                    with em.block():
                        em.w(f"{p.name} = rt.init_prop(B, {self.jdt(p.dtype)})")
                elif p.kind == "scalar":
                    self.dtypes[p.name] = p.dtype
            if has_refresh_variant(f):
                for c, dt in self.counters:
                    self.declare(c, "int32")
                    em.w(f"{c} = {dt}(0)")
            for s in f.body:
                self._top = s
                self.stmt(s, HostCtx())
            rets = ", ".join(f"'{v}': {v}" for v in self.declared)
            em.w(f"return {{{rets}}}")
        return em.source()

    # ------------------------------------------------------------------ helpers
    def _count_relax(self, frontier, pushed, swept: str):
        """No relax counters here: a shard sweeps only its own edges."""

    def _edge_term(self, expr, ctx) -> str:
        # a term's neighbor operands read the exchanged `{p}_full` buffers
        # by global id; the shard's own [B] vertex block cannot stand in
        return self.ex.expr(expr, ctx)

    def fidx(self, arr: str, idx: str) -> str:
        """Index a replicated full array by an id array, batch-aware."""
        if self.batch is not None and arr in self.batch.arrays:
            return f"{arr}[:, {idx}]"
        return f"{arr}[{idx}]"

    def _full_vmask(self, expr: str) -> str:
        """Materialize a full-width ([N_PAD] / [S, N_PAD]) vertex mask;
        inside a batched region it is broadcast so downstream edge gathers
        see one uniform [S, *] shape."""
        m = self.em.uid("vmf")
        if self.batch is not None:
            self.em.w(f"{m} = jnp.broadcast_to(jnp.asarray({expr}), "
                      f"({self.batch.size}, N_PAD))")
            self.batch.arrays.add(m)
        else:
            self.em.w(f"{m} = {expr}")
        return m

    def _full_filter_expr(self, flt, it, ctx) -> str:
        """Emit a loop filter over the gathered full arrays."""
        self.ex.full_mode = True
        try:
            return self.ex.expr(flt, VertexCtx(it=it, mask=None, parent=ctx))
        finally:
            self.ex.full_mode = False

    def _carried_fulls(self) -> set:
        return {p for grp in self._full_stack for p in grp}

    def _invariant_fulls(self) -> set:
        return {p for grp in self._invariant_stack for p in grp}

    @contextlib.contextmanager
    def _bsp_loop_fulls(self, stmts):
        """Set up the `{p}_full` gathered views for one BSP loop.

        Effect split (the compile-time effect analysis made precise at the
        IR level): properties the loop reads but never writes are
        *loop-invariant* — gathered once here, before the loop, under every
        frontier policy, and never re-shipped per superstep (the view is a
        closure constant of the loop body). Read-AND-written properties are
        the actual BSP exchange set: under compact/auto their full views
        are carried through the loop and each superstep's `emit_gathers`
        applies only the changed entries (rtd.exchange); under dense they
        are re-gathered from scratch every superstep."""
        carried = self._carried_fulls()
        hoisted = self._invariant_fulls()
        written = I.written_vars(stmts)
        reads = [p for p in sorted(read_props(stmts))
                 if p in self.dtypes and p not in carried
                 and p not in hoisted]
        invariant = ([p for p in reads if p not in written]
                     if HOIST_INVARIANT else [])
        for p in invariant:
            self._emit_full_gather(p)
        self._invariant_stack.append(invariant)
        try:
            if self.schedule.dist_frontier == "dense":
                yield
                return
            props = [p for p in reads if p in written]
            for p in props:
                self._emit_full_gather(p)
            self._full_stack.append(props)
            try:
                yield
            finally:
                self._full_stack.pop()
        finally:
            self._invariant_stack.pop()

    def _emit_full_gather(self, p: str):
        batched = self.batch is not None and p in self.batch.arrays
        gfn = "rtd.gather_rows" if batched else "rtd.gather"
        self.em.w(f"{p}_full = {gfn}({p})")
        self.em.w(f"_gather_elems = _gather_elems + {p}_full.size")

    def emit_gathers(self, stmts):
        """BSP property exchange: make the `{p}_full` views every property
        the step reads consistent with the current blocks. This is the
        paper's scatter/gather communication phase; emitting it at loop
        entry gives exactly one exchange per BSP superstep. Properties with
        a carried full view exchange only their changed entries under the
        compiled `dist_frontier` policy; everything else takes the dense
        all-gather."""
        carried = self._carried_fulls()
        hoisted = self._invariant_fulls()
        sched = self.schedule
        for p in sorted(read_props(stmts)):
            if p not in self.dtypes:   # unknown name (not a property)
                continue
            if p in hoisted:   # loop-invariant: gathered once before the loop
                continue
            if p in carried:
                batched = self.batch is not None and p in self.batch.arrays
                xfn = "rtd.exchange_rows" if batched else "rtd.exchange"
                win = ""
                if not batched and self._delta_within is not None \
                        and p == self._delta_within[0]:
                    # priority slice: only changed entries inside the current
                    # bucket window ship this superstep; out-of-window changes
                    # stay local until their bucket is reached (they keep
                    # differing from the full view — values only decrease —
                    # so `chg` re-selects them then). The bucketed frontier is
                    # exchanged unsliced, so every in-window read is fresh.
                    win = f", within={self._delta_within[1]}"
                ge = self.em.uid("ge")
                self.em.w(f"{p}_full, {ge} = {xfn}({p}_full, {p}, own_ids, "
                          f"{sched.dist_gather_frac!r}, "
                          f"skip_empty={sched.dist_frontier == 'auto'}{win})")
                self.em.w(f"_gather_elems = _gather_elems + {ge}")
            else:
                self._emit_full_gather(p)

    def carries(self, body):
        out = super().carries(body)
        for p in (x for grp in self._full_stack for x in grp):
            full = f"{p}_full"
            if full not in out:
                out.append(full)
        if "_gather_elems" not in out:
            out.append("_gather_elems")
        return out

    def emit_finished(self, var: str, conv: str):
        self.em.w(f"{var} = ~rtd.any_global({conv})")

    # ---- delta-stepping hooks -------------------------------------------
    # the bucket advance runs on [B] blocks, so its any/min reductions must
    # be global collectives — every shard then agrees on the same bucket
    def _delta_any(self, expr: str) -> str:
        return f"rtd.any_global({expr})"

    def _delta_min(self, expr: str) -> str:
        return f"rtd.min_global({expr})"

    def _emit_delta_preamble(self, n: str, vprop: str, conv: str):
        """Bucketed-frontier preamble over the [B] blocks (emitted before
        this superstep's `emit_gathers`, so the window mask is available to
        priority-slice the value prop's exchange). The rebinding of `conv`
        to the windowed frontier happens on the block, BEFORE its exchange
        — the frontier's full view is therefore exact, and every read of
        the (possibly stale out-of-window) value full view is masked by
        it."""
        super()._emit_delta_preamble(n, vprop, conv)
        d = self.schedule.delta_bucket
        self.em.w(f"{n}_win = {vprop} < ({n}_bk + 1) * {d}")
        self._delta_within = (vprop, f"{n}_win")

    # ------------------------------------------------------------------ attach
    def s_IAttach(self, s: I.IAttach, ctx):
        if s.kind != "node":
            raise CodegenError("edge properties not supported")
        for prop, dtype, init in s.props:
            self.declare(prop, dtype)
            jdt = self.jdt(dtype)
            if self.batch is not None:
                # per-source property inside a batched set loop -> [S, B]
                self.batch.arrays.add(prop)
                sz = f"{self.batch.size}, B"
                if init is None:
                    self.em.w(f"{prop} = rt.init_prop_batch({sz}, {jdt})")
                elif isinstance(init, I.IConst) and init.kind == "inf":
                    self.em.w(f"{prop} = rt.init_prop_batch({sz}, {jdt}, rt.inf_for({jdt}))")
                else:
                    self.em.w(f"{prop} = rt.init_prop_batch({sz}, {jdt}, {self.ex.expr(init, ctx)})")
                continue
            if init is None:
                self.em.w(f"{prop} = rt.init_prop(B, {jdt})")
            elif isinstance(init, I.IConst) and init.kind == "inf":
                self.em.w(f"{prop} = rt.init_prop(B, {jdt}, rt.inf_for({jdt}))")
            else:
                self.em.w(f"{prop} = rt.init_prop(B, {jdt}, {self.ex.expr(init, ctx)})")

    def s_IWriteProp(self, s: I.IWriteProp, ctx):
        # single-node write: only the owning device's block slot changes
        # (in a batched region the [S, 1] iterator broadcasts lane-wise:
        # row s updates its own source vertex if owned)
        node = self.ex.expr(s.node, ctx)
        val = self.ex.expr(s.expr, ctx)
        p = self.wtarget(s.prop)
        if self.batch is not None:
            b = self.batch
            if s.prop not in b.arrays or node != b.srcs2d:
                raise CodegenError(
                    "batched single-node write must target the set iterator "
                    "on a per-source property")
        self.em.w(f"{p} = jnp.where(own_ids == {node}, {val}, {p})")

    def s_ICopyProp(self, s: I.ICopyProp, ctx):
        if self.batch is not None:
            ba = self.batch.arrays
            if (s.dst in ba) != (s.src in ba):
                raise CodegenError("copy between batched and shared property")
        self.em.w(f"{self.wtarget(s.dst)} = {s.src}")

    # ------------------------------------------------------------------ loops
    def s_IVertexLoop(self, s: I.IVertexLoop, ctx):
        em = self.em
        self.emit_gathers([s])
        mask = mask_full = None
        if s.filter is not None:
            mask_full = self._full_vmask(
                self._full_filter_expr(s.filter, s.it, ctx))
            if self.batch is not None:
                mask = self._vmask(f"{mask_full}[:, own_ids]")
            else:
                mask = em.uid("vm")
                em.w(f"{mask} = {mask_full}[own_ids]")
        vctx = VertexCtx(it=s.it, mask=mask, parent=ctx)
        vctx.mask_full = mask_full
        self.body(s.body, vctx)

    def _edge_arrays(self, direction: str):
        if direction == "out":
            return dict(vid="esrc", nid="edst", w="ew", seg="esrc_local",
                        valid="evalid")
        return dict(vid="idst", nid="isrc", w="iw", seg="idst_local",
                    valid="ivalid")

    def s_INbrLoop(self, s: I.INbrLoop, ctx):
        em = self.em
        vctx = self._vertex_ctx(ctx)
        if vctx is None:
            raise CodegenError("neighbor loop outside a vertex context")
        if self._try_wedge(s, ctx):
            return
        if isinstance(vctx, BFSCtx):
            return self._bfs_nbr_loop(s, ctx, vctx)
        a = self._edge_arrays(s.direction)
        ectx = EdgeCtx(it=s.it, source=s.source, direction=s.direction,
                       vid=a["vid"], nid=a["nid"], w=a["w"], seg=a["seg"],
                       seg_sorted=False, mask=None, parent=ctx)
        terms = [a["valid"]]
        pure = True
        mf = getattr(vctx, "mask_full", None)
        if mf:
            terms.append(self.fidx(mf, ectx.vid))
            ectx.src_vmask = mf
        if s.filter is not None:
            if only_reads_side(s.filter, s.it):
                # neighbor-side filter that only reads nbr-props: hoist it
                # to one full vertex mask (the frontier the engine and the
                # direction switch consume)
                nm = self._full_vmask(
                    self._full_filter_expr(s.filter, s.it, ctx))
                terms.append(self.fidx(nm, ectx.nid))
                ectx.it_vmask = nm
            else:
                terms.append(self.ex.expr(s.filter, ectx))
                pure = False
        ectx.pure_frontier = pure
        mask = em.uid("em")
        em.w(f"{mask} = {' & '.join(terms)}")
        ectx.mask = mask
        self.body(s.body, ectx)

    def _bfs_nbr_loop(self, s: I.INbrLoop, ctx, bctx: BFSCtx):
        em = self.em
        if s.direction != "out":
            raise CodegenError("only neighbors() supported inside iterateInBFS")
        a = self._edge_arrays("out")
        ectx = EdgeCtx(it=s.it, source=s.source, direction="out",
                       vid=a["vid"], nid=a["nid"], w=a["w"], seg=a["seg"],
                       seg_sorted=False, mask=None, parent=ctx)
        terms = [a["valid"],
                 f"({self.fidx(bctx.level, ectx.vid)} == {bctx.cur})",
                 f"({self.fidx(bctx.level, ectx.nid)} == ({bctx.cur} + 1))"]
        mf = getattr(bctx, "mask_full", None)
        if mf:
            terms.append(self.fidx(mf, ectx.vid))
        if s.filter is not None:
            terms.append(self.ex.expr(s.filter, ectx))
        mask = em.uid("em")
        em.w(f"{mask} = {' & '.join(terms)}")
        ectx.mask = mask
        self.body(s.body, ectx)

    # ------------------------------------------------------------------ writes
    def _dist_hybrid(self, s: I.IMinMaxUpdate, ectx):
        """Detect the frontier-relax pattern `Min(t.p, other.p [+ e.weight])`
        with nothing but a hoisted vertex frontier masking the contributing
        side — the pattern whose direction the Schedule may pin or switch.
        Returns (full frontier-mask name, weighted) or None; `weighted` is
        False for the bare-prop candidate (CC's unweighted component min),
        which takes the same push/pull supersteps minus the weight term."""
        if self.batch is not None or s.kind != "Min" \
                or not getattr(ectx, "pure_frontier", False):
            return None
        if self.f.node_props.get(s.prop) != "int32":
            return None
        if s.target == ectx.it and ectx.direction == "out":
            # push DSL form: the outer (frontier) vertex relaxes out-edges
            other, fr = ectx.source, ectx.src_vmask
            if ectx.it_vmask is not None:
                return None
        elif s.target == ectx.source and ectx.direction == "in":
            # pull DSL form: in-neighbors on the frontier contribute
            other, fr = ectx.it, ectx.it_vmask
            if ectx.src_vmask is not None:
                return None
        else:
            return None
        cand = relax_candidate(s.cand, other)
        if fr is None or cand is None or cand[0] != s.prop:
            return None
        return fr, cand[1]

    def _emit_relax_hybrid_dist(self, s: I.IMinMaxUpdate, fr: str,
                                weighted: bool = True) -> str:
        """Direction-optimized distributed relax superstep.

          push — local scatter-min over out-edges of frontier sources + one
                 global min-combine (the paper's §4.2 aggregation);
          pull — a purely local segment-min over the shard's in-edge
                 partition (no combine collective at all).

        Both compute min(dist[v], min over frontier in-neighbors u of
        dist[u] + w) exactly, so the per-superstep switch (on the
        replicated frontier's occupancy, shard-uniform by construction)
        never changes results. `Schedule.direction` pins one branch."""
        em = self.em
        sched = self.schedule
        jdt = self.jdt(self.f.node_props.get(s.prop, "int32"))
        full = f"{s.prop}_full"
        new = em.uid("new")
        wexp = (lambda w: f" + {w}" if weighted else "")
        push, pull = em.uid("push"), em.uid("pull")
        if sched.direction != "pull":
            em.w(f"{push} = lambda _fr: jnp.minimum({s.prop}, "
                 f"rtd.combine_scatter_min(N_PAD, edst, "
                 f"jnp.where(evalid & _fr[esrc], {full}[esrc]{wexp('ew')}, "
                 f"rt.inf_for({jdt})), {jdt})[own_ids])")
        if sched.direction != "push":
            em.w(f"{pull} = lambda _fr: jnp.minimum({s.prop}, "
                 f"rt.segment_min(jnp.where(ivalid & _fr[isrc], "
                 f"{full}[isrc]{wexp('iw')}, rt.inf_for({jdt})), "
                 f"idst_local, B, sorted_ids=False))")
        if sched.direction == "push":
            em.w(f"{new} = {push}({fr})")
        elif sched.direction == "pull":
            em.w(f"{new} = {pull}({fr})")
        else:
            em.w(f"{new} = jax.lax.cond(rtd.dist_should_push({fr}, "
                 f"{sched.push_threshold_frac!r}), {push}, {pull}, {fr})")
        return new

    def s_IMinMaxUpdate(self, s: I.IMinMaxUpdate, ctx):
        em = self.em
        if self.batch is not None:
            raise CodegenError("Min/Max construct inside a batched source "
                               "loop (falls back to the sequential lowering)")
        ectx = self._edge_ctx(ctx)
        if ectx is None:
            raise CodegenError("Min/Max update outside a neighbor loop")
        p = self.wtarget(s.prop)
        dtype = self.f.node_props.get(s.prop, "int32")
        jdt = self.jdt(dtype)
        hyb = self._dist_hybrid(s, ectx)
        if hyb is not None:
            fr, weighted = hyb
            new = self._emit_relax_hybrid_dist(s, fr, weighted)
            upd = em.uid("upd")
            em.w(f"{upd} = {new} < {s.prop}")
            em.w(f"{p} = {new}" if p == s.prop
                 else f"{p} = jnp.where({upd}, {new}, {p})")
            for eprop, _etgt, eval_ in s.extras:
                ep = self.wtarget(eprop)
                ev = self.ex.expr(eval_, HostCtx())
                em.w(f"{ep} = jnp.where({upd}, {ev}, {ep})")
            return
        cand = self.ex.expr(s.cand, ctx)
        cv = em.uid("cand")
        ident = f"rt.inf_for({jdt})" if s.kind == "Min" else f"-rt.inf_for({jdt})"
        em.w(f"{cv} = jnp.where({ectx.mask}, {cand}, {ident})" if ectx.mask
             else f"{cv} = {cand}")
        new = em.uid("new")
        if s.target == ectx.it:
            # push: local scatter + one global combine = §4.2 aggregation
            fn = "rtd.combine_scatter_min" if s.kind == "Min" else "rtd.combine_scatter_max"
            comb = em.uid("comb")
            em.w(f"{comb} = {fn}(N_PAD, {ectx.nid}, {cv}, {jdt})")
            mm = "jnp.minimum" if s.kind == "Min" else "jnp.maximum"
            em.w(f"{new} = {mm}({s.prop}, {comb}[own_ids])")
        elif s.target == ectx.source:
            # pull: purely local segment reduction over owned in-edges
            fn = "rt.segment_min" if s.kind == "Min" else "rt.segment_max"
            mm = "jnp.minimum" if s.kind == "Min" else "jnp.maximum"
            em.w(f"{new} = {mm}({s.prop}, {fn}({cv}, {ectx.seg}, B, sorted_ids=False))")
        else:
            raise CodegenError(f"Min/Max target {s.target} not an endpoint")
        upd = em.uid("upd")
        cmp = "<" if s.kind == "Min" else ">"
        em.w(f"{upd} = {new} {cmp} {s.prop}")
        em.w(f"{p} = {new}" if p == s.prop else f"{p} = jnp.where({upd}, {new}, {p})")
        for eprop, _etgt, eval_ in s.extras:
            ep = self.wtarget(eprop)
            ev = self.ex.expr(eval_, HostCtx())
            em.w(f"{ep} = jnp.where({upd}, {ev}, {ep})")

    def _batched_assign_prop(self, s: I.IAssignProp, ectx, vctx, p: str, e: str):
        """Property write inside a batched distributed source region. Edge
        contexts need the distributed combines ([S, E] candidates scattered
        by global ids and psum'd across shards); everything vertex-level
        reuses the local batched lowering (pure block ops)."""
        em = self.em
        b = self.batch
        if ectx is not None:
            if s.reduce_op is None:
                raise CodegenError(
                    f"unsynchronized per-edge write to {s.prop}")
            if s.reduce_op != "+":
                raise CodegenError(f"unsupported edge reduction {s.reduce_op}")
            if s.prop not in b.arrays:
                raise CodegenError(
                    "write to a shared property from an edge context in a "
                    "batched distributed source loop")
            masked = f"jnp.where({ectx.mask}, {e}, 0)" if ectx.mask else e
            if s.target == ectx.source:
                # pull: local batched segment reduction over owned edges
                em.w(f"{p} = {p} + rt.segment_sum_batch("
                     f"jnp.broadcast_to(jnp.asarray({masked}), ({b.size},) + {ectx.seg}.shape), "
                     f"{ectx.seg}, B, sorted_ids=False)")
            else:
                # push: one [S, N_PAD] scatter-add + psum serves all lanes
                dtype = self.jdt(self.f.node_props.get(s.prop, "float32"))
                em.w(f"{p} = {p} + rtd.combine_scatter_add_rows(N_PAD, {ectx.nid}, "
                     f"jnp.broadcast_to(jnp.asarray({masked}), ({b.size},) + {ectx.nid}.shape), "
                     f"{dtype})[:, own_ids]")
            return
        super()._batched_assign_prop(s, ectx, vctx, p, e)

    def s_IAssignProp(self, s: I.IAssignProp, ctx):
        em = self.em
        ectx = self._edge_ctx(ctx)
        vctx = self._vertex_ctx(ctx)
        p = self.wtarget(s.prop)
        e = self.ex.expr(s.expr, ctx)
        if self.batch is not None:
            return self._batched_assign_prop(s, ectx, vctx, p, e)
        if ectx is not None:
            if s.reduce_op is None:
                raise CodegenError(f"unsynchronized per-edge write to {s.prop}")
            if s.reduce_op != "+":
                raise CodegenError(f"unsupported edge reduction {s.reduce_op}")
            masked = f"jnp.where({ectx.mask}, {e}, 0)" if ectx.mask else e
            dtype = self.jdt(self.f.node_props.get(s.prop, "float32"))
            if s.target == ectx.source:
                em.w(f"{p} = {p} + rt.segment_sum({masked}, {ectx.seg}, B, sorted_ids=False)")
            else:
                em.w(f"{p} = {p} + rtd.combine_scatter_add(N_PAD, {ectx.nid}, {masked}, {dtype})[own_ids]")
            return
        super().s_IAssignProp(s, ctx)   # vertex-level path works on blocks

    def s_IAssign(self, s: I.IAssign, ctx):
        # host-scalar reductions from parallel regions need a global combine;
        # per-source lane scalars (sequential set-loop fallback) too — each
        # shard only sums its own block, and the enclosing while trip count
        # must stay shard-uniform
        if s.reduce_op is not None and \
                (not s.vertex_local or s.name in self.lane_scalars) and \
                (self._vertex_ctx(ctx) is not None or self._edge_ctx(ctx) is not None):
            if self.batch is not None:
                raise CodegenError("host-scalar reduction inside a batched "
                                   "distributed source loop")
            em = self.em
            e = self.ex.expr(s.expr, ctx)
            dt = self.dtype_of(s.name)
            ectx = self._edge_ctx(ctx)
            vctx = self._vertex_ctx(ctx)
            mask = ectx.mask if ectx is not None else (vctx.mask if vctx else None)
            masked = f"jnp.where({mask}, {e}, 0)" if mask else e
            op = {"+": "+"}.get(s.reduce_op)
            if op is None:
                raise CodegenError(f"unsupported global reduction {s.reduce_op}")
            body = f"{s.name} {op} rtd.psum(jnp.sum({masked}))"
            em.w(f"{s.name} = jnp.asarray({body}, {self.jdt(dt)})" if dt else
                 f"{s.name} = {body}")
            return
        super().s_IAssign(s, ctx)

    # ------------------------------------------------------------------ BSP loops
    def s_IFixedPoint(self, s: I.IFixedPoint, ctx):
        prev_within = self._delta_within
        try:
            with self._bsp_loop_fulls(s.body):
                super().s_IFixedPoint(s, ctx)
        finally:
            self._delta_within = prev_within

    def s_IDoWhile(self, s: I.IDoWhile, ctx):
        with self._bsp_loop_fulls(s.body):
            super().s_IDoWhile(s, ctx)

    def s_IWhile(self, s: I.IWhile, ctx):
        with self._bsp_loop_fulls(s.body):
            super().s_IWhile(s, ctx)

    # ------------------------------------------------------------------ BFS
    def s_IBFS(self, s: I.IBFS, ctx):
        em = self.em
        sched = self.schedule
        root = self.ex.expr(s.root, ctx)
        lvl = em.uid("level")
        dep = em.uid("depth")
        ge = em.uid("ge")
        kw = (f"frontier={sched.dist_frontier!r}, "
              f"gather_frac={sched.dist_gather_frac!r}, "
              f"direction={sched.direction!r}, "
              f"threshold_frac={sched.push_threshold_frac!r}")
        if self.batch is not None:
            if root != self.batch.srcs2d:
                raise CodegenError("batched iterateInBFS root must be the "
                                   "set iterator")
            em.w(f"{lvl}, {dep}, {ge} = rtd.bfs_levels_1d_batch(esrc, edst, "
                 f"evalid, isrc, idst_local, ivalid, own_ids, "
                 f"{self.batch.srcs}, N_PAD, {kw})")
            self.batch.arrays.add(lvl)
        else:
            em.w(f"{lvl}, {dep}, {ge} = rtd.bfs_levels_1d(esrc, edst, evalid, "
                 f"isrc, idst_local, ivalid, own_ids, {root}, N_PAD, {kw})")
        em.w(f"_gather_elems = _gather_elems + {ge}")
        lvlf = f"{lvl}_full"
        em.w(f"{lvlf} = {'rtd.gather_rows' if self.batch is not None else 'rtd.gather'}({lvl})")
        em.w(f"_gather_elems = _gather_elems + {lvlf}.size")
        if self.batch is not None:
            self.batch.arrays.add(lvlf)
        # forward pass: level-synchronous over the BFS DAG
        with self._bsp_loop_fulls(s.body):
            carry = self.carries(s.body)
            pack = ", ".join(carry)
            n = em.uid("bfsf")
            em.w(f"def {n}(_l, _carry):")
            with em.block():
                em.w(f"({pack},) = _carry" if len(carry) == 1 else f"({pack}) = _carry")
                self.emit_gathers(s.body)
                bctx = BFSCtx(it=s.it, level=lvlf, cur="_l", mask=None, parent=ctx)
                bctx.mask_full = None
                self.body(s.body, bctx)
                em.w(f"return ({pack},)" if len(carry) == 1 else f"return ({pack})")
            em.w(f"_carry = jax.lax.fori_loop(0, {dep} - 1, {n}, ({pack}{',' if len(carry) == 1 else ''}))")
            em.w(f"({pack},) = _carry" if len(carry) == 1 else f"({pack}) = _carry")
        if s.rev_body is None:
            return
        # reverse pass: levels from deepest-1 down to 0
        with self._bsp_loop_fulls(s.rev_body):
            carry = self.carries(s.rev_body)
            pack = ", ".join(carry)
            n = em.uid("bfsr")
            em.w(f"def {n}(_k, _carry):")
            with em.block():
                em.w(f"({pack},) = _carry" if len(carry) == 1 else f"({pack}) = _carry")
                em.w(f"_l = {dep} - 2 - _k")
                self.emit_gathers(s.rev_body)
                vmf = em.uid("vmf")
                em.w(f"{vmf} = ({lvlf} == _l)")
                bctx = BFSCtx(it=s.it, level=lvlf, cur="_l", mask=None, parent=ctx)
                if s.rev_filter is not None:
                    self.ex.full_mode = True
                    try:
                        em.w(f"{vmf} = {vmf} & ({self.ex.expr(s.rev_filter, bctx)})")
                    finally:
                        self.ex.full_mode = False
                vm = em.uid("vm")
                if self.batch is not None:
                    self.batch.arrays.add(vmf)
                    em.w(f"{vm} = {vmf}[:, own_ids]")
                    self.batch.arrays.add(vm)
                else:
                    em.w(f"{vm} = {vmf}[own_ids]")
                bctx.mask = vm
                bctx.mask_full = vmf
                self.body(s.rev_body, bctx)
                em.w(f"return ({pack},)" if len(carry) == 1 else f"return ({pack})")
            em.w(f"_carry = jax.lax.fori_loop(0, {dep} - 1, {n}, ({pack}{',' if len(carry) == 1 else ''}))")
            em.w(f"({pack},) = _carry" if len(carry) == 1 else f"({pack}) = _carry")

    # ------------------------------------------------------------------ wedge
    def _try_wedge(self, s: I.INbrLoop, ctx) -> bool:
        inner = s.body[0] if len(s.body) == 1 and isinstance(s.body[0], I.INbrLoop) else None
        if inner is None or inner.source != s.source or s.direction != "out" \
                or inner.direction != "out":
            return False
        iff = inner.body[0] if len(inner.body) == 1 and isinstance(inner.body[0], I.IIf) else None
        if iff is None or not isinstance(iff.cond, I.ICall) or iff.cond.fn != "is_an_edge":
            raise CodegenError("unsupported nested neighbor loop pattern")
        red = iff.then[0] if len(iff.then) == 1 and isinstance(iff.then[0], I.IAssign) else None
        if red is None or red.reduce_op != "+":
            raise CodegenError("wedge body must be a count reduction")
        if self.batch is not None:
            raise CodegenError("wedge pattern inside a batched source loop")
        self.needs_ell = self.needs_edge_key = True
        dt = self.dtype_of(red.name)
        acc = (f"{red.name} + rtd.wedge_count_1d(ell_cols, own_ids, "
               f"{self.f.graph_param}['edge_key_rep'], n_true) * "
               f"({self.ex.expr(red.expr, HostCtx())})")
        self.em.w(f"{red.name} = jnp.asarray({acc}, {self.jdt(dt)})" if dt else
                  f"{red.name} = {acc}")
        return True


def generate_distributed(irfn: I.IRFunction, schedule=None, **opts):
    """Emit the distributed-backend source under `schedule`. The BSP
    lowering consumes `dist_frontier`/`dist_gather_frac` (exchange policy),
    `direction`/`push_threshold_frac` (relax/BFS direction), and
    `batch_sources` (source-set lanes) — all baked in as literals, so the
    same schedule yields byte-identical source."""
    cg = DistCodegen(irfn, schedule=schedule)
    body = cg.generate()
    from .. import runtime_dist as rtd
    meta = {
        "out_props": [v for v in cg.declared if v in irfn.node_props],
        "out_scalars": [v for v in cg.declared if v not in irfn.node_props],
        "needs_ell": cg.needs_ell,
        "needs_edge_key": cg.needs_edge_key,
    }
    return body, {"rtd": rtd, "__dist_meta__": meta}
