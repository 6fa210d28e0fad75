"""Pallas backend — the paper's CUDA code generator, rethought for TPU.

The CUDA backend turns each outermost `forall` into a kernel launch with
thread-per-vertex + atomics (paper §3.2). TPU has no SIMT threads and no
atomics, so this backend restructures the two hot patterns into blocked
dense Pallas kernels (see kernels/ell_spmv), now over the degree-bucketed
sliced-ELL view with frontier-aware direction optimization:

  * Min/Max edge relaxation  → per-bucket min-plus SpMV over the REVERSE
    (in-edge) sliced-ELL view, masked to the current frontier, with an
    on-device switch to scatter-push over the CSR out-edges when the
    frontier is sparse (Beamer-style direction optimization). The frontier
    is the fixedPoint convergence property, threaded through the generated
    while_loop carry; each relax recomputes it from the update mask. Pull
    from non-frontier sources cannot change the result (relaxation is
    monotone-idempotent), so push and pull branches agree exactly.
  * neighborhood sum reductions (PR) → per-bucket (+,×) SpMV of a per-node
    contribution vector (plus the COO hub fallback inside the op).

Everything else (BFS, scalar reductions, fixed point) inherits the local
backend's vectorized lowering — those are memory-bound scatter/gathers XLA
already fuses well; the kernels own the compute-dense inner loops.
"""
from __future__ import annotations

from .. import ir as I
from .base import HostCtx, VertexCtx, only_reads_side
from .local_jax import LocalCodegen, has_refresh_variant


class PallasCodegen(LocalCodegen):
    backend_name = "pallas"
    # the kernel op already takes an arbitrary frontier mask, so a delta-
    # stepping fixedPoint relaxes its bucketed window through the same
    # sliced-ELL kernels — no separate `_dell` padded view needed
    supports_delta_ell = False

    def _block_rows_literal(self) -> str:
        """`Schedule.block_rows` as a source literal for the kernel ops.

        A uniform int cap stays an int; per-bucket caps are emitted as a
        {bucket_width: cap} mapping (width-keyed, because empty buckets are
        dropped from a graph's sliced view, so positional caps would drift
        per graph)."""
        s = self.schedule
        if isinstance(s.block_rows, int):
            return repr(s.block_rows)
        return repr(dict(zip(s.bucket_widths(), s.bucket_block_rows())))

    def _kernel_kwargs(self) -> str:
        """Literal kwargs for kops calls: engine knobs + kernel block caps."""
        return f"{self._engine_kwargs()}, block_rows={self._block_rows_literal()}"

    def _sig_head(self, args):
        # the bound sliced-ELL view is a required positional (the bind/api
        # layer resolves it from the GraphContext per call)
        return [args[0], "_ell"]

    # ---- hot pattern 1: frontier relax → sliced-ELL hybrid kernel ------------
    def emit_relax_hybrid(self, s: I.IMinMaxUpdate, frontier,
                          weighted: bool = True):
        """Same pattern the local backend detects, with the pull branch
        lowered to the kernel op: per-bucket pull kernels over the reverse
        sliced-ELL view, or scatter-push over the CSR edge arrays when the
        frontier is sparse (the inherited on-device occupancy switch, with
        the compiled schedule's threshold/direction baked in as literals).
        Under delta-stepping the frontier arriving here is already the
        bucketed window, so the same lowering applies unchanged. A dense
        sweep (no frontier) is one kernel pull. The unweighted relax (CC)
        keeps the inherited inline jnp lowering — the min-plus kernels are
        weighted."""
        if frontier is not None or not weighted:
            return super().emit_relax_hybrid(s, frontier, weighted)
        new = self.em.uid("new")
        self.em.w(f"{new} = kops.relax_minplus(_ell, {s.prop}, frontier=None, "
                  f"csr={self.f.graph_param}{self._kernel_kwargs()})")
        self._count_relax(None, None, "_ell.padded_cells()")
        return new

    def _relax_pull_expr(self, frontier: str, weighted: bool) -> str:
        if not weighted:
            return super()._relax_pull_expr(frontier, weighted)
        return (f"kops.relax_minplus(_ell, _d, frontier={frontier}, "
                f"csr={self.f.graph_param}, direction='pull', "
                f"block_rows={self._block_rows_literal()})")

    def _relax_swept(self, weighted: bool):
        push, pull = super()._relax_swept(weighted)
        return push, ("_ell.padded_cells()" if weighted else pull)

    # ---- hot pattern 2: neighborhood sum → sliced-ELL (+,×) kernel -----------
    def s_IAssign(self, s: I.IAssign, ctx):
        ectx = self._edge_ctx(ctx)
        # the gather kernel produces one [N] vector: batched ([B, N]) regions
        # and per-source lane scalars keep the inherited segment lowering
        if (s.reduce_op == "+" and s.vertex_local and ectx is not None
                and ectx.direction == "in" and ectx.mask is None
                and self.batch is None and s.name not in self.lane_scalars
                and only_reads_side(s.expr, ectx.it)):
            em = self.em
            contrib = em.uid("contrib")
            # evaluate the per-edge term as a per-NODE vector (nbr ↦ node)
            vctx = VertexCtx(it=ectx.it, mask=None, parent=HostCtx())
            em.w(f"{contrib} = {self.ex.expr(s.expr, vctx)}")
            em.w(f"{contrib} = jnp.asarray({contrib}, jnp.float32) * jnp.ones((N,), jnp.float32)")
            em.w(f"{s.name} = {s.name} + kops.gather_plustimes(_ell, "
                 f"{contrib}, block_rows={self._block_rows_literal()})")
            return
        super().s_IAssign(s, ctx)


def generate_pallas(irfn: I.IRFunction, schedule=None, batch_sources=None,
                    **opts):
    cg = PallasCodegen(irfn, schedule=schedule, batch_sources=batch_sources)
    body = cg.generate()
    if has_refresh_variant(irfn):
        rcg = PallasCodegen(irfn, schedule=schedule,
                            batch_sources=batch_sources)
        rcg.refresh_variant = True
        body = body + "\n\n" + rcg.generate()
    from ...kernels.ell_spmv import ops as kops
    return body, {"kops": kops}
