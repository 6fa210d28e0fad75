"""Jitted wrappers: graph-level relax/gather ops on the ELL kernels.

These are what the DSL's Pallas backend emits calls to. They own the
padding/layout glue (sentinel slot, row-block padding, degree buckets) so
the kernels themselves stay rectangular.

Two layouts coexist:

  * dense ELL (`prepare_ell` → cols/wts arrays): the original single
    `[N, max_deg]` view — kept for the kernel unit tests and as the
    benchmark baseline;
  * sliced ELL (`prepare_sliced_ell` → `SlicedEllGraph`): degree-bucketed
    tiles + a COO hub fallback — the frontier-aware engine's layout.
    `relax_minplus` / `gather_plustimes` dispatch on the first argument.

Off the TPU the sliced ops run an equivalent pure-jnp path instead of
interpret-mode Pallas: identical math, without the interpreter overhead
(the kernels proper are still exercised by tests/test_kernels.py). On a
TPU they call the kernel, which Mosaic refuses (`TPU_REFUSAL`); so
`compile_program(..., backend="pallas")` refuses there up front rather
than run the jnp path under the pallas name. Both decisions are made per
call, from `jax.default_backend()`, never at import.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ...graph.csr import (CSRGraph, INF_I32, SlicedEllGraph, to_ell,
                          to_sliced_ell)
from .kernel import _best_block, ell_spmv

# Mosaic's answers when `ell_spmv` is compiled for a TPU v5e (JAX 0.9.0);
# tests/test_chip_compile.py checks they are still what it says.
MOSAIC_ERRORS = ("Only 2D gather is supported",                 # x: [N+1]
                 "Shape mismatch in input, indices and output")  # x: [N+1, B]
TPU_REFUSAL = (
    "the pallas backend does not run on a TPU: Mosaic refuses the ell_spmv "
    f"kernel's gather of x ({MOSAIC_ERRORS[0]!r} for an [N+1] x, "
    f"{MOSAIC_ERRORS[1]!r} for an [N+1, B] x), and the kernel keeps all of x "
    "in VMEM, which a 32-lane x of 2^21 nodes (268 MB) outgrows; compile "
    "with backend='local'")


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _use_kernel() -> bool:
    """The bucket ops call the Pallas kernel on a TPU only; elsewhere they
    run the same math in jnp."""
    return not _interpret()

INF = jnp.int32(INF_I32)


def _pad_rows(a, block):
    n = a.shape[0]
    pad = (-n) % block
    if pad == 0:
        return a
    fill = jnp.full((pad,) + a.shape[1:], a.dtype.type(0) if a.ndim == 1 else 0, a.dtype)
    return jnp.concatenate([a, fill], axis=0)


def prepare_ell(g: CSRGraph, *, reverse: bool = False, block_rows: int = 256):
    """Host-side: build the padded dense-ELL arrays once per graph.

    Returns (cols, wts, n_rows_padded). cols pad slots point at the sentinel
    row (index n); wts pad slots are INF (masked out by the semiring)."""
    ell = to_ell(g, reverse=reverse)
    n = g.num_nodes
    cols = np.asarray(ell.cols).copy()
    wts = np.asarray(ell.wts)
    block = min(block_rows, -(-n // 8) * 8)   # 8-aligned, capped at block_rows
    pad = (-n) % block
    n_pad = n + pad
    cols[cols == n] = n_pad                   # sentinel = last slot of padded x
    if pad:
        cols = np.concatenate([cols, np.full((pad, cols.shape[1]), n_pad, np.int32)])
        wts = np.concatenate([wts, np.full((pad, wts.shape[1]), int(INF_I32), np.int32)])
    return jnp.asarray(cols), jnp.asarray(wts), block


def prepare_sliced_ell(g: CSRGraph, *, reverse: bool = True, schedule=None,
                       **knobs) -> SlicedEllGraph:
    """Host-side: degree-bucketed view for the frontier-aware engine.
    Default orientation is reverse (in-edges) — the pull layout. The bucket
    layout comes from `schedule` (a `repro.schedule.Schedule`). Prefer
    `repro.core.context.GraphContext.sliced_ell`, which memoizes this per
    (graph, layout)."""
    return to_sliced_ell(g, reverse=reverse, schedule=schedule, **knobs)


# --------------------------------------------------------------------------
# dense-ELL ops (baseline layout)
# --------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("block_rows",))
def _relax_dense(cols, wts, dist, *, block_rows: int = 256):
    """One dense SSSP relax sweep over the single-width ELL view."""
    n = dist.shape[0]
    n_pad = cols.shape[0]
    block_rows = min(block_rows, n_pad)   # prepare_ell guarantees divisibility
    # padded slots + the sentinel hold 0 — never read as real neighbors,
    # and 0 keeps INF(pad weight) + x from overflowing int32.
    x = jnp.zeros((n_pad + 1,), dist.dtype).at[:n].set(dist)
    y = ell_spmv(cols, wts, x, semiring="minplus",
                 block_rows=block_rows, interpret=_interpret())
    return jnp.minimum(dist, y[:n])


@partial(jax.jit, static_argnames=("block_rows",))
def _gather_dense(cols, contrib, *, block_rows: int = 256):
    n = contrib.shape[0]
    n_pad = cols.shape[0]
    block_rows = min(block_rows, n_pad)
    ones = jnp.where(cols == n_pad, 0.0, 1.0).astype(contrib.dtype)
    x = jnp.zeros((n_pad + 1,), contrib.dtype).at[:n].set(contrib)
    y = ell_spmv(cols, ones, x, semiring="plustimes",
                 block_rows=block_rows, interpret=_interpret())
    return y


# --------------------------------------------------------------------------
# sliced-ELL ops (frontier-aware engine)
# --------------------------------------------------------------------------

def _bucket_caps(ell: SlicedEllGraph, block_rows):
    """Per-kept-bucket kernel row-block caps from `Schedule.block_rows`.

    `block_rows` is an int (uniform cap), a {bucket_width: cap} mapping
    (the pallas codegen's literal form — keyed by width because empty
    buckets are dropped from the sliced view, so positional indexing would
    drift), or None (default cap)."""
    if block_rows is None:
        return [256] * len(ell.cols)
    if isinstance(block_rows, dict):
        return [int(block_rows.get(w, 256)) for w in ell.widths]
    return [int(block_rows)] * len(ell.cols)


def _bucket_minplus(cols, wts, x, cap: int = 256):
    """x: [M] (SpMV) or [M, B] (SpMM, lanes = source batch)."""
    if _use_kernel():
        return ell_spmv(cols, wts, x, semiring="minplus",
                        block_rows=_best_block(cols.shape[0], cap),
                        interpret=_interpret())
    if x.ndim == 2:
        wts = wts[..., None]
    return jnp.min(jnp.take(x, cols, axis=0) + wts, axis=1)


def _bucket_plustimes(cols, x, cap: int = 256):
    if _use_kernel():
        ones = jnp.ones(cols.shape, x.dtype)   # pads hit the 0 sentinel
        return ell_spmv(cols, ones, x, semiring="plustimes",
                        block_rows=_best_block(cols.shape[0], cap),
                        interpret=_interpret())
    return jnp.sum(jnp.take(x, cols, axis=0), axis=1)


def _relax_sliced_pull(ell: SlicedEllGraph, dist, frontier=None,
                       block_rows=None):
    """Masked-pull sweep: per-bucket min-plus kernels + COO hub fallback.
    Frontier masking happens on the gather source (x), so the kernels stay
    unmasked and rectangular. dist may be [N] (one traversal) or [B, N]
    (batched: the gathered operand becomes the [N+1, B] matrix the SpMM
    kernel consumes — batch lanes minor, so every bucket tile is reused
    across all B sources in one pass). This and `_relax_push` are the
    kernel-layer copies of the push/pull relaxation — keep in sync with
    runtime.relax_minplus_hybrid (see the NOTE there)."""
    n = ell.num_nodes
    x = dist if frontier is None else jnp.where(frontier, dist, INF)
    batched = dist.ndim == 2
    if batched:
        # sentinel slot (index n) holds 0 so INF pad weights never overflow
        x_ext = jnp.zeros((n + 1, dist.shape[0]), dist.dtype).at[:n].set(x.T)
        y = jnp.full((n, dist.shape[0]), INF, dist.dtype)
    else:
        x_ext = jnp.zeros((n + 1,), dist.dtype).at[:n].set(x)
        y = jnp.full((n,), INF, dist.dtype)
    for cols, wts, rows, cap in zip(ell.cols, ell.wts, ell.rows,
                                    _bucket_caps(ell, block_rows)):
        y = y.at[rows].min(_bucket_minplus(cols, wts, x_ext, cap), mode="drop")
    if ell.hub_rows.shape[0]:
        hub_w = ell.hub_wts[:, None] if batched else ell.hub_wts
        y = y.at[ell.hub_rows].min(x_ext[ell.hub_cols] + hub_w, mode="drop")
    return jnp.minimum(dist, y.T if batched else y)


def _relax_push(g: CSRGraph, dist, frontier):
    """Scatter-push from the (sparse) frontier over out-edges.
    dist/frontier: [N] or [B, N] (row-wise scatter-min). Integer dist
    gathers once: off-frontier sources read the dtype's maximum, which
    becomes an INF candidate (runtime._frontier_cand's fold)."""
    if jnp.issubdtype(dist.dtype, jnp.integer):
        sent = jnp.iinfo(dist.dtype).max
        src = jnp.where(frontier, dist, sent)[..., g.edge_src]
        cand = jnp.where(src == sent, INF, src + g.weights)
    else:
        cand = jnp.where(frontier[..., g.edge_src],
                         dist[..., g.edge_src] + g.weights, INF)
    return dist.at[..., g.indices].min(cand)


def relax_minplus(cols_or_ell, wts_or_dist, dist=None, *, frontier=None,
                  csr: CSRGraph | None = None, block_rows=256,
                  threshold_frac: float | None = None,
                  direction: str = "auto"):
    """One SSSP relax step.

    Dense form (baseline): `relax_minplus(cols, wts, dist)` — full pull
    sweep over the `[N, max_deg]` reverse-ELL view.

    Sliced form (engine): `relax_minplus(ell, dist, frontier=fr, csr=g)` —
    frontier-masked, direction-optimized: when the frontier occupancy is
    under `threshold_frac · N` (the compiled `Schedule`'s knob; `None`
    falls back to the deprecated `ENGINE` shim) the relax runs push-style
    over the CSR out-edges (scatter-min), otherwise as per-bucket pull
    kernels. `direction="push"|"pull"` pins one branch. Both directions
    compute the identical relaxation, so neither the on-device `lax.cond`
    switch nor a pinned direction ever changes results.

    Batched sliced form: dist/frontier [B, N] — the pull sweep becomes a
    per-bucket min-plus SpMM over the [N+1, B] operand, and the push/pull
    choice is made per batch ROW (homogeneous batches take a single-
    direction fast path; mixed batches run each direction masked to its
    rows, which partition the frontier, so the result is exact).

    `block_rows` caps the kernel row-block per bucket: an int (uniform
    cap), or — sliced form only — a {bucket_width: cap} mapping, the
    literal form `Schedule.block_rows` reaches generated code in."""
    if not isinstance(cols_or_ell, SlicedEllGraph):
        return _relax_dense(cols_or_ell, wts_or_dist, dist,
                            block_rows=int(block_rows))
    if dist is not None:
        raise TypeError(
            "sliced form takes (ell, dist) positionally; pass the frontier "
            "as relax_minplus(ell, dist, frontier=fr, csr=g)")
    ell, dist = cols_or_ell, wts_or_dist
    if frontier is None or csr is None:
        # dense sweep (or no CSR for push): pull is the only orientation
        return _relax_sliced_pull(ell, dist, frontier, block_rows)
    if direction == "push":
        return _relax_push(csr, dist, frontier)
    if direction == "pull":
        return _relax_sliced_pull(ell, dist, frontier, block_rows)
    from ...core.runtime import (_cond_by_rows, frontier_rows_should_push,
                                 frontier_should_push)
    if dist.ndim == 2:
        rows_push = frontier_rows_should_push(frontier, ell.num_nodes,
                                              threshold_frac)
        return _cond_by_rows(
            rows_push,
            lambda d: _relax_push(csr, d, frontier),
            lambda d: _relax_sliced_pull(ell, d, frontier, block_rows),
            lambda d: _relax_sliced_pull(
                ell, _relax_push(csr, d, frontier & rows_push[:, None]),
                frontier & ~rows_push[:, None], block_rows),
            dist)
    return jax.lax.cond(
        frontier_should_push(frontier, ell.num_nodes, threshold_frac),
        lambda d: _relax_push(csr, d, frontier),
        lambda d: _relax_sliced_pull(ell, d, frontier, block_rows),
        dist)


def gather_plustimes(cols_or_ell, contrib, n_out: int = None, *,
                     block_rows=256):
    """PR gather: y[v] = sum_{u in-nbr} contrib[u]; `contrib` already divided
    by out-degree.

    Dense form: `gather_plustimes(cols, contrib)` (returns padded rows).
    Sliced form: `gather_plustimes(ell, contrib)` (returns exactly [N]).
    Batched sliced form: contrib [B, N] → [B, N] (plus-times SpMM, one
    bucket pass shared by all B lanes). `block_rows` caps the per-bucket
    kernel row-block (int, or {bucket_width: cap} in the sliced form)."""
    if not isinstance(cols_or_ell, SlicedEllGraph):
        return _gather_dense(cols_or_ell, contrib, block_rows=int(block_rows))
    ell = cols_or_ell
    n = ell.num_nodes
    batched = contrib.ndim == 2
    if batched:
        x_ext = jnp.zeros((n + 1, contrib.shape[0]),
                          contrib.dtype).at[:n].set(contrib.T)
        y = jnp.zeros((n, contrib.shape[0]), contrib.dtype)
    else:
        x_ext = jnp.zeros((n + 1,), contrib.dtype).at[:n].set(contrib)
        y = jnp.zeros((n,), contrib.dtype)
    for cols, rows, cap in zip(ell.cols, ell.rows,
                               _bucket_caps(ell, block_rows)):
        y = y.at[rows].add(_bucket_plustimes(cols, x_ext, cap), mode="drop")
    if ell.hub_rows.shape[0]:
        y = y.at[ell.hub_rows].add(x_ext[ell.hub_cols], mode="drop")
    return y.T if batched else y
