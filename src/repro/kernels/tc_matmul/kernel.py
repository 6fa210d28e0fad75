"""Triangle counting as masked blocked matmul — the MXU-native rewrite of the
paper's Fig. 20 doubly-nested loop.

GraphBLAS identity: with L = strict lower-triangular adjacency of the
undirected closure, triangles = sum( (L @ L) ⊙ L ). The paper's CUDA
backend walks neighbor lists per thread; the TPU has a 128×128 systolic
array instead of independent threads, so we feed it dense tiles:

  grid (I, J, K) over [N/B]³ tiles; A_ik @ A_kj accumulates into a VMEM
  scratch; on the last K step the tile of C is masked by A_ij and reduced
  into a per-(I,J) partial count, written to element [0, 0] of that
  (I, J)'s own (8, 128) output tile — the smallest block Mosaic tiles.

Dense N² is the price of MXU regularity — viable for the per-device vertex
blocks the distributed layer produces (B_block ≤ a few thousand), which is
exactly how CombBLAS-style systems do it at scale.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_OUT_TILE = (8, 128)


def _tc_body(a_ik_ref, a_kj_ref, a_ij_ref, out_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ik_ref[...], a_kj_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _final():
        count = jnp.sum(acc_ref[...] * a_ij_ref[...])
        first = ((jax.lax.broadcasted_iota(jnp.int32, _OUT_TILE, 0) == 0)
                 & (jax.lax.broadcasted_iota(jnp.int32, _OUT_TILE, 1) == 0))
        out_ref[...] = jnp.where(first, count, 0.0)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def tc_matmul(lower: jax.Array, *, block: int = 128,
              interpret: bool = True) -> jax.Array:
    """lower: [N, N] float32 strict lower-triangular adjacency (N % block == 0).
    Returns the triangle count as a float32 scalar."""
    n = lower.shape[0]
    assert n % block == 0 and lower.shape == (n, n)
    nb = n // block
    partials = pl.pallas_call(
        functools.partial(_tc_body, n_k=nb),
        grid=(nb, nb, nb),
        in_specs=[
            pl.BlockSpec((block, block), lambda i, j, k: (i, k)),   # A_ik
            pl.BlockSpec((block, block), lambda i, j, k: (k, j)),   # A_kj
            pl.BlockSpec((block, block), lambda i, j, k: (i, j)),   # mask A_ij
        ],
        out_specs=pl.BlockSpec(_OUT_TILE, lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nb * _OUT_TILE[0], nb * _OUT_TILE[1]),
                                       jnp.float32),
        scratch_shapes=[pltpu.VMEM((block, block), jnp.float32)],
        interpret=interpret,
    )(lower, lower, lower)
    return jnp.sum(partials)
