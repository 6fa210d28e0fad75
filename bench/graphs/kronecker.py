"""Graph500 Kronecker generator ("Generating the edge list", Graph500
specification), on the device in one jitted call.

Each of the `edgefactor * 2**scale` edges picks one quadrant per bit of its
endpoints, with probabilities A, B, C and D = 1 - A - B - C. The structure
and the weights come from `graph_seed` and are the same in every run; the
run's `seed` draws the vertex relabelling (the specification's random
permutation of labels). Every seed therefore gives the same graph up to
isomorphism, and the same work, in another memory order. JAX's counter-based
generator gives the same edges on every platform.

Weights are integers in [weight_lo, weight_hi], a hash of the unordered
base pair: both directions and every duplicate of an edge carry one weight,
so no graph build can pick a different copy.
"""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from seeds import pair_weights, run_rng


@partial(jax.jit, static_argnames=("scale", "m", "a", "b", "c", "lo", "hi"))
def _edges(key, label, *, scale, m, a, b, c, lo, hi):
    def bit(k, ij):
        r = jax.random.uniform(jax.random.fold_in(key, k), (m,), jnp.float32)
        q = (r >= a).astype(jnp.int32) + (r >= a + b) + (r >= a + b + c)   # quadrant 0-3
        return ij[0] | (q >> 1) << k, ij[1] | (q & 1) << k
    zero = jnp.zeros((m,), jnp.int32)
    i, j = jax.lax.fori_loop(0, scale, bit, (zero, zero))
    return label[i], label[j], pair_weights(i, j, lo, hi, key[-1])


def generate(params: dict, seed: int) -> dict:
    n = 1 << params["scale"]
    label = run_rng(seed, "relabel").permutation(n).astype(np.int32)
    src, dst, w = _edges(jax.random.key_data(jax.random.key(params["graph_seed"])),
                         jnp.asarray(label), scale=params["scale"],
                         m=params["edgefactor"] * n, a=params["A"], b=params["B"],
                         c=params["C"], lo=params["weight_lo"], hi=params["weight_hi"])
    return {"n": n, "src": np.asarray(src), "dst": np.asarray(dst), "w": np.asarray(w),
            "label": label, "undirected": True, "drop_self_loops": True}
