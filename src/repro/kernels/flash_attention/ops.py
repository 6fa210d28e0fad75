"""Model-facing attention op: GQA head handling + (B, H, S, D) layout glue."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import flash_attention
from .ref import attention_ref


@partial(jax.jit, static_argnames=("causal", "use_kernel"))
def gqa_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True, use_kernel: bool = True) -> jax.Array:
    """q: [B, Hq, S, D]; k/v: [B, Hkv, Skv, D] with Hq % Hkv == 0."""
    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    qf = q.reshape(b * hq, sq, d)
    kf = k.reshape(b * hq, -1, d)
    vf = v.reshape(b * hq, -1, d)
    if use_kernel and sq >= 8:
        o = flash_attention(qf, kf, vf, causal=causal,
                            interpret=jax.default_backend() != "tpu")
    else:
        o = attention_ref(qf, kf, vf, causal=causal)
    return o.reshape(b, hq, sq, d)
