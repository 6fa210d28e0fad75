"""Seeds and hashed weights shared by the generators."""
import jax.numpy as jnp
import numpy as np


def run_rng(seed: int, stream: str) -> np.random.Generator:
    """A generator for one named use of the run's seed (any whole number)."""
    return np.random.default_rng([seed % 2**64, sum(map(ord, stream))])


def pair_weights(u, v, lo: int, hi: int, salt):
    """Integer weights in [lo, hi], a 32-bit hash (murmur3's finaliser) of
    the unordered pair {u, v} and `salt`: an edge and its reverse, and
    every duplicate, get one weight."""
    a = jnp.minimum(u, v).astype(jnp.uint32)
    b = jnp.maximum(u, v).astype(jnp.uint32)
    x = a * jnp.uint32(0x9E3779B1) ^ (b + jnp.asarray(salt, jnp.uint32)) * jnp.uint32(0x85EBCA77)
    x ^= x >> 16
    x *= jnp.uint32(0x85EBCA6B)
    x ^= x >> 13
    x *= jnp.uint32(0xC2B2AE35)
    x ^= x >> 16
    return (lo + x % jnp.uint32(hi - lo + 1)).astype(jnp.int32)
