"""Padded-tail edge cases of the 1-D distributed partition.

The last block is padded (paper §4.2 "we pad temporary vertices for the
last process"); with small N whole shards own nothing but padding. These
tests pin that the compact/gather exchange paths never let padded slots
influence results: unit tests seed the padding with poison values and
assert it stays inert, and end-to-end runs cover N % P != 0, N < P, a
shard owning only padding, and isolated vertices — on both the dense and
the frontier-compressed exchange.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import Schedule, compile_bundled, dist, runtime_dist as rtd
from repro.graph import from_edges, uniform_random
from repro.graph.algorithms_ref import sssp_ref

POLICIES = ["dense", "compact", "auto"]


def _sssp_dist(g, shards, policy):
    prog = compile_bundled("sssp", backend="distributed",
                           schedule=Schedule(dist_frontier=policy))
    return np.asarray(
        prog.bind(g, mesh=dist.make_mesh_1d(shards))(src=0)["dist"])


@pytest.mark.parametrize("policy", POLICIES)
def test_n_not_divisible_by_shards(eight_devices, policy):
    g = uniform_random(101, 5, seed=2)            # 101 % 8 = 5
    assert np.array_equal(_sssp_dist(g, 8, policy),
                          sssp_ref(g, 0).astype(np.int32))


@pytest.mark.parametrize("policy", POLICIES)
def test_shards_owning_only_padding(eight_devices, policy):
    # N=9, P=8: block=2, shards 5..7 own nothing but padding
    g = uniform_random(9, 3, seed=5)
    assert np.array_equal(_sssp_dist(g, 8, policy),
                          sssp_ref(g, 0).astype(np.int32))


@pytest.mark.parametrize("policy", POLICIES)
def test_n_smaller_than_shard_count(eight_devices, policy):
    g = uniform_random(5, 2, seed=7)              # N=5 < P=8, block=1
    assert np.array_equal(_sssp_dist(g, 8, policy),
                          sssp_ref(g, 0).astype(np.int32))


@pytest.mark.parametrize("policy", POLICIES)
def test_isolated_vertices(eight_devices, policy):
    # vertices 7..9 have no edges at all; 0..6 form a weighted path
    src = np.arange(6)
    dst = np.arange(1, 7)
    w = np.arange(1, 7)
    g = from_edges(10, src, dst, w)
    out = _sssp_dist(g, 8, policy)
    ref = sssp_ref(g, 0).astype(np.int32)
    assert np.array_equal(out, ref)
    assert (out[7:] == ref[7:]).all() and (ref[7:] == ref[7]).all()  # all INF


# --------------------------------------------------------------------------
# poison: padding slots must pass through the exchange untouched
# --------------------------------------------------------------------------

POISON = np.int32(-777777)


def _run_exchange(full_prev, blk, own_ids, mesh, frac, skip_empty):
    def body(fp, b, o):
        return rtd.exchange(fp, b[0], o[0], frac, skip_empty=skip_empty)
    fn = jax.jit(jax.shard_map(body, mesh=mesh,
                               in_specs=(P(), P("data"), P("data")),
                               out_specs=(P(), P()), check_vma=False))
    return fn(full_prev, blk, own_ids)


@pytest.mark.parametrize("frac,skip", [(0.25, True), (0.25, False),
                                       (1.0, True)])
def test_exchange_never_reads_poisoned_padding(eight_devices, frac, skip):
    """Seed the padded tail (slots >= n_true) of both the carried full view
    and the owning blocks with poison. Initialized-but-never-written
    padding never differs between block and full view, so the compact
    selection must not transmit it: after an exchange that moves real
    changes, the true slots are exact and every poison slot is bit-equal
    untouched."""
    p, block, n_true = 8, 4, 27                   # n_pad=32, 5 poison slots
    n_pad = p * block
    own_ids = jnp.arange(n_pad, dtype=jnp.int32).reshape(p, block)
    rng = np.random.default_rng(3)
    full = rng.integers(0, 100, n_pad).astype(np.int32)
    full[n_true:] = POISON
    blk = full.reshape(p, block).copy()
    # real changes on three different shards (true slots only)
    blk[0, 1] = 41
    blk[3, 2] = 42
    blk[6, 1] = 43
    mesh = dist.make_mesh_1d(p)
    out, elems = _run_exchange(jnp.asarray(full), jnp.asarray(blk),
                               own_ids, mesh, frac, skip)
    out = np.asarray(out)
    assert np.array_equal(out[:n_true], blk.reshape(-1)[:n_true])
    assert (out[n_true:] == POISON).all(), "padding was rewritten"
    assert int(elems) > 0


def test_exchange_skips_when_nothing_changed(eight_devices):
    p, block = 8, 4
    n_pad = p * block
    own_ids = jnp.arange(n_pad, dtype=jnp.int32).reshape(p, block)
    full = jnp.asarray(np.full(n_pad, POISON, np.int32))
    blk = full.reshape(p, block)
    mesh = dist.make_mesh_1d(p)
    out, elems = _run_exchange(full, blk, own_ids, mesh, 0.25, True)
    assert int(elems) == 0
    assert np.array_equal(np.asarray(out), np.asarray(full))


def test_exchange_dense_fallback_on_overflow(eight_devices):
    """When a shard's change count overflows the compact buffer the
    exchange must fall back to the dense gather (correctness over
    volume) — and report the dense element count."""
    p, block = 8, 8
    n_pad = p * block
    own_ids = jnp.arange(n_pad, dtype=jnp.int32).reshape(p, block)
    full = jnp.zeros(n_pad, jnp.int32)
    blk = jnp.arange(1, n_pad + 1, dtype=jnp.int32).reshape(p, block)  # all change
    mesh = dist.make_mesh_1d(p)
    out, elems = _run_exchange(full, blk, own_ids, mesh, 0.25, True)
    assert int(elems) == n_pad
    assert np.array_equal(np.asarray(out), np.asarray(blk).reshape(-1))


@pytest.mark.parametrize("policy", POLICIES)
def test_batched_bc_on_padded_tail(eight_devices, policy):
    """Batched source lanes ([S, B] blocks) across a padded tail: BC over
    a source set on N=9 / P=8 agrees with the local backend."""
    from repro.graph.algorithms_ref import bc_ref
    g = uniform_random(9, 3, seed=5)
    srcs = np.array([0, 3, 7], np.int32)
    prog = compile_bundled("bc", backend="distributed",
                           schedule=Schedule(dist_frontier=policy))
    out = prog.bind(g, mesh=dist.make_mesh_1d(8))(sourceSet=srcs)["BC"]
    np.testing.assert_allclose(np.asarray(out), bc_ref(g, srcs.tolist()),
                               atol=1e-3)
