"""Multi-shard agreement matrix: every bundled program, distributed vs
local, across shard counts {1, 2, 4, 8} — in-process, on the 8 forced host
devices the shared conftest sets up.

N is prime (101), so no shard count > 1 divides it: every mesh in the
matrix exercises the padded last block. The distributed runs use the
frontier-compressed "auto" exchange policy (the new path); the dense
baseline is pinned against the same references in test_distributed.py,
and dense-vs-compact equivalence per schedule is covered by the
hypothesis test in test_property.py.
"""
import numpy as np
import pytest

from repro.core import Schedule, compile_bundled, dist

PROGRAMS = ["sssp", "sssp_pull", "pr", "tc", "bc", "cc", "ppr", "lp",
            "kcore"]
SHARDS = [1, 2, 4, 8]

# the distributed schedule under test: compressed exchange + adaptive
# direction — every new knob on at once
DIST_SCHED = Schedule(dist_frontier="auto", direction="auto")


def _params(name, g):
    if name in ("sssp", "sssp_pull"):
        return dict(src=0)
    if name == "pr":
        return dict(beta=1e-4, delta=0.85, maxIter=60)
    if name == "bc":
        return dict(sourceSet=np.array([0, 7, 23], np.int32))
    if name == "ppr":
        return dict(beta=1e-4, delta=0.85, maxIter=60,
                    sourceSet=np.array([0, 7, 23], np.int32))
    if name == "kcore":
        return dict(k=2)
    return {}


_OUT_KEY = {"sssp": "dist", "sssp_pull": "dist", "pr": "pageRank",
            "tc": "triangle_count", "bc": "BC", "cc": "comp",
            "ppr": "ppr", "lp": "label", "kcore": "core"}


@pytest.fixture(scope="module")
def g_prime(eight_devices):
    from repro.graph import uniform_random
    return uniform_random(101, 5, seed=2)


@pytest.fixture(scope="module")
def local_refs(g_prime):
    """One local-backend run per program — the agreement oracle."""
    refs = {}
    for name in PROGRAMS:
        prog = compile_bundled(name, backend="local")
        refs[name] = np.asarray(
            prog(g_prime, **_params(name, g_prime))[_OUT_KEY[name]])
    return refs


@pytest.mark.parametrize("shards", SHARDS)
@pytest.mark.parametrize("name", PROGRAMS)
def test_distributed_agrees_with_local(name, shards, g_prime, local_refs):
    prog = compile_bundled(name, backend="distributed", schedule=DIST_SCHED)
    mesh = dist.make_mesh_1d(shards)
    out = np.asarray(prog.bind(g_prime, mesh=mesh)(
        **_params(name, g_prime))[_OUT_KEY[name]])
    ref = local_refs[name]
    if ref.dtype.kind == "f":
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4,
                                   err_msg=f"{name} @ {shards} shards")
    else:
        assert np.array_equal(out, ref), f"{name} @ {shards} shards"


def test_context_owns_per_shard_partition_views(g_prime):
    """One graph serves every mesh in the matrix through its single
    GraphContext: the 1-D partitions are memoized per shard count, so
    binding the same (program, shard count) twice builds nothing new."""
    from repro.core import get_context
    prog = compile_bundled("sssp", backend="distributed", schedule=DIST_SCHED)
    for shards in SHARDS:
        prog.bind(g_prime, mesh=dist.make_mesh_1d(shards))
    ctx = get_context(g_prime)
    keys = {k[1] for k in ctx.view_keys() if k[0] == "dist_1d"}
    assert set(SHARDS) <= keys
    before = len(ctx.view_keys())
    prog.bind(g_prime, mesh=dist.make_mesh_1d(4))   # memoized: no new views
    assert len(ctx.view_keys()) == before


def test_delta_priority_on_weighted_grid(eight_devices):
    """Delta-stepping distributed: the bucketed frontier plus the
    priority-sliced exchange must agree with the local monotonic oracle on
    the weighted-grid family the schedule targets, under both the dense
    and the compressed exchange policies."""
    from repro.graph.algorithms_ref import sssp_ref
    from repro.graph.generators import road
    g = road(16, seed=7)
    ref = sssp_ref(g, 0).astype(np.int32)
    mesh = dist.make_mesh_1d(4)
    for frontier in ("dense", "auto"):
        sched = Schedule(priority="delta", delta_bucket=150,
                         dist_frontier=frontier, direction="auto")
        prog = compile_bundled("sssp", backend="distributed", schedule=sched)
        out = prog.bind(g, mesh=mesh)(src=0)
        assert np.array_equal(np.asarray(out["dist"]), ref), frontier
        # bucket advance is collective on every policy; the exchange is
        # priority-sliced only on the compressed path (dense publishes the
        # full fresh view, which needs no slicing)
        assert "rtd.min_global" in prog.source
        assert ("within=" in prog.source) == (frontier == "auto"), frontier


def test_exchange_within_ships_only_window_entries(eight_devices):
    """Unit contract of the priority-sliced compact exchange: changed
    entries inside `within` ship; changed entries outside are withheld
    (deferred until their bucket opens — the full view stays stale for
    them); the fused pair buffer still costs exactly 2*cap*P elements."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as PS

    from repro.core import runtime_dist as rtd
    P, B = 8, 16
    n_pad = P * B                                   # 128
    mesh = dist.make_mesh_1d(P)
    idx = np.arange(n_pad)
    changed = idx % B < 4                           # 4 changed per shard
    window = idx % B < 2                            # ...2 of them in-window
    full_prev = jnp.full(n_pad, 100, jnp.int32)
    blk = jnp.where(changed, 50, 100).astype(jnp.int32)
    own = jnp.arange(n_pad, dtype=jnp.int32)

    def body(fp, b, w, o):
        return rtd.exchange(fp, b, o, 0.25, skip_empty=False, within=w)

    out, elems = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(PS(), PS("data"), PS("data"), PS("data")),
        out_specs=(PS(), PS()), check_vma=False))(
            full_prev, blk, jnp.asarray(window), own)
    out = np.asarray(out)
    assert (out[window] == 50).all()                # in-window changes ship
    assert (out[changed & ~window] == 100).all()    # out-of-window deferred
    assert (out[~changed] == 100).all()
    cap = rtd.compact_cap(B, 0.25)
    assert 2 * cap * P < n_pad, "setup must stay on the compact path"
    assert int(elems) == 2 * cap * P


def test_comm_volume_counter_monotone_in_policy(g_prime):
    """The generated `_gather_elems` counter: the compressed policies never
    move MORE property-exchange elements than the dense baseline, and the
    empty-skip ("auto") never more than plain compact."""
    mesh = dist.make_mesh_1d(8)
    elems = {}
    for pol in ("dense", "compact", "auto"):
        prog = compile_bundled("sssp", backend="distributed",
                               schedule=Schedule(dist_frontier=pol))
        elems[pol] = int(prog.bind(g_prime, mesh=mesh)(src=0)["_gather_elems"])
    assert elems["compact"] <= elems["dense"]
    assert elems["auto"] <= elems["compact"]
    assert elems["auto"] < elems["dense"], elems
