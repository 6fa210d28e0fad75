"""The async serving layer: coalescing, admission, deadlines, the graph
pool's memory-bounded eviction, bind memoization on the query path, and
TuningStore concurrent-writer safety.

Async tests run real event loops via `asyncio.run` (no plugin dependency);
sweeps execute in worker threads exactly as in production.
"""
import asyncio
import dataclasses
import gc
import os
import time
import weakref

import numpy as np
import pytest

from repro.autotune import (TuningRecord, TuningStore, schedule_to_dict,
                            source_digest)
from repro.core import Schedule, get_context, load_program_source
from repro.graph import preferential_attachment
from repro.graph.algorithms_ref import bc_ref, bfs_levels_ref, sssp_ref
from repro.serve import (GraphService, QueryKind, ServiceConfig,
                         ServiceError, ServiceOverloaded, ServiceTimeout,
                         UnknownGraph, UnknownQueryKind)
from repro.serve.pool import GraphPool


@pytest.fixture(scope="module")
def g_a():
    return preferential_attachment(300, m=4, seed=3)


@pytest.fixture(scope="module")
def g_b():
    return preferential_attachment(200, m=3, seed=5)


class SlowKind(QueryKind):
    """Test kind: a sweep that takes `delay` seconds (off-loop, like jax)."""

    name = "slow"
    per_source = True
    program = None

    def __init__(self, delay=0.25):
        self.delay = delay

    def make_runner(self, handle, sched, width):
        def run(params_list):
            time.sleep(self.delay)
            return [np.int32(p["src"]) for p in params_list]
        return run


class FailKind(QueryKind):
    name = "fail"
    per_source = True
    program = None

    def make_runner(self, handle, sched, width):
        def run(params_list):
            raise ValueError("kaboom")
        return run


# --- the service smoke: 2 graphs, interleaved concurrent queries, oracles ----

def test_service_interleaved_two_graphs_match_oracles(g_a, g_b):
    async def main():
        async with GraphService(ServiceConfig(max_wait_ms=10.0)) as svc:
            svc.register_graph("a", g_a)
            svc.register_graph("b", g_b)
            jobs, expect = [], []
            for s in (0, 5, 9, 17, 42):
                jobs.append(svc.query("a", "sssp", src=s))
                expect.append(("sssp", g_a, s))
                jobs.append(svc.query("b", "sssp", src=s))
                expect.append(("sssp", g_b, s))
                jobs.append(svc.query("a", "bfs", src=s))
                expect.append(("bfs", g_a, s))
            jobs.append(svc.query("b", "bc",
                                  sourceSet=np.array([0, 3, 7], np.int32)))
            res = await asyncio.gather(*jobs)
            for (kind, g, s), out in zip(expect, res):
                ref = (sssp_ref(g, s).astype(np.int32) if kind == "sssp"
                       else bfs_levels_ref(g, s))
                assert np.array_equal(np.asarray(out), ref), (kind, s)
            np.testing.assert_allclose(np.asarray(res[-1]),
                                       bc_ref(g_b, [0, 3, 7]), atol=1e-3)
            return svc.stats()

    st = asyncio.run(main())
    assert st["served"] == 16
    # coalescing actually packed lanes: strictly fewer sweeps than queries
    assert st["sweeps"] < st["served"]
    assert st["max_batch"] > 1
    assert st["rejected"] == 0 and st["timeouts"] == 0


def test_lone_query_flushes_at_deadline_not_full_lane(g_a):
    """A single query must never starve waiting for batch_sources - 1
    lane-mates that will never arrive."""
    async def main():
        cfg = ServiceConfig(max_wait_ms=5.0,
                            schedule=Schedule(batch_sources=64))
        async with GraphService(cfg) as svc:
            svc.register_graph("a", g_a)
            t0 = asyncio.get_running_loop().time()
            out = await svc.query("a", "sssp", src=3)
            dt = asyncio.get_running_loop().time() - t0
            assert np.array_equal(np.asarray(out),
                                  sssp_ref(g_a, 3).astype(np.int32))
            return dt, svc.stats()

    dt, st = asyncio.run(main())
    assert st["sweeps"] == 1 and st["mean_batch"] == 1.0
    assert dt < 30.0    # flushed on the 5 ms deadline (plus sweep + trace)


def test_coalescing_packs_concurrent_queries(g_a):
    async def main():
        cfg = ServiceConfig(schedule=Schedule(batch_sources=8),
                            max_wait_ms=20.0)
        async with GraphService(cfg) as svc:
            svc.register_graph("a", g_a, kinds=["sssp"])
            res = await asyncio.gather(
                *(svc.query("a", "sssp", src=s % 11) for s in range(16)))
            for s, out in zip(range(16), res):
                assert np.array_equal(
                    np.asarray(out), sssp_ref(g_a, s % 11).astype(np.int32))
            return svc.stats()

    st = asyncio.run(main())
    assert st["served"] == 16
    assert st["sweeps"] <= 8            # 16 queries, 8-wide lanes, slack
    assert st["max_batch"] >= 2


def test_coalesce_false_serves_one_query_per_sweep(g_a):
    async def main():
        cfg = ServiceConfig(coalesce=False,
                            schedule=Schedule(batch_sources=8))
        async with GraphService(cfg) as svc:
            svc.register_graph("a", g_a, kinds=["sssp"])
            await asyncio.gather(
                *(svc.query("a", "sssp", src=s) for s in range(6)))
            return svc.stats()

    st = asyncio.run(main())
    assert st["sweeps"] == st["served"] == 6
    assert st["max_batch"] == 1


# --- personalized PageRank through the service --------------------------------

def test_ppr_kind_coalesces_and_matches_oracle(g_a):
    """Concurrent per-user PPR queries pack into one `rt.ppr_multi` sweep;
    every user gets exactly their own restart vector's ranks."""
    from repro.graph.algorithms_ref import ppr_matrix_ref

    async def main():
        cfg = ServiceConfig(schedule=Schedule(batch_sources=4),
                            max_wait_ms=20.0)
        async with GraphService(cfg) as svc:
            svc.register_graph("a", g_a, kinds=["ppr"])
            srcs = [0, 7, 23, 42]
            res = await asyncio.gather(
                *(svc.query("a", "ppr", src=s) for s in srcs))
            ref = ppr_matrix_ref(g_a, srcs)
            for row, out in zip(ref, res):
                np.testing.assert_allclose(np.asarray(out), row,
                                           rtol=1e-4, atol=1e-5)
            return svc.stats()

    st = asyncio.run(main())
    assert st["served"] == 4
    assert st["max_batch"] > 1          # lanes actually shared a sweep


def test_ppr_lone_query_matches_singleton_program(g_a):
    """A lone PPR request takes the compiled singleton-set path (a
    one-element seed set's aggregate IS the user's row)."""
    from repro.graph.algorithms_ref import ppr_matrix_ref

    async def main():
        async with GraphService(ServiceConfig(max_wait_ms=0.0)) as svc:
            svc.register_graph("a", g_a, kinds=["ppr"])
            out = await svc.query("a", "ppr", src=5)
            np.testing.assert_allclose(np.asarray(out),
                                       ppr_matrix_ref(g_a, [5])[0],
                                       rtol=1e-4, atol=1e-5)
            return svc.stats()

    st = asyncio.run(main())
    assert st["sweeps"] == 1 and st["mean_batch"] == 1.0


def test_zero_wait_lone_request_flushes_immediately(g_a):
    """max_wait_ms=0 disables coalesce-waiting entirely: a lone admitted
    request must flush on the first gather pass (deadline already expired),
    never spin or starve waiting for lane-mates."""
    async def main():
        cfg = ServiceConfig(max_wait_ms=0.0,
                            schedule=Schedule(batch_sources=64))
        async with GraphService(cfg) as svc:
            svc.register_graph("a", g_a, kinds=["sssp"])
            t0 = asyncio.get_running_loop().time()
            out = await svc.query("a", "sssp", src=2)
            dt = asyncio.get_running_loop().time() - t0
            assert np.array_equal(np.asarray(out),
                                  sssp_ref(g_a, 2).astype(np.int32))
            return dt, svc.stats()

    dt, st = asyncio.run(main())
    assert st["served"] == 1 and st["mean_batch"] == 1.0
    assert dt < 30.0    # bounded by sweep + trace time, not a hang


# --- admission control, timeouts, failure scatter -----------------------------

def test_admission_sheds_load_beyond_max_pending(g_a):
    async def main():
        cfg = ServiceConfig(max_pending=2, max_wait_ms=0.0)
        svc = GraphService(cfg)
        svc.register_kind(SlowKind(delay=0.3))
        svc.register_graph("a", g_a, kinds=["slow"])
        async with svc:
            t1 = asyncio.create_task(svc.query("a", "slow", src=1))
            t2 = asyncio.create_task(svc.query("a", "slow", src=2))
            await asyncio.sleep(0.05)   # both admitted and in flight
            with pytest.raises(ServiceOverloaded):
                await svc.query("a", "slow", src=3)
            assert svc.stats()["rejected"] == 1
            assert [int(await t) for t in (t1, t2)] == [1, 2]
            # load shed, not wedged: capacity freed, queries flow again
            assert int(await svc.query("a", "slow", src=4)) == 4

    asyncio.run(main())


def test_request_timeout_raises_and_service_recovers(g_a):
    async def main():
        svc = GraphService(ServiceConfig(max_wait_ms=0.0))
        svc.register_kind(SlowKind(delay=0.4))
        svc.register_graph("a", g_a, kinds=["slow"])
        async with svc:
            with pytest.raises(ServiceTimeout):
                await svc.query("a", "slow", src=1, timeout=0.05)
            assert svc.stats()["timeouts"] == 1
            # the timed-out request's sweep result is discarded, the next
            # query is served normally
            assert int(await svc.query("a", "slow", src=2)) == 2

    asyncio.run(main())


def test_sweep_failure_scatters_to_waiters_only(g_a):
    async def main():
        svc = GraphService(ServiceConfig())
        svc.register_kind(FailKind())
        svc.register_graph("a", g_a, kinds=["fail", "sssp"])
        async with svc:
            with pytest.raises(ServiceError, match="kaboom"):
                await svc.query("a", "fail", src=0)
            # other lanes are unaffected
            out = await svc.query("a", "sssp", src=0)
            assert np.array_equal(np.asarray(out),
                                  sssp_ref(g_a, 0).astype(np.int32))

    asyncio.run(main())


def test_unknown_graph_and_kind_errors(g_a):
    async def main():
        async with GraphService() as svc:
            svc.register_graph("a", g_a, kinds=["sssp"])
            with pytest.raises(UnknownGraph, match="nope"):
                await svc.query("nope", "sssp", src=0)
            with pytest.raises(UnknownQueryKind, match="bc"):
                await svc.query("a", "bc", sourceSet=np.array([0]))
            with pytest.raises(ValueError, match="src"):
                await svc.query("a", "sssp", source=3)

    asyncio.run(main())


@pytest.mark.parametrize("bad,match", [
    (dict(backend="distributed"), "backend"),
    (dict(max_wait_ms=-1.0), "max_wait_ms"),
    (dict(max_pending=0), "max_pending"),
    (dict(default_timeout_s=0.0), "default_timeout_s"),
    (dict(max_concurrent_sweeps=0), "max_concurrent_sweeps"),
    (dict(view_budget_bytes=0), "view_budget_bytes"),
])
def test_service_config_validation(bad, match):
    with pytest.raises(ValueError, match=match):
        ServiceConfig(**bad)


# --- GraphContext pool: accounting, LRU eviction, pinning ---------------------

def test_context_view_accounting_and_selective_drop():
    g = preferential_attachment(150, m=3, seed=7)
    ctx = get_context(g)
    ctx.fingerprint()
    ctx.stats()
    assert ctx.total_view_nbytes() == 0       # metadata views are free
    view = ctx.ell()
    assert ctx.total_view_nbytes() > 0
    assert ctx.view_nbytes()[("ell", False)] >= view.cols.nbytes
    freed = ctx.drop_derived_views()
    assert freed > 0 and ctx.total_view_nbytes() == 0
    # metadata survives eviction (it keys persisted tuning records)
    assert ("fingerprint",) in ctx.view_keys()
    assert ("stats",) in ctx.view_keys()
    assert ("ell", False) not in ctx.view_keys()
    assert ctx.ell() is not view              # rebuilt lazily on demand


def test_pool_lru_eviction_frees_views_weakref_observed():
    g1 = preferential_attachment(150, m=3, seed=1)
    g2 = preferential_attachment(150, m=3, seed=2)
    pool = GraphPool(view_budget_bytes=1)
    ctx1, ctx2 = pool.add("one", g1), pool.add("two", g2)
    wref = weakref.ref(ctx1.ell())
    ctx2.ell()
    pool.get("two")                            # "one" is now LRU
    with pool.pin("two"):
        evicted = pool.enforce_budget()
    assert evicted == ["one"], "LRU unpinned graph's views go first"
    gc.collect()
    assert wref() is None, "evicted view must actually be freed"
    assert ctx1.total_view_nbytes() == 0
    assert ctx2.total_view_nbytes() > 0        # pinned graph kept its views


def test_pool_never_evicts_pinned_graph():
    g = preferential_attachment(100, m=3, seed=4)
    pool = GraphPool(view_budget_bytes=1)
    ctx = pool.add("g", g)
    ctx.ell()
    with pool.pin("g"):
        assert pool.enforce_budget() == []
        assert ctx.total_view_nbytes() > 0     # mid-sweep views untouched
    assert pool.enforce_budget() == ["g"]


def test_eviction_then_query_transparently_reprepares(g_a, g_b):
    """Under a 1-byte view budget every sweep evicts the other graph's
    views; queries keep answering correctly (lazy re-prepare), eviction is
    observable in stats, and the evicted sliced-ELL view object dies."""
    async def main():
        cfg = ServiceConfig(backend="pallas", view_budget_bytes=1)
        async with GraphService(cfg) as svc:
            svc.register_graph("a", g_a, kinds=["bc"])
            svc.register_graph("b", g_b, kinds=["bc"])
            wref = weakref.ref(
                svc.handle("a").ctx.sliced_ell(Schedule(), reverse=True))
            srcs = np.array([0, 3], np.int32)
            for name, g in (("a", g_a), ("b", g_b), ("a", g_a)):
                out = await svc.query(name, "bc", sourceSet=srcs)
                np.testing.assert_allclose(np.asarray(out),
                                           bc_ref(g, srcs.tolist()),
                                           atol=1e-3)
            return wref, svc.stats()

    wref, st = asyncio.run(main())
    assert st["evictions"], "the 1-byte budget must have evicted views"
    gc.collect()
    assert wref() is None, "evicted sliced-ELL view must be freed"


# --- TuningStore: warm-reload + concurrent writers ----------------------------

def _record(digest, fingerprint, schedule):
    return TuningRecord(
        source_digest=digest, backend="local", graph_fingerprint=fingerprint,
        fn_name="f", schedule=schedule_to_dict(schedule), best_ms=1.0,
        default_ms=2.0, trials=[], budget=1, seed=0)


def test_tuning_store_concurrent_writers_merge(tmp_path):
    path = str(tmp_path / "store.json")
    a, b = TuningStore(path), TuningStore(path)   # both loaded empty
    a.put(_record("a" * 16, "f" * 16, Schedule()))
    a.save()
    b.put(_record("b" * 16, "f" * 16, Schedule(direction="pull")))
    b.save()    # reload-merge: must NOT truncate a's record
    c = TuningStore(path)
    assert len(c) == 2
    assert c.lookup("a" * 16, "local", "f" * 16) is not None
    assert c.lookup("b" * 16, "local", "f" * 16) is not None
    # memory wins key conflicts on merge
    b.put(_record("a" * 16, "f" * 16, Schedule(direction="push")))
    b.save()
    c = TuningStore(path)
    assert c.lookup("a" * 16, "local",
                    "f" * 16).best_schedule().direction == "push"
    # merge=False restores explicit-overwrite semantics (pruning)
    fresh = TuningStore(path)
    fresh._records = {}
    fresh.save(merge=False)
    assert len(TuningStore(path)) == 0
    # atomic write leaves no temp droppings behind
    assert [p for p in os.listdir(tmp_path) if p.endswith(".tmp")] == []


def test_service_warm_reloads_tuned_schedule(tmp_path, g_a):
    """A persisted tuning record keyed (program digest, backend, graph
    fingerprint) supplies the serving schedule at registration — the first
    query hits the tuned path with no measurement sweep."""
    tuned = Schedule(direction="pull", batch_sources=4)
    store = TuningStore(str(tmp_path / "t.json"))
    store.put(_record(source_digest(load_program_source("sssp")),
                      get_context(g_a).fingerprint(), tuned))
    store.save()

    async def main():
        svc = GraphService(ServiceConfig(backend="local"),
                           tune_store=str(tmp_path / "t.json"))
        async with svc:
            h = svc.register_graph("a", g_a, kinds=["sssp", "bfs"])
            assert h.tuned == ["sssp"]
            assert h.schedules["sssp"] == tuned
            assert h.schedules["bfs"] == Schedule()   # no record -> default
            out = await svc.query("a", "sssp", src=5)
            assert np.array_equal(np.asarray(out),
                                  sssp_ref(g_a, 5).astype(np.int32))

    asyncio.run(main())


def test_service_ignores_record_tuned_on_another_device(tmp_path, g_a):
    """A record timed on another kind of device (here: a TPU, while this
    process computes elsewhere) neither warm-starts the serving schedule
    nor seeds the tuner's cost model."""
    from repro.autotune import device_kind, nearest_record
    other = "TPU v5 lite" if device_kind() != "TPU v5 lite" else "cpu"
    digest = source_digest(load_program_source("sssp"))
    rec = dataclasses.replace(
        _record(digest, get_context(g_a).fingerprint(),
                Schedule(direction="pull", batch_sources=4)),
        device_kind=other, graph_stats=dict(get_context(g_a).stats()))
    store = TuningStore(str(tmp_path / "t.json"))
    store.put(rec)
    store.save()
    store = TuningStore(str(tmp_path / "t.json"))
    assert store.records() == [rec]                 # kept, under its own kind
    assert store.lookup(digest, "local", rec.graph_fingerprint) is None
    assert nearest_record(store, digest, "local", rec.graph_stats) is None

    svc = GraphService(ServiceConfig(backend="local"), tune_store=store)
    h = svc.register_graph("a", g_a, kinds=["sssp"])
    assert h.tuned == []
    assert h.schedules["sssp"] == Schedule()


def test_register_graph_rejects_duplicates_and_unknown_kind(g_a):
    svc = GraphService()
    svc.register_graph("a", g_a, kinds=["sssp"])
    with pytest.raises(ValueError, match="already registered"):
        svc.register_graph("a", g_a)
    with pytest.raises(UnknownQueryKind, match="katz"):
        svc.register_graph("b", g_a, kinds=["katz"])
    assert "b" not in svc.graphs()    # failed registration fully rolled back


def test_dataclass_record_roundtrip_guard():
    """_record helper stays in sync with TuningRecord's fields."""
    rec = _record("a" * 16, "f" * 16, Schedule())
    assert TuningRecord.from_dict(dataclasses.asdict(rec)) == rec


# --- write batches (g.update through the service) ----------------------------

def test_update_after_eviction_reprepares_and_answers(g_a, g_b):
    """An updated graph whose derived views were LRU-evicted still serves
    correct answers: view adoption is a no-op on an empty context and the
    next query transparently re-prepares against the new version."""
    async def main():
        cfg = ServiceConfig(backend="pallas", view_budget_bytes=1)
        async with GraphService(cfg) as svc:
            svc.register_graph("a", g_a, kinds=["sssp"])
            svc.register_graph("b", g_b, kinds=["sssp"])  # evicts a's views
            assert any(n == "a" for n, _ in svc.stats()["evictions"])
            e_src = np.asarray(g_a.edge_src)
            e_dst = np.asarray(g_a.indices)
            delta = await svc.update_graph(
                "a", adds=[(1, 7), (3, 11)], weights=[2, 2],
                dels=[(int(e_src[0]), int(e_dst[0]))])
            assert svc.handle("a").graph is delta.graph
            out = await svc.query("a", "sssp", src=1)
            assert np.array_equal(np.asarray(out),
                                  sssp_ref(delta.graph, 1).astype(np.int32))
            assert svc.stats()["updates"] == 1

    asyncio.run(main())


class BlockingKind(QueryKind):
    """Sweep blocks until released; reports the graph version it ran on."""

    name = "block"
    per_source = True
    program = None

    def __init__(self):
        import threading
        self.entered = threading.Event()
        self.release = threading.Event()

    def make_runner(self, handle, sched, width):
        g = handle.graph          # the version this runner was built for

        def run(params_list):
            self.entered.set()
            self.release.wait(10)
            return [np.int32(g.version) for _ in params_list]

        return run


def test_update_defers_until_pinned_sweep_unpins(g_a):
    """A write batch arriving mid-sweep must wait for the pin to drop: the
    in-flight sweep finishes against the old version, the update applies
    the moment the last pin releases, and later queries see the new one."""
    async def main():
        kind = BlockingKind()
        async with GraphService(ServiceConfig(max_wait_ms=0.0)) as svc:
            svc.register_kind(kind)
            svc.register_graph("a", g_a, kinds=["block", "sssp"])
            q = asyncio.create_task(svc.query("a", "block", src=0))
            await asyncio.to_thread(kind.entered.wait, 10)  # sweep pinned
            upd = asyncio.create_task(svc.update_graph("a", adds=[(0, 1)],
                                                       weights=[2]))
            await asyncio.sleep(0.05)
            assert not upd.done(), "update applied while the graph was pinned"
            assert svc.handle("a").graph.version == 0
            kind.release.set()
            swept_version = int(await q)
            delta = await upd
            assert swept_version == 0, "sweep must see the pre-update version"
            assert delta.graph.version == 1
            assert svc.handle("a").graph is delta.graph
            out = await svc.query("a", "sssp", src=0)
            assert np.array_equal(np.asarray(out),
                                  sssp_ref(delta.graph, 0).astype(np.int32))

    asyncio.run(main())
