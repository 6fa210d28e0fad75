"""In-program tracing (`repro.trace`): host spans on the profiler's clock,
XLA compile records, and the device counters generated programs return
(`_supersteps`, `_push_steps`, `_edges_active`, `_edges_swept`)."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import trace
from repro.core import Schedule, compile_bundled, prepare
from repro.graph import powerlaw_social, rmat, road

SCHEDULES = {
    "auto": Schedule(),
    "push": Schedule(direction="push"),
    "pull": Schedule(direction="pull"),
    "delta": Schedule(priority="delta", delta_bucket=64),
}
PR_PARAMS = dict(beta=1e-4, delta=0.85, maxIter=60)
COUNTERS = {"_supersteps", "_push_steps", "_edges_active", "_edges_swept"}


@pytest.fixture(scope="module")
def g_kron():
    """A small Kronecker (R-MAT, Graph500's A/B/C) graph."""
    return rmat(9, edge_factor=8, seed=3)


@pytest.fixture(scope="module")
def g_grid():
    return road(12, seed=5)


def sssp_supersteps(g, src):
    """Supersteps of sssp.sp's frontier Bellman-Ford, in float64 numpy:
    loop bodies until one changes nothing. Also the out-edges of each
    superstep's frontier, summed."""
    edge_src = np.asarray(g.edge_src)
    indices = np.asarray(g.indices)
    wts = np.asarray(g.weights, np.float64)
    out_deg = np.asarray(g.out_degree)
    dist = np.full(g.num_nodes, np.inf)
    dist[src] = 0.0
    front = np.zeros(g.num_nodes, bool)
    front[src] = True
    steps, active = 0, 0
    while True:
        steps += 1
        active += int(out_deg[front].sum())
        fe = front[edge_src]
        cand = np.full(g.num_nodes, np.inf)
        np.minimum.at(cand, indices[fe], dist[edge_src[fe]] + wts[fe])
        front = cand < dist
        dist = np.minimum(dist, cand)
        if not front.any():
            return steps, active


# --- host spans ----------------------------------------------------------------

def test_spans_nest_and_record_their_parent():
    trace.reset()
    with trace.span("outer") as outer:
        with trace.span("inner") as inner:
            pass
        with trace.span("inner"):
            pass
    recs = trace.records()
    assert [r["name"] for r in recs] == ["outer", "inner", "inner"]
    assert outer["parent"] is None
    assert recs[1]["parent"] == recs[2]["parent"] == outer["id"]
    assert outer["start_ns"] <= inner["start_ns"] <= inner["end_ns"] <= outer["end_ns"]
    secs = trace.seconds()
    assert set(secs) == {"outer", "inner"}
    assert secs["inner"] <= secs["outer"]
    trace.reset()
    assert trace.records() == [] and trace.seconds() == {}


def test_span_closes_and_counts_when_its_body_raises():
    trace.reset()
    with pytest.raises(ValueError):
        with trace.span("fails"):
            raise ValueError("x")
    with trace.span("next") as rec:
        pass
    assert rec["parent"] is None               # the failed span left the stack
    assert set(trace.seconds()) == {"fails", "next"}


def test_span_agrees_with_the_profiler_trace_within_1ms(tmp_path):
    """The record and the span's event in a CPU profiler trace fall on one
    clock: the trace's `profile_start_time` plus the event's offset."""
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    with trace.span("clock_check") as rec:
        jnp.ones(8).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)[0]
    pd = ProfileData.from_file(path)
    start = dict(next(p for p in pd.planes if p.name == "Task Environment").stats)
    events = [ev for p in pd.planes if p.name.startswith("/host:")
              for line in p.lines for ev in line.events if ev.name == "repro.clock_check"]
    assert len(events) == 1
    t0 = start["profile_start_time"] + events[0].start_ns
    assert abs(t0 - rec["start_ns"]) < 1e6
    assert abs(t0 + events[0].duration_ns - rec["end_ns"]) < 1e6


def test_graph_build_and_compile_phases_are_spans():
    trace.reset()
    g = rmat(6, seed=1)
    prog = compile_bundled("sssp", schedule=Schedule(push_threshold_frac=0.03125))
    compile_bundled("sssp", schedule=Schedule(push_threshold_frac=0.03125))   # a hit
    names = [r["name"] for r in trace.records()]
    assert names.count("graph.dedup") == 1 and names.count("graph.csr") == 2
    # the host build puts nothing on a device; `prepare` copies it there once
    assert names.count("graph.to_device") == 0
    prepare(g, program=prog)
    prog.bind(g)
    names = [r["name"] for r in trace.records()]
    assert names.count("graph.to_device") == 1
    assert names.count("compile.parse") == 1 and names.count("compile.codegen") == 1
    assert g.num_nodes == 64


def test_run_span_keeps_the_counters_and_its_compile(g_kron):
    """The first call of a bound program traces and compiles inside its
    `run` span; every call's record holds its result's counters."""
    trace.reset()
    bound = compile_bundled("pr", schedule=Schedule(push_threshold_frac=0.125)).bind(g_kron)
    out = bound(**PR_PARAMS)
    out2 = bound(**PR_PARAMS)
    runs = [r for r in trace.records() if r["name"] == "run"]
    assert len(runs) == 2
    assert trace.counters(runs[0]["counters"]) == trace.counters(out)
    assert runs[1]["counters"]["_supersteps"] is out2["_supersteps"]
    compiles = [r for r in trace.records() if r["name"] == trace.XLA_COMPILE]
    assert compiles and all(c["parent"] == runs[0]["id"] for c in compiles)
    assert trace.seconds()[trace.XLA_COMPILE] > 0


# --- device counters -------------------------------------------------------------

@pytest.mark.parametrize("backend", ["local", "pallas"])
def test_pr_supersteps_equal_iter_count(backend, g_kron):
    out = compile_bundled("pr", backend=backend)(g_kron, **PR_PARAMS)
    c = trace.counters(out)
    assert set(c) == COUNTERS
    assert c["_supersteps"] == int(out["iterCount"]) > 1
    assert c["_push_steps"] == c["_edges_active"] == c["_edges_swept"] == 0


@pytest.mark.parametrize("backend", ["local", "pallas"])
@pytest.mark.parametrize("sched", ["auto", "push", "pull"])
def test_sssp_supersteps_equal_the_float64_reference(backend, sched, g_kron):
    src = int(np.argmax(np.asarray(g_kron.out_degree)))
    out = compile_bundled("sssp", backend=backend, schedule=SCHEDULES[sched])(g_kron, src=src)
    c = trace.counters(out)
    steps, active = sssp_supersteps(g_kron, src)
    assert c["_supersteps"] == steps
    assert c["_edges_active"] == active
    assert c["_push_steps"] == {"auto": c["_push_steps"], "push": steps, "pull": 0}[sched]


@pytest.mark.parametrize("backend", ["local", "pallas"])
@pytest.mark.parametrize("sched", sorted(SCHEDULES))
@pytest.mark.parametrize("name", ["sssp", "sssp_pull", "cc"])
def test_counters_are_bounded(backend, sched, name, g_grid):
    """Push steps never exceed supersteps, nor active edges the edges
    swept; a dense branch sweeps at least E per push or pull step."""
    params = dict(src=0) if name.startswith("sssp") else {}
    out = compile_bundled(name, backend=backend, schedule=SCHEDULES[sched])(g_grid, **params)
    c = trace.counters(out)
    assert 0 <= c["_push_steps"] <= c["_supersteps"]
    assert 0 < c["_edges_active"] <= c["_edges_swept"]
    if sched != "delta":
        assert c["_edges_swept"] >= c["_supersteps"] * g_grid.num_edges


def test_auto_direction_both_pushes_and_pulls():
    """On a power-law graph from a leaf the frontier starts sparse (push)
    and floods (pull): the counter sees both."""
    g = powerlaw_social(400, avg_degree=10, seed=2)
    src = int(np.argmin(np.where(np.asarray(g.out_degree) > 0,
                                 np.asarray(g.out_degree), 10**9)))
    c = trace.counters(compile_bundled("sssp")(g, src=src))
    assert 0 < c["_push_steps"] < c["_supersteps"]


def test_distributed_returns_only_gather_elems(eight_devices, g_grid):
    """Of the superstep counters the distributed backend carries only
    `_supersteps`, beside its exchange counter `_gather_elems`: a shard
    sweeps only its own edges, so the relax counters have no twin there."""
    from repro.core.dist import make_mesh_1d
    prog = compile_bundled("sssp", backend="distributed")
    out = prog.bind(g_grid, mesh=make_mesh_1d(4))(src=0)
    assert set(trace.device_counters(out)) == {"_gather_elems", "_supersteps"}
    assert "_push_steps" not in prog.source
    local = compile_bundled("sssp")(g_grid, src=0)
    assert np.array_equal(np.asarray(out["dist"]), np.asarray(local["dist"]))
    assert int(out["_supersteps"]) == int(local["_supersteps"])


def test_outputs_strip_only_the_counters(g_grid):
    out = compile_bundled("sssp")(g_grid, src=0)
    assert set(trace.outputs(out)) == {"dist", "modified", "finished"}
    assert set(trace.device_counters(out)) == COUNTERS


def test_refresh_takes_a_previous_result_with_counters():
    """`refresh` warm-starts from `prev` with its counters in it: they are
    scalars, so they seed nothing, and the answer equals a fresh run."""
    g = powerlaw_social(150, avg_degree=8, seed=7)
    prog = compile_bundled("sssp", schedule=Schedule(refresh_threshold_frac=1.0))
    prev = prog.bind(g)(src=0)
    assert COUNTERS <= set(prev)
    delta = g.update(np.array([[3, 40], [5, 90]]), weights=np.array([1, 2]))
    bound = prog.bind(delta.graph)
    refreshed = bound.refresh(prev, delta, src=0)
    scratch = bound(src=0)
    assert np.array_equal(np.asarray(refreshed["dist"]), np.asarray(scratch["dist"]))
    assert 0 < int(refreshed["_supersteps"])


# --- the compiled program ----------------------------------------------------------

def test_hlo_carries_the_loop_and_branch_scopes(g_kron):
    prog = compile_bundled("sssp")
    hlo = prog.fn.lower(g_kron, src=0).compile().as_text()
    for scope in ("fp1.body", "fp1.push", "fp1.pull"):
        assert scope in hlo, scope


@pytest.mark.parametrize("name,params", [("sssp", dict(src=0)), ("pr", PR_PARAMS)])
def test_jitted_program_has_no_host_callback(name, params, g_kron):
    hlo = compile_bundled(name).fn.lower(g_kron, **params).as_text()
    assert "callback" not in hlo.lower()
