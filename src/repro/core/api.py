"""Public compiler API: StarPlat source → executable JAX program.

The algorithm/schedule split (GraphIt-style):

    sched = Schedule(batch_sources=16)               # the schedule
    prog  = compile_program(source, backend="pallas", schedule=sched)
    bound = prog.bind(g)                             # per-graph entry point
    out   = bound(src=0)                             # serve queries
    print(prog.source)                               # generated Python/JAX

`compile_program` is memoized on `(source digest, backend, schedule,
fn_name, jit)`: repeated calls return the SAME `CompiledProgram` without
re-parsing or re-exec'ing generated code — compile once per (program,
schedule), prepare each graph once (`repro.core.context.prepare`), then
serve. Per-graph derived structures (sliced-ELL views, distributed
partitions) live in the shared `GraphContext` registry, not in
backend-private caches.
"""
from __future__ import annotations

import dataclasses
import hashlib
import os
import weakref
from typing import Callable, Optional

import jax

from ..graph.csr import resolve_schedule
from ..schedule import Schedule
from ..trace import device_counters, span
from . import runtime as rt
from .analysis import (DiagnosticError, check_schedule, entry_error,
                       program_analysis, split)
from .context import get_context, on_device
from .lowering import lower
from .parser import parse

_BACKENDS = ("local", "pallas", "distributed")

_PROGRAM_DIR = os.path.join(os.path.dirname(__file__), "programs")

_PRELUDE = (
    "import jax\n"
    "import jax.numpy as jnp\n"
    "from repro.core import runtime as rt\n\n"
)


@dataclasses.dataclass(eq=False)
class CompiledProgram:
    name: str
    backend: str
    source: str          # generated Python/JAX source text
    fn: Callable         # compiled callable (jit according to backend)
    raw_fn: Callable     # un-jitted generated function
    ir: object
    schedule: Schedule   # the schedule baked into `source`
    dist_meta: Optional[dict] = None   # distributed backend: output specs
    dsl_source: str = ""  # the StarPlat source this was compiled from
    jit: bool = True      # jit flag the program was compiled under
    diagnostics: tuple = ()  # analysis findings that survived the gate
    # jitted `<name>__refresh` wrapper (same calling convention as `fn`,
    # plus _warm/_reset/_seed), or None when the program has no top-level
    # iterative construct to warm-start. Call through
    # `BoundProgram.refresh`, which derives the seeding from a GraphDelta.
    refresh_fn: Optional[Callable] = None

    def recompile(self, schedule: Schedule) -> "CompiledProgram":
        """The same algorithm under a different schedule — a compile-cache
        probe, so repeated requests (e.g. autotuning trials) for an
        already-built (source, backend, schedule) are free."""
        return compile_program(self.dsl_source, backend=self.backend,
                               fn_name=self.name, jit=self.jit,
                               schedule=schedule)

    def __call__(self, *args, **kwargs):
        return self.fn(*args, **kwargs)

    def bind(self, g, *, mesh=None) -> "BoundProgram":
        """Graph-bound callable — the uniform calling convention.

        `prog.bind(g)(**params)` works identically on every backend: the
        local/pallas backends resolve the graph's derived views through its
        `GraphContext` (warming them at bind time), and the distributed
        backend folds in the mesh / partition / `dist_meta` plumbing that
        previously had to go through `repro.core.dist.run` by hand
        (`mesh=None` → one shard per local device).

        Memoized per (program, graph) with weakref keying (the GraphContext
        registry idiom): repeated binds on a serving query path return the
        SAME `BoundProgram` as long as someone holds it, instead of
        re-warming views and (distributed) re-building the jitted runner.
        An explicit `mesh=` bypasses the cache (the mesh is caller state)."""
        if mesh is not None:
            return BoundProgram(self, g, mesh=mesh)
        key = (id(self), id(g))
        entry = _BIND_CACHE.get(key)
        if entry is not None:
            wp, wg, wb = entry
            bound = wb()
            if bound is not None and wp() is self and wg() is g:
                return bound
        bound = BoundProgram(self, g)

        def _evict(_r, _k=key):
            # only remove the entry this weakref belongs to: the key may
            # have been re-filled after an id() reuse
            cur = _BIND_CACHE.get(_k)
            if cur is not None and (cur[2]() is None or cur[0]() is None
                                    or cur[1]() is None):
                _BIND_CACHE.pop(_k, None)

        _BIND_CACHE[key] = (weakref.ref(self, _evict), weakref.ref(g, _evict),
                            weakref.ref(bound, _evict))
        return bound


class BoundProgram:
    """A `CompiledProgram` bound to one graph (`prog.bind(g)`).

    Holds the graph strongly (a bound program keeps its graph alive) and
    warms the per-graph structures once at construction, so every
    subsequent call is pure execution. For the distributed backend the
    shard_map-wrapped jitted runner is also built once per parameter
    signature and cached here."""

    def __init__(self, program: CompiledProgram, graph, *, mesh=None):
        with span("bind"):
            self._bind(program, graph, mesh)

    def _bind(self, program: CompiledProgram, graph, mesh):
        self.program = program
        self.graph = graph
        ctx = get_context(graph)
        if program.backend == "distributed":
            from . import dist
            from . import runtime_dist as rtd
            self.mesh = mesh if mesh is not None else dist.make_mesh_1d()
            self._gd = ctx.dist_arrays(self.mesh,
                                       **rtd.view_flags(program.dist_meta))
        else:
            if mesh is not None:
                raise ValueError(
                    "mesh= applies to the distributed backend only (this "
                    f"program's backend is {program.backend!r})")
            self.mesh = None
            on_device(graph)      # warm the one device copy every call runs on
            if program.backend == "pallas":
                ctx.sliced_ell(program.schedule, reverse=True)
            elif program.backend == "local" and ", _dell" in program.source:
                ctx.delta_ell()   # warm the delta-stepping compact-relax view

    def __call__(self, **params):
        """One run. Its `run` span times the dispatch (the first call also
        traces and compiles) and keeps the result's device counters,
        unfetched, on its record (`repro.trace`)."""
        prog = self.program
        with span("run") as rec:
            if prog.backend != "distributed":
                out = prog.fn(self.graph, **params)
            else:
                from . import dist
                out = dist.run_prepared(prog, self._gd, self.mesh,
                                        num_nodes=self.graph.num_nodes,
                                        **params)
            rec["counters"] = device_counters(out)
        return out

    def refresh(self, prev: dict, delta, /, **params):
        # prev/delta are positional-only: program params are free to reuse
        # the names (PR's damping factor is literally called `delta`)
        """Incremental recompute after `g.update()`: the previous result
        warm-starts the program's iterative construct instead of running it
        from the cold init.

        `prev` is a prior result dict of the SAME program (on the
        pre-update graph), `delta` the `GraphDelta` whose `.graph` this
        program is bound to. The delta's `plan()` supplies the seeding:
        previous per-node values are kept except in the deletion cone
        (reset to cold init), and the first sweep's frontier is the
        update-incident seed set. When the affected fraction of N exceeds
        `Schedule.refresh_threshold_frac`, the warm start would touch most
        of the graph anyway, so this falls back to a dense from-scratch
        run — either path returns the same converged result dict a plain
        call would."""
        prog = self.program
        if prog.backend == "distributed":
            raise ValueError(
                "refresh is a local/pallas entry point; recompute "
                "distributed programs from scratch after an update")
        if prog.refresh_fn is None:
            raise ValueError(
                f"{prog.name!r} has no incremental refresh: the program "
                "has no top-level iterative construct (fixedPoint / while "
                "/ do-while) to warm-start")
        fx = program_analysis(prog.dsl_source).functions.get(prog.name)
        if fx is not None and fx.refresh_unsafe:
            from .analysis import diag
            raise DiagnosticError(
                [diag("SP209", fx.refresh_unsafe_reason, fn=prog.name,
                      line=fx.refresh_unsafe_line, src=prog.dsl_source)],
                header=f"refresh rejected for {prog.name!r}")
        if delta.graph is not self.graph:
            raise ValueError(
                "refresh must run on the post-update graph: bind the "
                "program to delta.graph and pass the matching delta")
        plan = delta.plan()
        if plan.affected_frac > prog.schedule.refresh_threshold_frac:
            return self(**params)
        n = self.graph.num_nodes
        warm = {k: v for k, v in prev.items()
                if getattr(v, "shape", None) == (n,)}
        import jax.numpy as jnp
        return prog.refresh_fn(self.graph, _warm=warm,
                               _reset=jnp.asarray(plan.reset),
                               _seed=jnp.asarray(plan.seed), **params)

    def __repr__(self):
        g = self.graph
        return (f"BoundProgram({self.program.name!r}, "
                f"backend={self.program.backend!r}, N={g.num_nodes}, "
                f"E={g.num_edges})")


def _exec_generated(src: str, fn_name: str, extra_env: Optional[dict] = None):
    """Exec the generated module source; returns its namespace (the main
    function plus, when emitted, the `<name>__refresh` incremental
    variant)."""
    import jax.numpy as jnp
    env = {"jax": jax, "jnp": jnp, "rt": rt}
    if extra_env:
        env.update(extra_env)
    code = compile(src, f"<starplat:{fn_name}>", "exec")
    exec(code, env)
    return env


# compile cache: (source digest, backend, schedule, fn_name, jit) -> program
_COMPILE_CACHE: dict = {}

# bind cache: (id(program), id(graph)) -> (wr(program), wr(graph), wr(bound)).
# Everything is held WEAKLY: a BoundProgram keeps its graph alive, so the
# cache must not keep the bound program alive (that would pin every graph
# ever bound); when the caller drops the bound runner — or either key dies —
# the entry evicts itself and the next bind rebuilds.
_BIND_CACHE: dict = {}


def compile_cache_clear() -> None:
    _COMPILE_CACHE.clear()


def compile_cache_size() -> int:
    return len(_COMPILE_CACHE)


def bind_cache_clear() -> None:
    _BIND_CACHE.clear()


def bind_cache_size() -> int:
    return len(_BIND_CACHE)


def compile_program(source: str, backend: str = "local",
                    fn_name: Optional[str] = None, jit: bool = True,
                    schedule: Optional[Schedule] = None,
                    batch_sources: Optional[int] = None,
                    strict: bool = False,
                    **backend_opts) -> CompiledProgram:
    """Compile a StarPlat program under an explicit `Schedule`.

    `schedule=None` snapshots the deprecated `ENGINE` shim (the default
    `Schedule` unless someone mutated it); `batch_sources=` is the legacy
    per-compile override, folded into the schedule. Every engine knob is
    baked into the generated source as a literal, so the same schedule
    yields byte-identical source and mutating `ENGINE` afterwards never
    changes an already-compiled program. Results are memoized — repeated
    identical calls return the same `CompiledProgram` object (unknown
    `backend_opts` bypass the cache).

    Every compile — cache hits included — passes the static analysis gate
    (`repro.core.analysis`): effect-analysis errors (races, non-terminating
    fixed points) and illegal schedule combinations raise
    `DiagnosticError` with stable SPxxx codes; `strict=True` promotes
    warnings to errors.  Surviving warnings ride on the returned program's
    `.diagnostics`.

    On a TPU, `backend="pallas"` raises `NotImplementedError`: Mosaic
    refuses its kernel (`repro.kernels.ell_spmv.ops.TPU_REFUSAL`)."""
    if backend not in _BACKENDS:
        raise entry_error(
            "SP301",
            f"unknown backend {backend!r}; backends: {', '.join(_BACKENDS)}")
    if backend == "pallas" and jax.default_backend() == "tpu":
        from ..kernels.ell_spmv.ops import TPU_REFUSAL
        raise NotImplementedError(TPU_REFUSAL)
    sched = resolve_schedule(schedule, batch_sources=batch_sources)

    # --- static analysis gate (runs before the cache: rejection must not
    # depend on whether an earlier permissive call already compiled) -------
    analysis = program_analysis(source)
    if fn_name is not None and fn_name not in analysis.functions:
        defined = ", ".join(analysis.functions) or "<none>"
        raise entry_error(
            "SP302",
            f"program defines no function named {fn_name!r}; it "
            f"defines: {defined}")
    gate_name = fn_name if fn_name is not None \
        else next(iter(analysis.functions))
    fx = analysis.functions[gate_name]
    diags = tuple(fx.diagnostics) + tuple(check_schedule(fx, sched, backend))
    errors, warnings = split(diags)
    if errors or (strict and warnings):
        raise DiagnosticError(
            diags, header=(f"analysis rejected {gate_name!r} "
                           f"(backend={backend!r})"))

    cache_key = None
    if not backend_opts:
        digest = hashlib.sha256(source.encode()).hexdigest()
        cache_key = (digest, backend, sched, fn_name, jit)
        cached = _COMPILE_CACHE.get(cache_key)
        if cached is not None:
            return cached

    with span("compile.parse"):
        irfns = lower(parse(source))
    if fn_name is None:
        irfn = irfns[0]
    else:
        irfn = [f for f in irfns if f.name == fn_name][0]
    with span("compile.codegen"):
        if backend == "local":
            from .codegen.local_jax import generate_local
            body = generate_local(irfn, schedule=sched, **backend_opts)
            extra_env = None
        elif backend == "distributed":
            from .codegen.distributed import generate_distributed
            body, extra_env = generate_distributed(irfn, schedule=sched,
                                                   **backend_opts)
        else:
            from .codegen.pallas_backend import generate_pallas
            body, extra_env = generate_pallas(irfn, schedule=sched,
                                              **backend_opts)
        src = _PRELUDE + body
        env = _exec_generated(src, irfn.name, extra_env)
    raw = env[irfn.name]
    raw_refresh = env.get(f"{irfn.name}__refresh")

    # CSRGraph is a registered pytree with static num_nodes/num_edges metadata,
    # so the graph argument is dynamic (arrays) + static (sizes) automatically.
    # The local and pallas entries take the graph as built on the host and
    # run on its one device copy (`on_device`), owned by its GraphContext.
    def _wrap(raw_fn):
        if backend == "distributed":
            return raw_fn
        jitted = jax.jit(raw_fn) if jit else raw_fn
        if backend == "pallas":
            def fn(g, *, _jitted=jitted, _sched=sched, **kw):
                # degree-bucketed reverse (in-edge) view, owned by the
                # graph's shared GraphContext — built once per (graph,
                # layout), shared with every other program compiled under
                # the same layout.
                ell = get_context(g).sliced_ell(_sched, reverse=True)
                return _jitted(on_device(g), ell, **kw)
            return fn
        if f"def {irfn.name}({irfn.graph_param}, _dell" in body:
            # delta-stepping program: the generated functions take the
            # padded forward-ELL view the compact bucket relax gathers
            # frontier out-rows from (None on hub-heavy graphs → dense
            # fallback)
            def fn(g, *, _jitted=jitted, **kw):
                return _jitted(on_device(g), get_context(g).delta_ell(), **kw)
            return fn

        def fn(g, *, _jitted=jitted, **kw):
            return _jitted(on_device(g), **kw)
        if jit:
            fn.lower = jitted.lower
        return fn

    fn = _wrap(raw)
    refresh_fn = _wrap(raw_refresh) if raw_refresh is not None else None
    prog = CompiledProgram(
        name=irfn.name, backend=backend, source=src, fn=fn, raw_fn=raw,
        ir=irfn, schedule=sched,
        dist_meta=(extra_env or {}).get("__dist_meta__"),
        dsl_source=source, jit=jit, diagnostics=diags,
        refresh_fn=refresh_fn)
    if cache_key is not None:
        _COMPILE_CACHE[cache_key] = prog
        if fn_name is None:
            # also file under the resolved name, so an explicit request for
            # the same function (e.g. CompiledProgram.recompile) is a hit
            # on the same object rather than a duplicate compile
            _COMPILE_CACHE[(digest, backend, sched, irfn.name, jit)] = prog
    return prog


def bundled_programs() -> list:
    """Names of the bundled paper programs (`.sp` sources)."""
    return sorted(p[:-3] for p in os.listdir(_PROGRAM_DIR)
                  if p.endswith(".sp"))


def load_program_source(name: str) -> str:
    """Source text of a bundled paper program (sssp, sssp_pull, pr, tc, bc,
    cc); raises `ValueError` naming the bundled programs otherwise."""
    path = os.path.join(_PROGRAM_DIR, f"{name}.sp")
    if not os.path.exists(path):
        raise entry_error(
            "SP303",
            f"no bundled program named {name!r}; bundled programs: "
            f"{', '.join(bundled_programs())}")
    with open(path) as f:
        return f.read()


def compile_bundled(name: str, backend: str = "local", **kw) -> CompiledProgram:
    return compile_program(load_program_source(name), backend=backend, **kw)
