"""Driver for the distributed backend: wraps a generated per-device body in
`jax.shard_map` over the mesh 'data' axis and runs it on a partitioned graph.

    prog = compile_bundled("sssp", backend="distributed")
    out  = dist.run(prog, g, mesh, src=0)     # same result dict as local
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, PartitionSpec as P

from ..graph.csr import CSRGraph
from . import runtime_dist as rtd


def make_mesh(shape: tuple, axes: tuple, devices=None):
    """A mesh with Auto axis types: the graph runners trim and reshape their
    sharded outputs outside `shard_map`, which Explicit axes (the default of
    `jax.make_mesh`) refuse."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_mesh_1d(num_devices: int | None = None):
    devs = jax.devices()
    n = num_devices or len(devs)
    return make_mesh((n,), (rtd.AXIS,), devices=devs[:n])


def prepare(g: CSRGraph, mesh, *, ell: bool = False,
            edge_key: bool = False) -> dict:
    """Partitioned device arrays for `g`, memoized in the graph's shared
    `GraphContext` — repeated runs against one graph partition it once."""
    from .context import get_context
    return get_context(g).dist_arrays(mesh, ell=ell, edge_key=edge_key)


def run(prog, g: CSRGraph, mesh, **params):
    """Partition `g`, shard_map the generated body, return global results
    (property arrays trimmed to the true vertex count).

    Equivalent to `prog.bind(g, mesh=mesh)(**params)` — prefer `bind` for
    repeated queries against one graph."""
    meta = getattr(prog, "dist_meta", None) or {}
    gd = prepare(g, mesh, **rtd.view_flags(meta))
    return run_prepared(prog, gd, mesh, num_nodes=g.num_nodes, **params)


def run_pod_parallel(prog, g: CSRGraph, mesh, source_set, **params):
    """Source-parallel execution over the 'pod' axis (multi-pod BC/SSSP).

    mesh must have axes ('pod', 'data'). The graph is replicated across
    pods; the source set is sharded over 'pod'; each pod runs the 1-D
    distributed program over its 'data' axis for its source subset; the
    centrality contributions are psum'd across pods at the end. Inter-pod
    traffic = one psum of the output — the DCI-friendly schedule."""
    meta = getattr(prog, "dist_meta", None) or {}
    gd = prepare(g, mesh, **rtd.view_flags(meta))
    in_specs = rtd.partition_specs(gd, mesh)          # 'data' only → pod-replicated
    npods = mesh.shape["pod"]
    srcs = np.asarray(source_set, np.int32)
    pad = (-len(srcs)) % npods
    if pad:   # pad with repeats of source 0 and subtract its extra runs
        raise ValueError("source set must divide the pod count for now")
    body = prog.raw_fn
    set_param = next(p.name for p in prog.ir.params if p.kind == "set_n")
    names = [n for n, v in params.items() if v is not None and n != set_param]
    other = tuple(params[n] for n in names)

    def pod_body(gd_, srcs_, *vs):
        kw = dict(zip(names, vs))
        kw[set_param] = srcs_
        out = body(gd_, **kw)
        # sum per-pod contributions of every output property; the
        # communication counter also diverges per pod (each pod ran its
        # own source subset), so the reported volume is the pod total
        summed = set(meta.get("out_props", ())) | {"_gather_elems"}
        return {k: (jax.lax.psum(v, "pod") if k in summed else v)
                for k, v in out.items()}

    out_specs = {v: P(rtd.AXIS) for v in meta.get("out_props", [])}
    out_specs.update({v: P() for v in meta.get("out_scalars", [])})
    fn = jax.jit(jax.shard_map(
        pod_body, mesh=mesh,
        in_specs=(in_specs, P("pod")) + tuple(P() for _ in other),
        out_specs=out_specs, check_vma=False))
    out = fn(gd, jnp.asarray(srcs), *other)
    return {k: (v[: g.num_nodes] if k in meta.get("out_props", ()) else v)
            for k, v in out.items()}


def run_prepared(prog, gd: dict, mesh, *, num_nodes: int | None = None, **params):
    meta = getattr(prog, "dist_meta", None) or {}
    names = tuple(n for n, v in params.items() if v is not None)
    vals = tuple(params[n] for n in names)
    fn = _runner(prog, gd, mesh, names, meta)
    out = fn(gd, *vals)
    if num_nodes is not None:
        out = {k: (v[:num_nodes] if k in meta.get("out_props", ()) else v)
               for k, v in out.items()}
    return out


def _runner(prog, gd: dict, mesh, names: tuple, meta: dict):
    """The jitted shard_map wrapper for one (program, mesh, param-signature).

    Built once and cached on the program: `jax.jit` keys its own cache on
    function identity, so constructing a fresh lambda per call (the old
    behavior) re-traced and re-compiled on EVERY query — fatal for a query
    server. `gd`'s keys are in the cache key because the views a program
    reads (`ell_cols`, `edge_key_rep`) change its pytree structure."""
    cache = getattr(prog, "_dist_runner_cache", None)
    if cache is None:
        cache = {}
        try:
            prog._dist_runner_cache = cache
        except AttributeError:   # e.g. a frozen/slots stand-in program
            pass
    key = (mesh, names, tuple(sorted(gd)))
    fn = cache.get(key)
    if fn is None:
        in_specs = rtd.partition_specs(gd, mesh)
        out_specs = {v: P(rtd.AXIS) for v in meta.get("out_props", [])}
        out_specs.update({v: P() for v in meta.get("out_scalars", [])})
        body = prog.raw_fn
        fn = cache[key] = jax.jit(jax.shard_map(
            lambda gd_, *vs: body(gd_, **dict(zip(names, vs))),
            mesh=mesh,
            in_specs=(in_specs,) + tuple(P() for _ in names),
            out_specs=out_specs, check_vma=False,
        ))
    return fn
