"""Distributed frontier-exchange benchmark: dense vs compressed supersteps.

Runs the distributed backend on 8 virtual host devices and compares the
BSP property-exchange policies (`Schedule.dist_frontier`) on the BFS and
SSSP workloads:

  * per-superstep gathered-element counts — reconstructed host-side by
    replaying the exchange decision rule over the same frontier sizes, and
    cross-checked against the `_gather_elems` counter the generated
    program itself accumulates on device (the two must agree exactly);
  * wall-clock per query, measured identically for every policy.

The dense policy is the paper's scheme (full all-gather every superstep)
and the baseline; "compact" exchanges only changed entries through fixed
per-shard buffers; "auto" additionally skips empty supersteps. On CPU
host devices the collectives are memcpys, so the volume reduction is the
headline number here and the wall-clock is reported honestly either way —
the volume is what an ICI-attached mesh would save.

    PYTHONPATH=src python benchmarks/bench_dist.py [--tiny]

Emits BENCH_dist.json next to the repo root (full run only).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

# 8 virtual devices — must precede the first jax import
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from common import timeit as _timeit_us  # noqa: E402

from repro.core import Schedule, compile_bundled, dist  # noqa: E402
from repro.core.runtime_dist import compact_cap  # noqa: E402
from repro.graph import preferential_attachment  # noqa: E402
from repro.graph.algorithms_ref import bfs_levels_ref  # noqa: E402
from repro.xla_cache import use_persistent_cache  # noqa: E402

OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_dist.json")
P = 8
POLICIES = ("dense", "compact", "auto")


# --------------------------------------------------------------------------
# host-side replay of the exchange decision rule (per-superstep volumes)
# --------------------------------------------------------------------------

def _exchange_vol(chg_counts, n_pad, block, frac, policy):
    """Elements one exchange moves, given per-shard change counts — the
    exact rule `rtd.exchange` applies on device."""
    if policy == "dense":
        return n_pad
    cap = compact_cap(block, frac)
    skip_empty = policy == "auto"
    if 2 * cap * P >= n_pad:                      # compact can't win: dense
        return 0 if (skip_empty and sum(chg_counts) == 0) else n_pad
    if skip_empty and sum(chg_counts) == 0:
        return 0
    return 2 * cap * P if max(chg_counts) <= cap else n_pad


def _shard_counts(changed_mask, block):
    n_pad = len(changed_mask)
    return [int(changed_mask[s * block:(s + 1) * block].sum())
            for s in range(n_pad // block)]


def _pad(arr, n_pad, fill):
    out = np.full(n_pad, fill, arr.dtype)
    out[: len(arr)] = arr
    return out


def replay_sssp_supersteps(g, src, frac, policy):
    """Per-superstep exchange volumes of the generated distributed SSSP:
    each superstep exchanges `dist` then `modified` (sorted read order),
    plus the two initial gathers when the policy carries full views."""
    n = g.num_nodes
    block = -(-n // P)
    n_pad = block * P
    INF = np.int32(2**30)
    esrc = np.asarray(g.edge_src)
    edst = np.asarray(g.indices)
    w = np.asarray(g.weights)
    dist_b = np.full(n_pad, INF, np.int64)
    dist_b[src] = 0
    mod_b = np.zeros(n_pad, bool)
    mod_b[src] = True
    dist_f, mod_f = dist_b.copy(), mod_b.copy()
    steps = []
    initial = 2 * n_pad if policy != "dense" else 0   # pre-loop full gathers
    while True:
        vol = _exchange_vol(_shard_counts(dist_b != dist_f, block),
                            n_pad, block, frac, policy)
        dist_f = dist_b.copy()
        vol += _exchange_vol(_shard_counts(mod_b != mod_f, block),
                             n_pad, block, frac, policy)
        mod_f = mod_b.copy()
        steps.append(vol)
        nd = dist_b.copy()
        on = mod_f[esrc]
        np.minimum.at(nd, edst[on], dist_f[esrc[on]] + w[on])
        mod_b = nd < dist_b
        dist_b = nd
        if not mod_b.any():
            break
    return steps, initial + sum(steps)


def replay_bfs_supersteps(g, src, frac, policy):
    """Per-superstep exchange volumes of `rtd.bfs_levels_1d` (the
    iterateInBFS expansion): per level, the changed entries are exactly
    the newly visited vertices."""
    n = g.num_nodes
    block = -(-n // P)
    n_pad = block * P
    level = _pad(bfs_levels_ref(g, src).astype(np.int64), n_pad, -1)
    depth = int(level.max())
    steps = []
    for lvl in range(1, depth + 2):   # loop runs until no new vertices
        newly = level == lvl
        steps.append(_exchange_vol(_shard_counts(newly, block),
                                   n_pad, block, frac, policy))
    return steps, n_pad + sum(steps)   # + the initial full gather


# --------------------------------------------------------------------------
# the measured side
# --------------------------------------------------------------------------

def _bfs_runner(g, mesh, policy, frac):
    """Drive `rtd.bfs_levels_1d` (the kernel the iterateInBFS construct
    calls) directly under shard_map — the pure BFS workload, with the
    returned gathered-element counter."""
    from jax.sharding import PartitionSpec as PS

    from repro.core import runtime_dist as rtd
    gd = rtd.prepare_graph_1d(g, mesh)
    n_pad = int(gd["own_ids"].size)
    specs = rtd.partition_specs(gd, mesh)

    def body(gd_, root_):
        return rtd.bfs_levels_1d(
            gd_["esrc"][0], gd_["edst"][0], gd_["evalid"][0],
            gd_["isrc"][0], gd_["idst_local"][0], gd_["ivalid"][0],
            gd_["own_ids"][0], root_, n_pad,
            frontier=policy, gather_frac=frac,
            direction="auto", threshold_frac=1.0 / 16.0)

    fn = jax.jit(jax.shard_map(body, mesh=mesh,
                               in_specs=(specs, PS()),
                               out_specs=(PS("data"), PS(), PS()),
                               check_vma=False))
    return lambda root: fn(gd, root)


def bench_family(name, g, mesh, src, reps, results):
    fam = {"num_nodes": g.num_nodes, "num_edges": g.num_edges,
           "num_shards": P, "workloads": {"sssp": {}, "bfs": {}}}
    for policy in POLICIES:
        sched = Schedule(dist_frontier=policy)

        # --- SSSP: the whole generated distributed program ---------------
        prog = compile_bundled("sssp", backend="distributed", schedule=sched)
        bound = prog.bind(g, mesh=mesh)
        us, out = _timeit_us(lambda: bound(src=src), reps=reps)
        measured = int(out["_gather_elems"])
        per_step, replayed = replay_sssp_supersteps(
            g, src, sched.dist_gather_frac, policy)
        fam["workloads"]["sssp"][policy] = {
            "wall_ms": round(us / 1e3, 3),
            "gather_elems_device": measured,
            "gather_elems_replayed": replayed,
            "counter_matches_replay": measured == replayed,
            "per_superstep": per_step,
            "supersteps": len(per_step),
        }
        print(f"[{name}/sssp] {policy:8s} wall={us / 1e3:9.2f}ms"
              f"  elems={measured} (replay {replayed})  steps={len(per_step)}")

        # --- BFS: the runtime kernel iterateInBFS lowers to ---------------
        run = _bfs_runner(g, mesh, policy, sched.dist_gather_frac)
        us, (_, _, elems) = _timeit_us(run, np.int32(src), reps=reps)
        measured = int(elems)
        per_step, replayed = replay_bfs_supersteps(
            g, src, sched.dist_gather_frac, policy)
        fam["workloads"]["bfs"][policy] = {
            "wall_ms": round(us / 1e3, 3),
            "gather_elems_device": measured,
            "gather_elems_replayed": replayed,
            "counter_matches_replay": measured == replayed,
            "per_superstep": per_step,
            "supersteps": len(per_step),
        }
        print(f"[{name}/bfs ] {policy:8s} wall={us / 1e3:9.2f}ms"
              f"  elems={measured} (replay {replayed})  steps={len(per_step)}")

    for work in ("sssp", "bfs"):
        w = fam["workloads"][work]
        w["volume_ratio_auto_vs_dense"] = round(
            w["auto"]["gather_elems_device"]
            / max(w["dense"]["gather_elems_device"], 1), 4)
    results["families"][name] = fam


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI-sized graph + reps (no JSON emitted)")
    args = ap.parse_args()
    assert len(jax.devices()) >= P, "expected 8 forced host devices"
    mesh = dist.make_mesh_1d(P)

    if args.tiny:
        fams = {"powerlaw": preferential_attachment(800, m=6, seed=1)}
        reps = 1
    else:
        fams = {"powerlaw": preferential_attachment(12000, m=8, seed=1)}
        reps = 3

    results = {"backend": jax.default_backend(), "num_shards": P,
               "config": {"tiny": args.tiny, "reps": reps},
               "note": ("gathered elements = property-exchange volume per "
                        "device; the push-combine volume is policy-"
                        "invariant and excluded. On CPU host devices the "
                        "collectives are memcpys, so wall-clock tracks "
                        "compute more than volume."),
               "families": {}}
    for name, g in fams.items():
        bench_family(name, g, mesh, src=0, reps=reps, results=results)

    for work in ("sssp", "bfs"):
        w = results["families"]["powerlaw"]["workloads"][work]
        assert all(w[p]["counter_matches_replay"] for p in POLICIES), (
            f"{work}: device counter disagrees with the host replay")
        print(f"{work}: volume auto/dense = {w['volume_ratio_auto_vs_dense']}"
              f"  (device counter == host replay for all policies)")
    if not args.tiny:
        with open(OUT_PATH, "w") as f:
            json.dump(results, f, indent=2)
        print(f"wrote {os.path.normpath(OUT_PATH)}")


if __name__ == "__main__":
    use_persistent_cache()
    main()
