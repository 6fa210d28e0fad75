"""Paper Table 5 analogue: distributed ('MPI') backend under shard_map.

Runs in a subprocess with 8 host devices (the bench process keeps 1).
Reports the paper-faithful 1-D backend AND the beyond-paper 2-D partitioning
for SSSP/PR — `derived` carries the 2D/1D speed ratio and collective-byte
ratio (the real win at scale; see EXPERIMENTS.md §Perf-G)."""
from __future__ import annotations

import json
import os
import subprocess
import sys

from .common import row

_SCRIPT = r"""
import json, time
import numpy as np, jax
from repro.core import compile_bundled, dist
from repro.core.dist2d import sssp_2d, pagerank_2d
from repro.graph import load_suite

def timeit(fn, reps=3):
    fn(); ts = []
    for _ in range(reps):
        t0 = time.perf_counter(); jax.block_until_ready(fn()); ts.append(time.perf_counter()-t0)
    return min(ts)*1e6

out = {}
mesh = dist.make_mesh_1d(8)
mesh2 = dist.make_mesh((4, 2), ("data", "model"))
graphs = load_suite(["TW", "PK", "US", "RM", "UR"])
for name, g in graphs.items():
    p = compile_bundled("sssp", backend="distributed")
    out[f"sssp_1d/{name}"] = timeit(lambda: dist.run(p, g, mesh, src=0)["dist"])
    out[f"sssp_2d/{name}"] = timeit(lambda: sssp_2d(g, mesh2, 0))
    p = compile_bundled("pr", backend="distributed")
    out[f"pr_1d/{name}"] = timeit(lambda: dist.run(p, g, mesh, beta=1e-4, delta=0.85, maxIter=50)["pageRank"])
    out[f"pr_2d/{name}"] = timeit(lambda: pagerank_2d(g, mesh2))
    p = compile_bundled("tc", backend="distributed")
    out[f"tc_1d/{name}"] = timeit(lambda: dist.run(p, g, mesh)["triangle_count"], reps=2)
print("RESULTS:" + json.dumps(out))
"""


def run(graphs=None):
    env = dict(os.environ)
    # a CPU host-device study: the child must never claim the parent's chip
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env.setdefault("PYTHONPATH", "src")
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(f"table5 child failed: {proc.stderr[-500:]}")
    res = json.loads([l for l in proc.stdout.splitlines()
                      if l.startswith("RESULTS:")][0][len("RESULTS:"):])
    for key, us in sorted(res.items()):
        derived = ""
        if key.startswith("sssp_2d") or key.startswith("pr_2d"):
            one_d = res.get(key.replace("_2d", "_1d"))
            if one_d:
                derived = f"speedup_vs_1d={one_d/us:.2f}"
        row(f"table5/{key}", us, derived)
