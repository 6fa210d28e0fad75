"""Paper Fig. 17 analogue: scaling with parallelism (threads → devices).
Runs the distributed SSSP/PR on 1/2/4/8 host devices in subprocesses and
reports the scaling curve."""
from __future__ import annotations

import json
import os
import subprocess
import sys

from .common import row

_SCRIPT = r"""
import json, time, sys
import numpy as np, jax
from repro.core import compile_bundled, dist
from repro.graph import load_suite

nd = int(sys.argv[1])
mesh = dist.make_mesh_1d(nd)
g = load_suite(["LJ"])["LJ"]

def timeit(fn, reps=3):
    fn(); ts = []
    for _ in range(reps):
        t0 = time.perf_counter(); jax.block_until_ready(fn()); ts.append(time.perf_counter()-t0)
    return min(ts)*1e6

out = {}
p = compile_bundled("sssp", backend="distributed")
out["sssp"] = timeit(lambda: dist.run(p, g, mesh, src=0)["dist"])
p = compile_bundled("pr", backend="distributed")
out["pr"] = timeit(lambda: dist.run(p, g, mesh, beta=1e-4, delta=0.85, maxIter=50)["pageRank"])
print("RESULTS:" + json.dumps(out))
"""


def run(graphs=None):
    base = {}
    for nd in (1, 2, 4, 8):
        env = dict(os.environ)
        # a CPU host-device study: the child must never claim the parent's chip
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={nd}"
        env.setdefault("PYTHONPATH", "src")
        proc = subprocess.run([sys.executable, "-c", _SCRIPT, str(nd)], env=env,
                              capture_output=True, text=True, timeout=900)
        if proc.returncode != 0:
            raise RuntimeError(
                f"fig17 child ({nd} devices) failed: {proc.stderr[-300:]}")
        res = json.loads([l for l in proc.stdout.splitlines()
                          if l.startswith("RESULTS:")][0][len("RESULTS:"):])
        for alg, us in res.items():
            if nd == 1:
                base[alg] = us
            row(f"fig17/{alg}/devices={nd}", us,
                f"speedup={base.get(alg, us)/us:.2f}")
