"""Tests of the benchmark itself, on the CPU at small sizes:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

`tiny_root` is a benchmark root with the real `BENCHMARK.json` cells whose
configurations are cut to a few hundred vertices, and one more cell,
`DIST_CELL`: the first configuration on the distributed backend over four
devices, under PageRank's traffic. `drive` runs one cell through the
harness with the chip check replaced, as a planted fault needs. JAX's CPU
backend is given four devices, so the distributed cell runs real
collectives over a real mesh."""
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# before JAX starts its backends
_FLAG = "--xla_force_host_platform_device_count=4"
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = f"{os.environ.get('XLA_FLAGS', '')} {_FLAG}".strip()

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), os.path.join(BENCH, "lib"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

import harness  # noqa: E402

TINY = {"scale": 9}
DIST_CELL = "tiny.dist-pr"


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench-root")
    os.symlink(os.path.join(ROOT, "src"), root / "src")
    (root / "configs").mkdir()
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for c in bench["configs"]:
        cfg = harness.load_json(os.path.join(ROOT, c["file"]))
        for key, value in TINY.items():
            if key in cfg["params"]:
                cfg["params"][key] = value
        c["file"] = f"configs/{c['name']}.json"
        (root / c["file"]).write_text(json.dumps(cfg))
    dist = dict(bench["configs"][0], name="tiny-dist", file="configs/tiny-dist.json")
    cfg = json.loads((root / bench["configs"][0]["file"]).read_text())
    (root / dist["file"]).write_text(json.dumps(dict(cfg, name="tiny-dist",
                                                     backend="distributed")))
    bench["configs"].append(dist)
    bench["workloads"].append({"name": DIST_CELL, "config": "tiny-dist",
                               "traffic": "pr-loop", "chips": 4, "why": "tests"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


@pytest.fixture
def drive(tiny_root, monkeypatch):
    """drive(cell, seed, trace=0) -> the result line, run on the CPU."""
    import argparse
    import time

    import jax
    v5e = harness.peaks_for("TPU v5 lite")
    monkeypatch.setattr(harness, "require_accelerator", lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(harness, "peaks_for", lambda kind: v5e)

    def run(cell, seed=2**31 + 7, seconds=0.05):
        args = argparse.Namespace(workload=cell, seed=seed, seconds=seconds, trace=0)
        return harness.run_cell(args, root=tiny_root, t_start=time.perf_counter())
    return run
