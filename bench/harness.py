"""The benchmark harness: one cell, one run, one result line.

Everything that belongs to one configuration, traffic mix, program or
metric sits in a file of its own, found by name:

    BENCHMARK.json               cells, metrics, bounds
    bench/configs/<config>.json  the deployment: generator and its parameters,
                                 and the backend the graph lies on
    bench/graphs/<generator>.py  `generate(params, seed)` -> edge list
    bench/traffic/<traffic>.json the mix: program, parameters, loop, limits
    bench/programs/<program>.py  drives one bundled program: inputs, warm-up,
                                 outputs, reference check, work in bytes
    bench/references/<program>.py  plain float64 numpy/scipy reference
    bench/loops/<loop>.py        the measured window -> end-to-end metrics
    bench/metrics/<metric>.py    `read(ctx)` -> one per-layer number or None
    bench/peaks.json             device peaks keyed by `device_kind`

A configuration's `backend` is `local` (the default: the whole graph on
one chip) or `distributed` (the graph partitioned 1-D over a mesh of the
cell's chips, one `shard_map` program exchanging vertex state by
collectives).

A run: find the chip; make the edge list from the seed; build the graph
and its derived views (the 1-D partition on `distributed`), compile, bind
and warm up the program (all of it `setup_s`); measure
the window; optionally read the profiler's trace of the window; free the
device; compare every output of the window with the reference.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, os.path.join(BENCH_DIR, "lib"))

# compile events JAX reports; none may fall inside the measured window
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


class NoDevice(RuntimeError):
    """No accelerator, too few chips, or a device with no known peaks."""


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """`bench/<kind>/<name>.py`, imported by path."""
    path = os.path.join(BENCH_DIR, kind, f"{name}.py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks_for(kind: str) -> dict:
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"))
    if kind not in table["devices"]:
        raise NoDevice(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table["devices"][kind]


def require_accelerator(chips: int):
    """The first `chips` TPU devices; anything else is an error."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX found {len(devs)} {devs[0].platform} device(s)")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def use_compile_cache(root: str) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where `JAX_COMPILATION_CACHE_DIR` says), for every program."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def import_program(root: str):
    """The system under test, from `<root>/src`."""
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise FileNotFoundError(f"the program is missing: no {src}/repro")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro.core
    import repro.graph
    return repro


class Spans:
    """Host-clock spans of the benchmark's own phases. Each is also a
    `TraceAnnotation`, so a traced run sees it on the trace's clock."""

    def __init__(self):
        self.seconds: dict = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            yield
        self.seconds[name] = self.seconds.get(name, 0.0) + time.perf_counter() - t0


class CompileCounter:
    """Counts JAX compile and trace events while `on`."""

    def __init__(self):
        import jax
        self.on, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event, duration, **_):
        if self.on and event in COMPILE_EVENTS:
            self.count += 1


# -- steps a test may replace to plant a fault ----------------------------------

def build_graph(repro, edges: dict):
    """The program's own graph build (host views)."""
    return repro.graph.from_edges(edges["n"], edges["src"], edges["dst"], edges["w"],
                                  undirected=edges["undirected"],
                                  drop_self_loops=edges["drop_self_loops"])


def prepare_graph(repro, prog, g, mesh=None):
    """The graph's derived views for `prog` (host views): on the
    distributed backend, the 1-D partition placed over `mesh`."""
    repro.core.prepare(g, program=prog, mesh=mesh)


def bind_program(repro, prog, g, mesh=None):
    """The program's entry, bound to the graph: `bound(**params)`."""
    return prog.bind(g, mesh=mesh)


# -------------------------------------------------------------------------------

def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload named {name!r} in BENCHMARK.json")


def cell_metrics(bench: dict, cell: str, kind: str) -> list:
    return [m for m in bench[kind] if cell in m.get("workloads", [cell])]


def device_info(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def make_mesh(backend: str, devices):
    """The 1-D mesh over exactly the cell's devices, or None on a backend
    that runs on one device."""
    if backend != "distributed":
        return None
    from repro.core import dist, runtime_dist
    return dist.make_mesh((len(devices),), (runtime_dist.AXIS,), devices=devices)


def compiled_text(bound, params: dict) -> str:
    """The optimised HLO of the program the window ran, which names the
    fused computations the trace's ops call (from the persistent cache):
    the bound program's own `lower` where it has one; else, on the
    distributed backend, its `shard_map` runner lowered on the partition
    it holds; else the jitted function lowered on the graph."""
    if hasattr(bound, "lower"):
        return bound.lower(**params).compile().as_text()
    prog = getattr(bound, "program", None)
    if getattr(prog, "backend", None) == "distributed":
        from repro.core import dist
        names = tuple(n for n, v in params.items() if v is not None)
        fn = dist._runner(prog, bound._gd, bound.mesh, names, prog.dist_meta or {})
        return fn.lower(bound._gd, *(params[n] for n in names)).compile().as_text()
    fn = getattr(prog, "fn", None)
    if not hasattr(fn, "lower"):
        return ""
    return fn.lower(bound.graph, **params).compile().as_text()


def memory_peaks(devices) -> list:
    """`peak_bytes_in_use` of each device that reports it."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    return [p for p in peaks if p is not None]


def run_cell(args, *, root: str, t_start: float) -> dict:
    """One run of one cell; returns the result line as a dict."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cell = find_cell(bench, args.workload)
    config_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(root, config_entry["file"]))
    backend = config.get("backend", "local")
    traffic = load_json(os.path.join(BENCH_DIR, "traffic", f"{cell['traffic']}.json"))

    spans = Spans()
    with spans("start"):                 # JAX, the chip and the program's modules
        devices = require_accelerator(cell["chips"])
        device = device_info(devices)
        peaks = peaks_for(device["kind"])
        use_compile_cache(root)
        repro = import_program(root)
    import jax

    generator = load_module("graphs", config["generator"])
    program = load_module("programs", traffic["program"])
    loop = load_module("loops", traffic["loop"])
    compiles = CompileCounter()

    with spans("generate"):
        edges = generator.generate(config["params"], args.seed)
    plan = program.plan(edges, traffic, args.seed)
    mesh = make_mesh(backend, devices)
    with spans("compile"):
        prog = repro.core.compile_bundled(program.BUNDLED, backend=backend)
    with spans("graph_build"):
        g = build_graph(repro, edges)
        prepare_graph(repro, prog, g, mesh)
        jax.block_until_ready(g)
    with spans("compile"):
        bound = bind_program(repro, prog, g, mesh)
        jax.block_until_ready(bound(**plan["warmup"]))
    setup_s = time.perf_counter() - t_start

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    compiles.on = True
    window = loop.measure(bound, plan["inputs"], args.seconds, spans)
    compiles.on = False
    if trace_dir:
        jax.profiler.stop_trace()

    trace = None
    if trace_dir:
        import trace_reader
        hlo = compiled_text(bound, plan["inputs"][0])
        trace = trace_reader.read_dir(trace_dir, [d.id for d in devices], hlo)
        shutil.rmtree(trace_dir, ignore_errors=True)
    chip_peaks = memory_peaks(devices)
    device["memory_peak_bytes"] = max(chip_peaks) if chip_peaks else None

    # the outputs to host, the program's state freed, then the reference
    outputs = [program.output(o) for o in window.pop("outputs")]
    del bound, g
    ref_edges = {k: edges[k] for k in ("n", "src", "dst", "w", "undirected",
                                        "drop_self_loops")}
    del edges
    reference = load_module("references", traffic["program"])
    t0 = time.perf_counter()
    ran = window.pop("ran")
    want = program.reference(reference, ref_edges, ran, traffic)
    check = program.compare(outputs, ran, want, traffic)
    reference_s = time.perf_counter() - t0

    attempted = len(outputs)
    failed = sum(1 for ok in check["ok_per_run"] if not ok)
    correct = attempted > 0 and failed == 0
    # a NaN or inf reading is printed as text: JSON has no such number
    checks = {k: {"value": v if math.isfinite(v) else repr(v), "limit": traffic["limits"][k]}
              for k, v in check["numbers"].items()}

    if args.trace:
        ctx = {"trace": trace, "spans": spans.seconds, "runs": window["runs"],
               "window": window, "peaks": peaks, "work_bytes": program.work_bytes(
                   ref_edges["n"], want["num_edges"], want, ran, traffic)}
        metrics = {}
        for m in cell_metrics(bench, cell["name"], "per_layer"):
            value = load_module("metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        window["info"].update(class_s=trace["class_s"], control_ops=trace["control_ops"],
                              collective_exposed_s=trace["collective_exposed_s"])
    else:
        values = dict(window["metrics"], setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell_metrics(bench, cell["name"], "end_to_end")
                   if m["name"] in values}

    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device}
    if args.trace:
        line["breakdown"] = trace["breakdown"]
    line["info"] = {"workload": cell["name"], "seed": args.seed,
                    "setup_s": setup_s, "spans_s": spans.seconds,
                    "window_compiles": compiles.count, "reference_s": reference_s,
                    "memory_peak_bytes_per_chip": chip_peaks,
                    **window["info"], **check["info"]}
    line["checks"] = checks
    return line


def main(argv=None, *, t_start: float | None = None, root: str = ROOT) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        line = run_cell(args, root=root, t_start=t_start)
    except (NoDevice, FileNotFoundError) as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    print(f"correct = {line['correct']} ({line['failed']} of "
          f"{line['attempted']} runs failed)", file=sys.stderr)
    for name, c in line["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
