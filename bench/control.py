"""A control of one cell: the plain reference, broken as a tempting
change would break it, put in the program's place. `bfloat16` stores its
values in bfloat16, the next precision below float32 and a 16-bit step
below int32; `int16` (SSSP) holds distances in int16; `stopped_short`
(SSSP) stops one superstep before the fixed point. The rest of a run is the
benchmark's own (the chip, the edge list from the seed, the window, the
comparison), and its `correct` has to come out false. Prints one result
line per seed.

    python3 bench/control.py --workload <cell> --control bfloat16 --seeds 11 12 13
"""
import argparse
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


@contextlib.contextmanager
def control_in_place(workload: str, control: str, root: str = harness.ROOT):
    """Make `harness.run_cell` run the control instead of the program while
    the block runs."""
    saved = {step: getattr(harness, step)
             for step in ("build_graph", "prepare_graph", "bind_program")}
    bench = harness.load_json(os.path.join(root, "BENCHMARK.json"))
    cell = harness.find_cell(bench, workload)
    traffic = harness.load_json(os.path.join(harness.BENCH_DIR, "traffic",
                                             f"{cell['traffic']}.json"))
    program = harness.load_module("programs", traffic["program"])
    reference = harness.load_module("references", traffic["program"])
    harness.build_graph = lambda repro, edges: edges
    harness.prepare_graph = lambda repro, prog, edges, mesh=None: None
    harness.bind_program = lambda repro, prog, edges, mesh=None: program.CONTROLS[control](
        reference, edges, traffic)
    try:
        yield
    finally:
        for step, fn in saved.items():
            setattr(harness, step, fn)


def main(argv=None, root: str = harness.ROOT) -> int:
    ap = argparse.ArgumentParser(description="Run a cell's lower-precision control.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--control", default="bfloat16")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    for seed in args.seeds:
        try:
            with control_in_place(args.workload, args.control, root):
                line = harness.run_cell(argparse.Namespace(
                    workload=args.workload, seed=seed, seconds=0.0, trace=0),
                    root=root, t_start=time.perf_counter())
        except harness.NoDevice as e:
            print(f"control: {e}", file=sys.stderr)
            return 2
        print(json.dumps({"control": args.control, "workload": args.workload, "seed": seed,
                          "correct": line["correct"], "checks": line["checks"],
                          "info": line["info"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
