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
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


def put_control_in_place(workload: str, seed: int, control: str,
                         root: str = harness.ROOT) -> None:
    """Make `harness.run_cell` run the control instead of the program."""
    bench = harness.load_json(os.path.join(root, "BENCHMARK.json"))
    cell = harness.find_cell(bench, workload)
    traffic = harness.load_json(os.path.join(harness.BENCH_DIR, "traffic",
                                             f"{cell['traffic']}.json"))
    program = harness.load_module("programs", traffic["program"])
    reference = harness.load_module("references", traffic["program"])
    harness.build_graph = lambda repro, edges: edges
    harness.bind_program = lambda repro, prog, edges: program.CONTROLS[control](
        reference, edges, traffic)


def main(argv=None, root: str = harness.ROOT) -> int:
    ap = argparse.ArgumentParser(description="Run a cell's lower-precision control.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--control", default="bfloat16")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    build, bind = harness.build_graph, harness.bind_program
    for seed in args.seeds:
        put_control_in_place(args.workload, seed, args.control, root)
        try:
            line = harness.run_cell(argparse.Namespace(
                workload=args.workload, seed=seed, seconds=0.0, trace=0),
                root=root, t_start=time.perf_counter())
        except harness.NoDevice as e:
            print(f"control: {e}", file=sys.stderr)
            return 2
        finally:
            harness.build_graph, harness.bind_program = build, bind
        print(json.dumps({"control": args.control, "workload": args.workload, "seed": seed,
                          "correct": line["correct"], "checks": line["checks"],
                          "info": line["info"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
