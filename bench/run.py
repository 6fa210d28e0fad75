"""Run one benchmark cell once on the accelerator and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. `BENCHMARK.json` names the cells; each
cell's configuration, traffic mix and metrics live in files of their own
under `bench/` (see `bench/harness.py`). The last line of standard output
is one JSON object: `correct`, `attempted`, `failed`, `metrics`, `device`
(and with `--trace 1`, `breakdown`), then `checks`, the numbers compared
with the plain reference beside their limits. Without a TPU, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()   # set-up is timed from here

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], t_start=T_START))
