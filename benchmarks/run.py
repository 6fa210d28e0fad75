"""Benchmark harness — one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV (paper Tables 3/4/5/6 + Fig. 17)."""
import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="table3|table4|table5|table6|fig17")
    ap.add_argument("--quick", action="store_true",
                    help="small graph subset (CI-speed)")
    args = ap.parse_args()

    from repro.xla_cache import use_persistent_cache
    use_persistent_cache()

    from .common import header
    from . import (bench_fig17_scaling, bench_table3_openmp,
                   bench_table4_scheduling, bench_table5_mpi,
                   bench_table6_cuda)

    graphs = None
    if args.quick:
        from repro.graph import load_suite
        graphs = load_suite(["PK", "US", "UR"])

    header()
    failed = []
    tables = {
        "table3": lambda: bench_table3_openmp.run(graphs),
        "table4": lambda: bench_table4_scheduling.run(graphs),
        "table5": lambda: bench_table5_mpi.run(graphs),
        "table6": lambda: bench_table6_cuda.run(graphs),
        "fig17": lambda: bench_fig17_scaling.run(graphs),
    }
    for name, fn in tables.items():
        if args.only and name != args.only:
            continue
        print(f"# --- {name} ---", file=sys.stderr)
        try:
            fn()
        except Exception as e:  # keep the harness going; report the failure
            print(f"{name}/HARNESS_ERROR,,{type(e).__name__}: {e}")
            failed.append(name)
    if failed:
        sys.exit(f"failed tables: {', '.join(failed)}")


if __name__ == "__main__":
    main()
