"""Benchmark entry point: one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV (see DESIGN.md §8 for the mapping).

    PYTHONPATH=src python -m benchmarks.run [--quick]
"""
import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller datasets (CI-scale)")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from . import bench_table5, bench_construction, bench_sweeps, bench_kernels

    print("name,us_per_call,derived")
    if args.quick:
        bench_table5.run(n1=50_000, n2=30_000)
        bench_construction.run(sizes=(20_000, 50_000))
        bench_kernels.run(n=50_000)
    else:
        bench_table5.run()
        bench_construction.run()
        bench_sweeps.run()
        bench_kernels.run()


if __name__ == '__main__':
    main()
