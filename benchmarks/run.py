"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Usage:
    PYTHONPATH=src python -m benchmarks.run [--full]

The collective-traffic bench needs 8 fake CPU devices and so a process of
its own: ``python -m benchmarks.bench_collectives``.
"""
import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="include the slower coordinator-resource bench "
                         "sizes")
    args = ap.parse_args()

    from benchmarks import (bench_costmodel, bench_fig3, bench_fig4,
                            bench_table1, bench_table2)
    print("name,us_per_call,derived")
    mods = [bench_costmodel, bench_table1, bench_fig3, bench_fig4,
            bench_table2]
    for mod in mods:
        for name, us, derived in mod.run():
            print(f"{name},{us:.1f},{derived}")


if __name__ == '__main__':
    main()
