"""Benchmark driver: one function per paper table + kernel + roofline.

Prints ``name,us_per_call,derived`` CSV sections. Roofline rows are read
from the dry-run artifacts when present (run ``python -m
repro.launch.dryrun`` first for the full 33-cell table).
"""
from __future__ import annotations

import time


def _section(title):
    print(f"\n## {title}")


def main() -> None:
    t0 = time.perf_counter()

    _section("table3_breakdown (paper Table III)")
    from benchmarks import table3_breakdown
    table3_breakdown.main()

    _section("table1_comparison (paper Table I)")
    from benchmarks import table1_comparison
    table1_comparison.main()

    _section("kernel_bench (SNE lif_scan + CUTIE ternary_matmul)")
    from benchmarks import kernel_bench
    kernel_bench.main()

    _section("roofline (from dry-run artifacts)")
    from benchmarks import roofline
    try:
        rows = roofline.load_all()
        if not rows:
            print("no dry-run artifacts found; run "
                  "`PYTHONPATH=src python -m repro.launch.dryrun`")
        for r in rows:
            print(f"{r['arch']}__{r['shape']},0,"
                  f"dominant={r['dominant']};frac="
                  f"{r['roofline_fraction']:.3f};useful="
                  f"{r['useful_ratio']:.2f}")
    except Exception as e:
        print(f"roofline unavailable: {e}")

    print(f"\n# benchmarks done in {time.perf_counter() - t0:.1f}s")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
